"""mx.np.linalg (reference: python/mxnet/numpy/linalg.py over _npi linalg ops).

Lazily wraps jax.numpy.linalg; every function dispatches through _invoke so
autograd recording and async dispatch apply.

General (non-symmetric) eigendecomposition has no TPU lowering in XLA —
the reference kept exactly this family CPU-only too (LAPACK geev via
src/operator/numpy/linalg/np_eig.cc, FComputeEx on cpu). On accelerator
backends `eig`/`eigvals` run on the host: eagerly as a device→CPU→device
round-trip (exactly the reference's CPU-only FCompute cost), and under a
jit trace through `jax.pure_callback` (a PJRT runtime without host
callbacks raises on the traced call).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# names with no accelerator lowering: host round-trip like the reference
_HOST_ONLY = ("eig", "eigvals")


def _host_eig_impl(name, a):
    """Run numpy's geev on host, with stable complex output dtype.

    numpy returns a *real* array when every eigenvalue is real, so the
    result is cast to the promised complex dtype unconditionally.
    """
    import numpy as onp

    cdt = (jnp.complex128 if a.dtype in (jnp.float64, jnp.complex128)
           else jnp.complex64)
    n_batch = a.shape[:-2]
    w_spec = jax.ShapeDtypeStruct(n_batch + a.shape[-1:], cdt)
    v_spec = jax.ShapeDtypeStruct(a.shape, cdt)

    if name == "eig":
        def host(x):
            w, v = onp.linalg.eig(onp.asarray(x))
            return w.astype(cdt), v.astype(cdt)
        specs = (w_spec, v_spec)
    else:
        def host(x):
            return onp.linalg.eigvals(onp.asarray(x)).astype(cdt)
        specs = w_spec

    if isinstance(a, jax.core.Tracer):
        # inside a jit trace the host hop must be a callback op
        return jax.pure_callback(host, specs, a)
    # eager: plain round-trip; results live on the CPU backend, exactly
    # like the reference's CPU-only geev outputs lived on cpu context
    # (accelerator runtimes need not support complex storage at all)
    cpu = jax.devices("cpu")[0]
    out = host(jax.device_get(a))
    if name == "eig":
        return (jax.device_put(out[0], cpu), jax.device_put(out[1], cpu))
    return jax.device_put(out, cpu)


def __getattr__(name):
    if name.startswith("__"):
        raise AttributeError(name)
    target = getattr(jnp.linalg, name, None)
    if target is None:
        raise AttributeError(f"linalg has no attribute {name!r}")
    if callable(target):
        from .multiarray import _invoke

        if name in _HOST_ONLY:
            jnp_target = target

            def target(a, _name=name, _jnp=jnp_target):
                if jax.default_backend() == "cpu":
                    return _jnp(a)  # XLA has a CPU lowering; keep it
                return _host_eig_impl(_name, a)

            def op(*args, _name=name, _target=target, **kwargs):
                if jax.default_backend() != "cpu":
                    from .. import autograd
                    from .multiarray import ndarray, _wrap_out
                    if autograd.is_recording():
                        # geev has no gradient anywhere (reference
                        # np_eig.cc registers no backward; jax defines
                        # no eig JVP/JVP-of-callback) — under record()
                        # compute values OUTSIDE the tape rather than
                        # letting jax.vjp trace into the host hop.
                        # Tracer inputs (hybridized re-trace) route to
                        # pure_callback inside _host_eig_impl.
                        raws = [a._data if isinstance(a, ndarray) else a
                                for a in args]
                        return _wrap_out(_host_eig_impl(_name, *raws))
                return _invoke(_target, args, kwargs,
                               name=f"linalg.{_name}")
            op.__name__ = name
            globals()[name] = op
            return op

        def op(*args, **kwargs):
            return _invoke(target, args, kwargs, name=f"linalg.{name}")
        op.__name__ = name
        globals()[name] = op
        return op
    return target
