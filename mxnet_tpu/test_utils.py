"""mx.test_utils.

Reference parity: python/mxnet/test_utils.py — assert_almost_equal (:656,
dtype-aware tolerances), check_numeric_gradient (:1044 finite differences),
check_consistency (:1491 cross-device oracle), environment helpers. These
are the kernel-correctness oracles the whole reference test suite leans on
(SURVEY §4); the TPU analog of check_consistency runs the same function on
cpu and the accelerator backend.
"""
from __future__ import annotations

import contextlib
import os

import numpy as onp

from .base import MXNetError
from .numpy.multiarray import ndarray

_DTYPE_TOL = {
    onp.dtype("float16"): (1e-2, 1e-2),
    onp.dtype("float32"): (1e-4, 1e-5),
    onp.dtype("float64"): (1e-6, 1e-8),
}


def default_rtol_atol(*arrays):
    rtol, atol = 1e-5, 1e-7
    for a in arrays:
        dt = onp.dtype(str(a.dtype)) if str(a.dtype) != "bfloat16" else None
        if dt is None:
            return (1e-2, 1e-2)
        if dt in _DTYPE_TOL:
            r, t = _DTYPE_TOL[dt]
            rtol, atol = max(rtol, r), max(atol, t)
    return rtol, atol


def _to_np(a):
    if isinstance(a, ndarray):
        return a.asnumpy()
    return onp.asarray(a)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Reference: test_utils.py:656."""
    a_np, b_np = _to_np(a), _to_np(b)
    if rtol is None or atol is None:
        r, t = default_rtol_atol(a_np if not hasattr(a, "dtype") else a,
                                 b_np if not hasattr(b, "dtype") else b)
        rtol = rtol if rtol is not None else r
        atol = atol if atol is not None else t
    onp.testing.assert_allclose(a_np.astype(onp.float64),
                                b_np.astype(onp.float64),
                                rtol=rtol, atol=atol, equal_nan=equal_nan,
                                err_msg=f"{names[0]} vs {names[1]}")


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    try:
        assert_almost_equal(a, b, rtol, atol, equal_nan=equal_nan)
        return True
    except AssertionError:
        return False


def check_numeric_gradient(f, inputs, grads=None, eps=1e-3, rtol=1e-2,
                           atol=1e-4):
    """Finite-difference gradient check (reference: test_utils.py:1044).

    f: callable(list of ndarrays) -> scalar ndarray. inputs: list of
    ndarrays with attach_grad() to compare against; if grads is given, it is
    the list of analytic grads instead.
    """
    from . import autograd
    from .numpy import array

    if grads is None:
        for x in inputs:
            x.attach_grad()
        with autograd.record():
            out = f(inputs)
        out.backward()
        grads = [x.grad.asnumpy() for x in inputs]

    for xi, x in enumerate(inputs):
        base = x.asnumpy().astype(onp.float64)
        num_grad = onp.zeros_like(base)
        flat = base.reshape(-1)
        ng_flat = num_grad.reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            xs = list(inputs)
            xs[xi] = array(base.reshape(x.shape).astype(onp.float32))
            fp = float(f(xs).asnumpy().sum())
            flat[i] = orig - eps
            xs[xi] = array(base.reshape(x.shape).astype(onp.float32))
            fm = float(f(xs).asnumpy().sum())
            flat[i] = orig
            ng_flat[i] = (fp - fm) / (2 * eps)
        onp.testing.assert_allclose(grads[xi], num_grad, rtol=rtol, atol=atol,
                                    err_msg=f"input {xi} gradient mismatch")


def check_consistency(fn, inputs, ctx_list=None, rtol=1e-4, atol=1e-5):
    """Run fn on several backends and compare (reference: test_utils.py:1491
    — the cross-device kernel oracle). ctx_list defaults to [cpu, default]."""
    import jax
    from .numpy import array
    results = []
    platforms = ["cpu"]
    if jax.devices()[0].platform != "cpu":
        platforms.append(jax.devices()[0].platform)
    for plat in platforms:
        dev = jax.devices(plat)[0]
        placed = [array(x.asnumpy() if isinstance(x, ndarray) else x)
                  for x in inputs]
        with jax.default_device(dev):
            results.append(fn(placed))
    for r in results[1:]:
        assert_almost_equal(results[0], r, rtol, atol)
    return results


@contextlib.contextmanager
def environment(*args):
    """Scoped env vars (reference: test_utils.py environment)."""
    if len(args) == 2:
        updates = {args[0]: args[1]}
    else:
        updates = args[0]
    old = {k: os.environ.get(k) for k in updates}
    try:
        for k, v in updates.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def rand_ndarray(shape, dtype="float32", scale=1.0):
    from .numpy import random as npr
    return npr.uniform(-scale, scale, size=shape, dtype=dtype)


def rand_shape_nd(ndim, dim=10):
    return tuple(onp.random.randint(1, dim + 1, size=ndim).tolist())


def same(a, b):
    return onp.array_equal(_to_np(a), _to_np(b))


def effective_dtype(x):
    return x.dtype


def default_context():
    from .context import current_context
    return current_context()


def set_default_context(ctx):
    ctx.__enter__()


def list_gpus():
    from .context import num_gpus
    return list(range(num_gpus()))


def assert_allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=True):
    """numpy-style allclose assert (reference test_utils.py
    assert_allclose, a thin alias the op suites use)."""
    onp.testing.assert_allclose(_to_np(a), _to_np(b), rtol=rtol, atol=atol,
                                equal_nan=equal_nan)


def assert_exception(f, exception_type, *args, **kwargs):
    """Assert calling f raises exception_type (reference
    test_utils.py assert_exception)."""
    try:
        f(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError(
        f"{f} did not raise {exception_type.__name__}")


def rand_shape_2d(dim0=10, dim1=10):
    return (onp.random.randint(1, dim0 + 1),
            onp.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (onp.random.randint(1, dim0 + 1),
            onp.random.randint(1, dim1 + 1),
            onp.random.randint(1, dim2 + 1))


def rand_coord_2d(x_low, x_high, y_low, y_high):
    x = onp.random.randint(x_low, x_high)
    y = onp.random.randint(y_low, y_high)
    return x, y


def random_arrays(*shapes):
    """Random float32 host arrays; a single shape returns one array.
    A shape may be a tuple/list, an int (1-D length), or () for a
    0-d scalar (reference test_utils.py random_arrays)."""
    def one(s):
        if isinstance(s, int):
            s = (s,)
        elif not isinstance(s, (list, tuple)):
            raise MXNetError(f"shape must be int or tuple, got {s!r}")
        if len(s) == 0:
            return onp.asarray(onp.random.randn(), "float32")
        return onp.random.randn(*s).astype("float32")

    arrays = [one(s) for s in shapes]
    return arrays[0] if len(arrays) == 1 else arrays


def random_sample(population, k):
    """Sample WITHOUT replacement, order preserved by draw (reference
    test_utils.py random_sample)."""
    import random as _random_mod
    return _random_mod.sample(list(population), k)


def same_array(a, b):
    """True when two mx arrays alias one device buffer (reference
    test_utils.py same_array — it mutates to prove aliasing; device
    buffers are immutable here, so compare the underlying buffer
    identity instead)."""
    ra = a._data if isinstance(a, ndarray) else a
    rb = b._data if isinstance(b, ndarray) else b
    return ra is rb


def check_speed(f, *args, n=20, warmup=3, **kwargs):
    """Average seconds per call (reference test_utils.py check_speed);
    syncs via engine.wait_all so async dispatch doesn't flatter."""
    import time

    from . import engine
    for _ in range(warmup):
        f(*args, **kwargs)
    engine.wait_all()
    t0 = time.perf_counter()
    for _ in range(n):
        f(*args, **kwargs)
    engine.wait_all()
    return (time.perf_counter() - t0) / n


def gen_buckets_probs_with_ppf(ppf, num_buckets):
    """Equal-probability buckets from a percent-point function
    (reference test_utils.py gen_buckets_probs_with_ppf)."""
    probs = [1.0 / num_buckets] * num_buckets
    edges = [ppf(i / num_buckets) for i in range(num_buckets + 1)]
    buckets = [(edges[i], edges[i + 1]) for i in range(num_buckets)]
    return buckets, probs


def chi_square_check(generator, buckets, probs, nsamples=1000000):
    """Chi-square goodness-of-fit for an i.i.d. sampler (reference
    test_utils.py:2108). Returns (p_value, obs_freq, expected_freq).
    The survival function is gammaincc(df/2, chi2/2) (no scipy in this
    image; jax.scipy.special supplies the regularized gamma)."""
    from jax.scipy.special import gammaincc

    samples = onp.asarray(_to_np(generator(nsamples))).ravel()
    continuous = isinstance(buckets[0], (tuple, list))
    obs = onp.zeros(len(buckets))
    if continuous:
        # per-bucket low/high membership so samples in a gap between
        # non-contiguous buckets are excluded, not mis-tallied
        for i, (lo, hi) in enumerate(buckets):
            obs[i] = ((samples >= lo) & (samples < hi)).sum()
    else:
        for i, v in enumerate(buckets):
            obs[i] = (samples == v).sum()
    exp = onp.asarray(probs, "float64") * samples.size
    chi2 = float(((obs - exp) ** 2 / exp).sum())
    df = len(buckets) - 1
    p = float(gammaincc(df / 2.0, chi2 / 2.0))
    return p, obs, exp


def verify_generator(generator, buckets, probs, nsamples=1000000,
                     nrepeat=5, success_rate=0.25, alpha=0.05):
    """Repeat the chi-square test; pass when >= success_rate of the
    repeats clear alpha (reference test_utils.py verify_generator —
    RNG tests are statistical, single runs flake)."""
    ps = [chi_square_check(generator, buckets, probs, nsamples)[0]
          for _ in range(nrepeat)]
    successes = sum(p > alpha for p in ps)
    if successes / nrepeat < success_rate:
        raise AssertionError(
            f"generator failed the chi-square test: p values {ps} "
            f"(needed {success_rate:.0%} above alpha={alpha})")
    return ps
