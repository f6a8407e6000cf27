"""mx.np ndarray: the framework's tensor.

Reference parity: python/mxnet/numpy/multiarray.py (class ndarray(NDArray) at
:272) over include/mxnet/ndarray.h + src/ndarray/ndarray.cc.

TPU-native design: an ndarray wraps a jax.Array. MXNet's Chunk (Storage handle
+ engine var + delayed alloc) maps onto the PJRT buffer a jax.Array owns;
MXNet's per-array engine variable + version maps onto JAX's async futures —
dispatch returns immediately, ``wait_to_read`` is ``block_until_ready``, and
the ``_version`` counter preserves the reference's versioned-var semantics for
in-place rebinding (``a[:] = ...`` swaps the underlying buffer, same wrapper).

Every op goes through ``_invoke``: unwrap -> jnp/lax primitive -> wrap, and
when ``autograd.record()`` is active and an input carries a tape entry, the
op's VJP closure is captured via ``jax.vjp`` (the analog of
Imperative::RecordOp, src/imperative/imperative.cc:235).
"""
from __future__ import annotations

import contextlib
import weakref

import jax
import jax.numpy as jnp
import numpy as onp
from jax import enable_x64 as _enable_x64

from .. import autograd
from .. import engine
from .. import fault as _fault
from .. import pipeline as _pipeline
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..base import MXNetError, np_dtype
from ..context import Context, current_context

__all__ = ["ndarray", "array", "zeros", "ones", "empty", "full", "arange",
           "linspace", "logspace", "eye", "identity", "zeros_like",
           "ones_like", "full_like", "empty_like", "fromnumpy", "from_dlpack",
           "newaxis", "pi", "e", "inf", "nan", "euler_gamma"]

newaxis = None
pi = onp.pi
e = onp.e
inf = onp.inf
nan = onp.nan
euler_gamma = onp.euler_gamma


_inexact_cache: dict = {}


def _is_inexact(x):
    # dispatch hot path: issubdtype walks the numpy type lattice every
    # call — memoize per dtype (a handful of distinct dtypes per process)
    dt = x.dtype
    r = _inexact_cache.get(dt)
    if r is None:
        r = _inexact_cache[dt] = bool(jnp.issubdtype(dt, jnp.inexact))
    return r


_64BIT = frozenset(("int64", "uint64", "float64", "complex128"))

# ops that jax.vjp cannot linearize fall back to record-without-grad
_VJP_FALLBACK_ERRORS = (TypeError, NotImplementedError,
                        jax.errors.TracerArrayConversionError,
                        jax.errors.ConcretizationTypeError)


def _wants_x64(dt):
    """True when a dtype spec names a 64-bit type that JAX's default
    32-bit canonicalization would truncate (the reference builds with
    MXNET_USE_INT64_TENSOR_SIZE; here 64-bit ops run in a scoped x64
    mode, see util.int64_tensor_size)."""
    if dt is None:
        return False
    try:
        return onp.dtype(dt).name in _64BIT
    except TypeError:
        return False


def _writeback(out, res):
    """Write an op result through an ``out=`` destination array.

    Reference: generated wrappers accept ``out`` and the engine writes the
    result into its buffer (python/mxnet/ndarray/register.py:171). Here the
    destination wrapper is rebound to the new buffer (cast to its dtype) so
    aliases observe the update; the autograd entry moves with it so
    recording through ``out=`` stays correct.
    """
    if out is None:
        return res
    if isinstance(out, (tuple, list)):
        if not isinstance(res, (tuple, list)) or len(res) != len(out):
            raise ValueError("out= arity does not match op outputs")
        return type(out)(_writeback(o, r) for o, r in zip(out, res))
    if not isinstance(out, ndarray):
        raise TypeError(f"out= must be an mxnet ndarray, got {type(out)}")
    if not isinstance(res, ndarray):
        raise TypeError("op returned a non-array; cannot write through out=")
    if tuple(out.shape) != tuple(res.shape):
        raise ValueError(
            f"out= shape mismatch: destination {out.shape} vs result {res.shape}")
    if isinstance(res._data, jax.core.Tracer) and \
            not isinstance(out._data, jax.core.Tracer):
        # a hybridized trace must not leak a tracer into a persistent
        # eager array (it would be corrupted forever)
        raise MXNetError(
            "out= cannot write a traced (hybridized) result into an array "
            "created outside the trace; allocate the destination inside "
            "the hybrid forward or drop out=")
    out._rebind(res._data.astype(out.dtype))
    out._entry = res._entry
    return out


def _wrap(raw, ctx=None):
    """Wrap a raw jax array into an ndarray without copying."""
    out = ndarray.__new__(ndarray)
    out._data = raw
    out._grad = None
    out._grad_req = "null"
    out._entry = None
    out._version = 0
    engine._track(raw)
    return out


def _wrap_out(out):
    """Wrap an op result which may be an array or a pytree of arrays."""
    if isinstance(out, (jnp.ndarray, jax.Array)):
        return _wrap(out)
    if isinstance(out, tuple) and hasattr(out, "_fields"):
        # NamedTuple results (jnp.linalg QRResult/SVDResult/...)
        return type(out)(*[_wrap_out(o) for o in out])
    if isinstance(out, (tuple, list)):
        return type(out)(_wrap_out(o) for o in out)
    return out


_profiler_mod = None
_amp_mod = None


def _invoke(prim, args, kwargs=None, name=None, x64=False):
    """Dispatch one op: the eager hot path.

    Reference analog: FFI glue -> Imperative::Invoke -> Engine::PushAsync
    (src/imperative/imperative.cc:49-140). Here: jnp call (async PJRT
    dispatch); under recording additionally capture the VJP with jax.vjp.
    When the profiler runs, every dispatch is recorded as a host span and
    an Xprof TraceAnnotation — the analog of the engine-integrated
    ProfileOperator (src/engine/threaded_engine.h:356-367).
    """
    global _profiler_mod
    _profiler = _profiler_mod
    if _profiler is None:  # late-bound once (import cycle at module load)
        from .. import profiler as _profiler
        _profiler_mod = _profiler
    if _profiler._state["running"] and _profiler._config["profile_imperative"]:
        with _profiler.span(name or getattr(prim, "__name__", "op"),
                            "operator"):
            out = _invoke_impl(prim, args, kwargs, name, x64)
    else:
        out = _invoke_impl(prim, args, kwargs, name, x64)
    # fault hook (disabled cost: one module-attr read + branch): every
    # dispatch probes invoke.nan_output; a hit turns the op's result into
    # all-NaN, emulating a kernel/overflow fault the trainer guard and
    # AMP scaler must absorb (docs/FAULT_TOLERANCE.md)
    if _fault._active and _fault.fire("invoke.nan_output"):
        _nan_corrupt(out)
    # telemetry hook, same disabled cost contract as the fault hook (the
    # CI telemetry stage bounds it at <2% of a tight eager loop)
    if _telemetry._active:
        _telemetry.inc("invoke.ops_total")
    return out


def _nan_corrupt(out):
    """Rebind the first inexact, concrete (non-tracer) output leaf to
    all-NaN.  Tracer leaves are left alone — corrupting a trace would
    bake the NaN into a compiled executable and replay it forever, which
    is not the transient fault being modeled."""
    leaves = jax.tree_util.tree_leaves(
        out, is_leaf=lambda x: isinstance(x, ndarray))
    for leaf in leaves:
        if isinstance(leaf, ndarray) and _is_inexact(leaf) \
                and not isinstance(leaf._data, jax.core.Tracer):
            leaf._rebind(jnp.full(leaf._data.shape, jnp.nan,
                                  leaf._data.dtype))
            return True
    return False


_64bit_cache: dict = {}


def _leaf_is_64bit(x):
    # dtype.name builds a python string per call — memoize per dtype
    dt = getattr(x, "dtype", None)
    if dt is None:
        return False
    r = _64bit_cache.get(dt)
    if r is None:
        r = _64bit_cache[dt] = getattr(dt, "name", "") in _64BIT
    return r


def _invoke_impl(prim, args, kwargs=None, name=None, x64=False):
    kwargs = kwargs or {}
    global _amp_mod
    _amp = _amp_mod
    if _amp is None:
        from .. import amp as _amp
        _amp_mod = _amp
    amp_dt = (_amp._op_cast_dtype(name or getattr(prim, "__name__", ""))
              if _amp.is_active() else None)
    # flat fast path (the eager hot loop, SURVEY §7 hard part #1): no
    # kwargs and no nested containers means tree_flatten/unflatten and
    # the container-aware closure are pure overhead
    if not kwargs and not any(isinstance(a, (tuple, list, dict))
                              for a in args):
        return _invoke_flat(prim, args, name, x64, amp_dt)
    leaves, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, ndarray))
    # differentiable inputs: inexact-dtype ndarrays; others are unwrapped
    # in place (bool masks / int indices stay concrete for eager indexing).
    # 64-bit dtype on an mx array input or an explicit dtype request ->
    # scoped x64 so JAX does not truncate (raw host-numpy operands do NOT
    # trigger it: numpy's default float64/int64 would otherwise drag every
    # mixed op into x64; they keep the 32-bit canonicalization).
    use_x64 = x64 or _wants_x64(kwargs.get("dtype"))
    arr_pos, diff_arrays = [], []
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, ndarray):
            use_x64 = use_x64 or _leaf_is_64bit(leaf)
            if _is_inexact(leaf):
                arr_pos.append(i)
                diff_arrays.append(leaf)
            else:
                leaves[i] = leaf._data

    def fn(*xs):
        if amp_dt is not None:
            # cast inside the traced fn: the cast's VJP upcasts cotangents
            # back to the caller's dtype, and _CachedGraph tracing re-enters
            # here so hybrid forward gets the same policy (amp.init()).
            xs = [x.astype(amp_dt)
                  if jnp.issubdtype(x.dtype, jnp.floating)
                  and x.dtype != amp_dt else x for x in xs]
        ls = list(leaves)
        for p, x in zip(arr_pos, xs):
            ls[p] = x
        a, kw = jax.tree_util.tree_unflatten(treedef, ls)
        return prim(*a, **kw)

    raws = [a._data for a in diff_arrays]
    recording = (autograd.is_recording()
                 and any(a._entry is not None for a in diff_arrays))
    x64_scope = _enable_x64(True) if use_x64 else contextlib.nullcontext()
    with x64_scope:
        if recording:
            try:
                out, vjp_fn = jax.vjp(fn, *raws)
            except _VJP_FALLBACK_ERRORS:
                recording = False
                out = fn(*raws)
        else:
            out = fn(*raws)
    if recording and use_x64:
        _inner_vjp = vjp_fn

        def vjp_fn(ct, _inner=_inner_vjp):
            with _enable_x64(True):
                return _inner(ct)

    wrapped = _wrap_out(out)
    if recording:
        out_leaves = [w for w in jax.tree_util.tree_leaves(
            wrapped, is_leaf=lambda x: isinstance(x, ndarray))
            if isinstance(w, ndarray)]
        # NOTE: must not rebind `treedef` — fn closes over the input treedef
        out_td = jax.tree_util.tree_structure(out)
        autograd._record_op(
            vjp_fn, diff_arrays, out_leaves,
            name or getattr(prim, "__name__", "op"),
            # only trustworthy when every pytree leaf is a wrapped array
            out_treedef=out_td if out_td.num_leaves == len(out_leaves)
            else None,
            # pure fn + primals: create_graph re-linearizes through these
            fun=fn, raw_args=tuple(raws), x64=use_x64)
    return wrapped


def _invoke_flat(prim, args, name, x64, amp_dt):
    """Dispatch with flat positional args only — semantics identical to
    the generic path (amp cast, scoped x64, vjp recording), minus the
    pytree walk and container-aware closure."""
    use_x64 = x64
    arr_pos = []
    diff_arrays = []
    leaves = list(args)
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, ndarray):
            if not use_x64 and _leaf_is_64bit(leaf._data):
                use_x64 = True
            if _is_inexact(leaf):
                arr_pos.append(i)
                diff_arrays.append(leaf)
            else:
                leaves[i] = leaf._data

    def fn(*xs):
        if amp_dt is not None:
            xs = [x.astype(amp_dt)
                  if jnp.issubdtype(x.dtype, jnp.floating)
                  and x.dtype != amp_dt else x for x in xs]
        ls = list(leaves)
        for p, x in zip(arr_pos, xs):
            ls[p] = x
        return prim(*ls)

    raws = [a._data for a in diff_arrays]
    recording = (autograd.is_recording()
                 and any(a._entry is not None for a in diff_arrays))
    x64_scope = _enable_x64(True) if use_x64 else contextlib.nullcontext()
    with x64_scope:
        if recording:
            try:
                out, vjp_fn = jax.vjp(fn, *raws)
            except _VJP_FALLBACK_ERRORS:
                recording = False
                out = fn(*raws)
        elif amp_dt is None and not use_x64:
            # no cast, no scope, nothing recorded: call through directly
            ls = leaves
            if arr_pos:
                ls = list(leaves)
                for p, a in zip(arr_pos, diff_arrays):
                    ls[p] = a._data
            out = prim(*ls)
        else:
            out = fn(*raws)
    if recording and use_x64:
        _inner_vjp = vjp_fn

        def vjp_fn(ct, _inner=_inner_vjp):
            with _enable_x64(True):
                return _inner(ct)

    wrapped = _wrap_out(out)
    if recording:
        out_leaves = [w for w in jax.tree_util.tree_leaves(
            wrapped, is_leaf=lambda x: isinstance(x, ndarray))
            if isinstance(w, ndarray)]
        out_td = jax.tree_util.tree_structure(out)
        autograd._record_op(
            vjp_fn, diff_arrays, out_leaves,
            name or getattr(prim, "__name__", "op"),
            out_treedef=out_td if out_td.num_leaves == len(out_leaves)
            else None,
            fun=fn, raw_args=tuple(raws), x64=use_x64)
    return wrapped


# the reference's generated fluent-method list for NDArray (the same op
# tail Symbol carries), minus names implemented as real methods below
_NDARRAY_FLUENT = frozenset("""
arccos arccosh arcsin arcsinh arctan arctanh argmax_channel
broadcast_axes broadcast_like cbrt ceil cos cosh degrees depth_to_space
diag expm1 fix flip floor log10 log1p log2 log_sigmoid log_softmax mish
nanprod nansum norm one_hot pad pick radians rcbrt reciprocal relu rint
rsqrt shape_array sigmoid sign sin sinh size_array slice_axis slice_like
softmax softmin space_to_depth split_v2 tan tanh tile topk trunc
""".split())
_FLUENT_CACHE: dict = {}  # name -> resolved op fn (name-only resolution)


class ndarray:
    """N-dimensional array on a device (reference: numpy/multiarray.py:272)."""

    __slots__ = ("_data", "_grad", "_grad_req", "_entry", "_version",
                 "__weakref__")

    def __init__(self, data, ctx=None, dtype=None):
        if isinstance(data, ndarray):
            raw = data._data
        else:
            raw = jnp.asarray(data, dtype=np_dtype(dtype))
        if dtype is not None and raw.dtype != np_dtype(dtype):
            raw = raw.astype(np_dtype(dtype))
        if ctx is not None:
            raw = jax.device_put(raw, Context(ctx).jax_device)
        self._data = raw
        self._grad = None
        self._grad_req = "null"
        self._entry = None
        self._version = 0
        engine._track(raw)

    # -- metadata ----------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def itemsize(self):
        return self._data.dtype.itemsize

    @property
    def T(self):
        return _invoke(jnp.transpose, (self,))

    @property
    def ctx(self):
        """Context of this array (reference: NDArray.ctx)."""
        dev = None
        try:
            dev = list(self._data.devices())[0]
        except Exception:
            pass
        if dev is None or dev.platform == "cpu":
            return Context("cpu", getattr(dev, "id", 0) or 0)
        return Context("tpu", dev.id)

    context = ctx
    device = ctx

    @property
    def sharding(self):
        return self._data.sharding

    # -- engine / version semantics ---------------------------------------
    @property
    def version(self):
        """Write-version counter (reference: NDArray::version, ndarray.h:413)."""
        return self._version

    def wait_to_read(self):
        """Block until the value is computed (Engine::WaitForVar analog)."""
        if _pipeline._guard_depth:
            _pipeline.note_host_sync("ndarray.wait_to_read")
        self._data.block_until_ready()
        return self

    def _rebind(self, raw):
        """In-place value replacement: same wrapper, new buffer, version+1."""
        self._data = raw
        self._version += 1
        engine._track(raw)

    # -- conversion --------------------------------------------------------
    def asnumpy(self):
        """Host copy with MXNet's contract: C-contiguous and writable.

        device_get is allowed to hand back a strided / read-only view
        (a zero-copy view of a CPU buffer is read-only; on such a view a
        `.astype(...).reshape(-1)` silently copies and in-place writes
        vanish, seen as all-zero finite differences); the reference's
        asnumpy always yields an owned dense buffer (ndarray.cc
        SyncCopyToCPU), so normalize here.
        """
        if _pipeline._guard_depth:
            _pipeline.note_host_sync("ndarray.asnumpy")
        # where the host waits for the device: it learns here, not when
        # the call returned, that the work is done
        with _trace.span("ndarray.asnumpy", category="ndarray"):
            host = onp.asarray(jax.device_get(self._data))
        if not (host.flags["C_CONTIGUOUS"] and host.flags["WRITEABLE"]):
            host = host.copy(order="C")  # owned, dense, writable
        return host

    def asscalar(self):
        return self.asnumpy().item()

    def item(self, *args):
        if _pipeline._guard_depth:
            _pipeline.note_host_sync("ndarray.item")
        return self._data.item(*args)

    def tolist(self):
        return self.asnumpy().tolist()

    def astype(self, dtype, copy=True):
        dt = np_dtype(dtype)
        if not copy and self._data.dtype == dt:
            return self
        return _invoke(lambda x: x.astype(dt), (self,), name="astype",
                       x64=_wants_x64(dt))

    def copy(self):
        return _invoke(jnp.copy, (self,))

    def copyto(self, other):
        """Copy value into another array or context (reference:
        NDArray.copyto / CopyFromTo src/ndarray/ndarray.cc)."""
        if isinstance(other, ndarray):
            if other.shape != self.shape:
                raise MXNetError(f"copyto shape mismatch {self.shape} vs {other.shape}")
            other._rebind(self._data.astype(other.dtype))
            return other
        if isinstance(other, Context):
            return _wrap(jax.device_put(self._data, other.jax_device))
        raise TypeError(type(other))

    def as_in_ctx(self, ctx):
        ctx = Context(ctx)
        return _wrap(jax.device_put(self._data, ctx.jax_device))

    as_in_context = as_in_ctx
    to_device = as_in_ctx

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    # -- NumPy interoperability protocols ---------------------------------
    # Reference: numpy_dispatch_protocol.py + multiarray.py:318-413 —
    # official numpy functions/ufuncs called ON mx arrays dispatch to the
    # mx implementation and return mx arrays (casting table: any mx
    # operand makes the result mx). Fallback to host numpy is allowed
    # only outside autograd recording (grads cannot flow through it).

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        if method != "__call__":
            return NotImplemented
        from .. import numpy as _mx_np
        name = ufunc.__name__
        fn = getattr(_mx_np, name, None)
        out = kwargs.pop("out", None)
        if out is not None:
            if isinstance(out, tuple):
                if len(out) != 1:
                    return NotImplemented
                out = out[0]
            kwargs["out"] = out
        ins = tuple(_wrap(jnp.asarray(a)) if isinstance(a, onp.ndarray)
                    else a for a in inputs)
        if fn is None or not callable(fn):
            return self._np_fallback(ufunc, ins, kwargs)
        return fn(*ins, **kwargs)

    def __array_function__(self, func, types, args, kwargs):
        from .. import numpy as _mx_np
        try:
            fn = getattr(_mx_np, func.__name__)
        except AttributeError:
            fn = None
        if fn is None or not callable(fn):
            return self._np_fallback(func, args, kwargs)
        return fn(*args, **kwargs)

    @staticmethod
    def _np_fallback(func, args, kwargs):
        from .. import autograd as _ag
        if _ag.is_recording():
            raise MXNetError(
                f"falling back to official NumPy operator "
                f"{getattr(func, '__name__', func)} under autograd.record() "
                "is not supported (gradients cannot flow through host "
                "numpy); move the call outside the recording scope")

        def to_onp(x):
            return x.asnumpy() if isinstance(x, ndarray) else x
        out = func(*jax.tree_util.tree_map(
            to_onp, args, is_leaf=lambda x: isinstance(x, ndarray)),
            **{k: to_onp(v) for k, v in kwargs.items()})
        return (_wrap(jnp.asarray(out))
                if isinstance(out, onp.ndarray) else out)

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, stream=None):
        return self._data.__dlpack__(stream=stream)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # -- autograd ----------------------------------------------------------
    def attach_grad(self, grad_req="write"):
        """Allocate a gradient buffer and mark as a tape leaf
        (reference: NDArray.attach_grad / mark_variables)."""
        grad = _wrap(jnp.zeros(self.shape, self.dtype))
        self._mark_variable(grad, grad_req)

    def _mark_variable(self, grad, grad_req):
        self._grad = grad
        self._grad_req = grad_req
        self._entry = autograd._Entry(None, 0, weakref.ref(self))

    def _write_grad(self, raw_grad):
        if self._grad_req == "null" or self._grad is None:
            return
        from ..ndarray import sparse as _sp
        if isinstance(raw_grad, _sp.BaseSparseNDArray):
            # row-sparse gradient (embedding sparse_grad): .grad becomes
            # the sparse object, the reference's grad-stype row_sparse
            if self._grad_req == "add":
                if isinstance(self._grad, _sp.BaseSparseNDArray):
                    self._grad = _sp.add(self._grad, raw_grad)
                elif bool(jnp.any(self._grad._data != 0)):
                    # accumulated dense grad present: densify-and-add
                    dense = self._grad._data + \
                        raw_grad.tostype("default")._data
                    self._grad = _wrap(dense.astype(self.dtype))
                else:
                    self._grad = raw_grad.astype(self.dtype)
            else:
                self._grad = raw_grad.astype(self.dtype)
            return
        if isinstance(self._grad, _sp.BaseSparseNDArray):
            # dense grad arriving over a sparse one: densify
            dense = self._grad.tostype("default")._data + raw_grad
            self._grad = _wrap(dense.astype(self.dtype))
            return
        g = raw_grad.astype(self._grad.dtype)
        if self._grad_req == "add":
            self._grad._rebind(self._grad._data + g)
        else:
            self._grad._rebind(g)

    def zero_grad(self):
        if self._grad is None:
            return
        from ..ndarray import sparse as _sp
        if isinstance(self._grad, _sp.BaseSparseNDArray):
            # back to a dense zero buffer; the next sparse backward
            # replaces it wholesale
            self._grad = _wrap(jnp.zeros(self.shape, self.dtype))
            return
        self._grad._rebind(jnp.zeros_like(self._grad._data))

    @property
    def grad(self):
        return self._grad

    def detach(self):
        out = _wrap(self._data)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad], retain_graph, train_mode)

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        key = _unwrap_key(key)
        return _invoke(lambda x: x[key], (self,), name="getitem",
                       x64=_key_is_64bit(key))

    def __setitem__(self, key, value):
        if isinstance(value, ndarray):
            value = value._data
        key = _unwrap_key(key)
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            new = jnp.broadcast_to(jnp.asarray(value, self.dtype), self.shape)
        else:
            new = self._data.at[key].set(jnp.asarray(value).astype(self.dtype))
        if autograd.is_recording() and self._entry is not None:
            # functional set: records like any op, entry moves to new version
            old = self
            res = _invoke(lambda x, v: jnp.broadcast_to(v, x.shape) if key is Ellipsis
                          else x.at[key].set(v.astype(x.dtype)),
                          (self, _wrap(jnp.asarray(value))), name="setitem")
            self._data = res._data
            self._entry = res._entry
            self._version += 1
            return
        self._rebind(new)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of 0-d array")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __contains__(self, x):
        return bool((self._data == (x._data if isinstance(x, ndarray) else x)).any())

    # -- python scalar protocol -------------------------------------------
    def _scalar(self):
        if self.size != 1:
            raise TypeError(
                f"only size-1 arrays convert to python scalars, got {self.shape}")
        return jax.device_get(self._data).reshape(())

    def __bool__(self):
        if self.size == 1:
            return bool(self._scalar())
        return bool(self._data)  # raises the standard ambiguity error

    def __float__(self):
        return float(self._scalar())

    def __int__(self):
        return int(self._scalar())

    def __index__(self):
        return int(self._scalar())

    def __hash__(self):
        return id(self)

    def __reduce__(self):
        # pickle as host numpy (DataLoader workers, Trainer state dumps);
        # the reference pickles NDArrays via shared memory (dataloader.py:28)
        # — device buffers always round-trip through host here
        return (_from_numpy_reduce, (self.asnumpy(),))

    def __repr__(self):
        try:
            return f"array({onp.array2string(self.asnumpy(), separator=', ')}, dtype={self.dtype})"
        except Exception:
            return f"ndarray(shape={self.shape}, dtype={self.dtype})"

    # -- arithmetic --------------------------------------------------------
    def _binop(self, other, fn, reflexive=False):
        if isinstance(other, (list, tuple, onp.ndarray)):
            other = _wrap(jnp.asarray(other))
        if reflexive:
            return _invoke(fn, (other, self))
        return _invoke(fn, (self, other))

    def __add__(self, o): return self._binop(o, jnp.add)
    def __radd__(self, o): return self._binop(o, jnp.add, True)
    def __sub__(self, o): return self._binop(o, jnp.subtract)
    def __rsub__(self, o): return self._binop(o, jnp.subtract, True)
    def __mul__(self, o): return self._binop(o, jnp.multiply)
    def __rmul__(self, o): return self._binop(o, jnp.multiply, True)
    def __truediv__(self, o): return self._binop(o, jnp.true_divide)
    def __rtruediv__(self, o): return self._binop(o, jnp.true_divide, True)
    def __floordiv__(self, o): return self._binop(o, jnp.floor_divide)
    def __rfloordiv__(self, o): return self._binop(o, jnp.floor_divide, True)
    def __mod__(self, o): return self._binop(o, jnp.mod)
    def __rmod__(self, o): return self._binop(o, jnp.mod, True)
    def __pow__(self, o): return self._binop(o, jnp.power)
    def __rpow__(self, o): return self._binop(o, jnp.power, True)
    def __matmul__(self, o): return self._binop(o, jnp.matmul)
    def __rmatmul__(self, o): return self._binop(o, jnp.matmul, True)
    def __neg__(self): return _invoke(jnp.negative, (self,))
    def __pos__(self): return self
    def __abs__(self): return _invoke(jnp.abs, (self,))
    def __invert__(self): return _invoke(jnp.invert, (self,))
    def __and__(self, o): return self._binop(o, jnp.bitwise_and)
    def __or__(self, o): return self._binop(o, jnp.bitwise_or)
    def __xor__(self, o): return self._binop(o, jnp.bitwise_xor)
    def __lshift__(self, o): return self._binop(o, jnp.left_shift)
    def __rshift__(self, o): return self._binop(o, jnp.right_shift)
    def __eq__(self, o): return self._binop(o, jnp.equal)
    def __ne__(self, o): return self._binop(o, jnp.not_equal)
    def __lt__(self, o): return self._binop(o, jnp.less)
    def __le__(self, o): return self._binop(o, jnp.less_equal)
    def __gt__(self, o): return self._binop(o, jnp.greater)
    def __ge__(self, o): return self._binop(o, jnp.greater_equal)

    # in-place: rebind the same wrapper (MXNet mutation semantics)
    def _iop(self, other, fn):
        res = self._binop(other, fn)
        self._data = res._data.astype(self.dtype)
        self._entry = res._entry
        self._version += 1
        return self

    def __iadd__(self, o): return self._iop(o, jnp.add)
    def __isub__(self, o): return self._iop(o, jnp.subtract)
    def __imul__(self, o): return self._iop(o, jnp.multiply)
    def __itruediv__(self, o): return self._iop(o, jnp.true_divide)
    def __ifloordiv__(self, o): return self._iop(o, jnp.floor_divide)
    def __imod__(self, o): return self._iop(o, jnp.mod)
    def __ipow__(self, o): return self._iop(o, jnp.power)

    # -- method forms of ops ----------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(-1 if s in (-1,) else int(s) for s in shape)
        return _invoke(lambda x: jnp.reshape(x, shape), (self,), name="reshape")

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        axes = axes if axes else None
        return _invoke(lambda x: jnp.transpose(x, axes), (self,), name="transpose")

    def swapaxes(self, a1, a2):
        return _invoke(lambda x: jnp.swapaxes(x, a1, a2), (self,))

    def flatten(self):
        return self.reshape(-1)

    def squeeze(self, axis=None):
        return _invoke(lambda x: jnp.squeeze(x, axis), (self,))

    def expand_dims(self, axis):
        return _invoke(lambda x: jnp.expand_dims(x, axis), (self,))

    def repeat(self, repeats, axis=None):
        return _invoke(lambda x: jnp.repeat(x, repeats, axis), (self,))

    def tile(self, reps):
        return _invoke(lambda x: jnp.tile(x, reps), (self,))

    def broadcast_to(self, shape):
        return _invoke(lambda x: jnp.broadcast_to(x, shape), (self,))

    def split(self, indices_or_sections, axis=0):
        return _invoke(lambda x: jnp.split(x, indices_or_sections, axis), (self,))

    def take(self, indices, axis=None, mode="clip"):
        idx = indices._data if isinstance(indices, ndarray) else indices
        return _invoke(lambda x: jnp.take(x, idx, axis, mode=mode), (self,))

    def clip(self, a_min=None, a_max=None):
        return _invoke(lambda x: jnp.clip(x, a_min, a_max), (self,))

    def round(self, decimals=0):
        return _invoke(lambda x: jnp.round(x, decimals), (self,))

    def _reduce(self, fn, axis=None, keepdims=False, **kw):
        return _invoke(lambda x: fn(x, axis=axis, keepdims=keepdims, **kw), (self,),
                       name=fn.__name__, x64=_wants_x64(kw.get("dtype")))

    def sum(self, axis=None, dtype=None, keepdims=False):
        return self._reduce(jnp.sum, axis, keepdims, dtype=np_dtype(dtype))

    def mean(self, axis=None, dtype=None, keepdims=False):
        return self._reduce(jnp.mean, axis, keepdims, dtype=np_dtype(dtype))

    def prod(self, axis=None, keepdims=False):
        return self._reduce(jnp.prod, axis, keepdims)

    def max(self, axis=None, keepdims=False):
        return self._reduce(jnp.max, axis, keepdims)

    def min(self, axis=None, keepdims=False):
        return self._reduce(jnp.min, axis, keepdims)

    def std(self, axis=None, keepdims=False, ddof=0):
        return self._reduce(jnp.std, axis, keepdims, ddof=ddof)

    def var(self, axis=None, keepdims=False, ddof=0):
        return self._reduce(jnp.var, axis, keepdims, ddof=ddof)

    def all(self, axis=None, keepdims=False):
        return self._reduce(jnp.all, axis, keepdims)

    def any(self, axis=None, keepdims=False):
        return self._reduce(jnp.any, axis, keepdims)

    def __getattr__(self, name):
        """Legacy fluent op methods (the reference generates ~80 per-op
        NDArray methods: a.relu(), a.log_softmax(), a.slice_axis(...)).
        Resolution is restricted to the fixed reference list so
        duck-typing probes keep their AttributeError contract; the
        methods call the same np/npx/legacy functions as module
        spellings. __slots__ means every other miss is a genuine
        AttributeError, so hot-path attribute access never lands here."""
        if name in _NDARRAY_FLUENT:
            fn = _FLUENT_CACHE.get(name)
            if fn is None:
                from .. import numpy as _np_mod
                from .. import numpy_extension as _npx_mod
                from ..ndarray import register as _legacy
                # npx/legacy FIRST: mx.np's module __getattr__ falls back
                # to jnp/jax.nn for unknown names, which would shadow the
                # reference-signature npx ops (softmax temperature=,
                # one_hot on_value=, ...)
                fn = _legacy.get(name) or getattr(_npx_mod, name, None) \
                    or getattr(_np_mod, name, None)
                if callable(fn):
                    _FLUENT_CACHE[name] = fn  # name-only resolution
            if callable(fn):
                def method(*args, _fn=fn, **kwargs):
                    return _fn(self, *args, **kwargs)
                method.__name__ = name
                return method
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute {name!r}")

    def argmax(self, axis=None):
        return _invoke(lambda x: jnp.argmax(x, axis), (self,))

    def argmin(self, axis=None):
        return _invoke(lambda x: jnp.argmin(x, axis), (self,))

    def argsort(self, axis=-1):
        return _invoke(lambda x: jnp.argsort(x, axis), (self,))

    def sort(self, axis=-1):
        return _invoke(lambda x: jnp.sort(x, axis), (self,))

    def cumsum(self, axis=None, dtype=None):
        return _invoke(lambda x: jnp.cumsum(x, axis, dtype=np_dtype(dtype)),
                       (self,), x64=_wants_x64(dtype))

    def dot(self, other):
        return self._binop(other, jnp.dot)

    def abs(self): return _invoke(jnp.abs, (self,))
    def exp(self): return _invoke(jnp.exp, (self,))
    def log(self): return _invoke(jnp.log, (self,))
    def sqrt(self): return _invoke(jnp.sqrt, (self,))
    def square(self): return _invoke(jnp.square, (self,))
    def sigmoid(self): return _invoke(jax.nn.sigmoid, (self,))
    def tanh(self): return _invoke(jnp.tanh, (self,))
    def relu(self): return _invoke(jax.nn.relu, (self,))

    def tostype(self, stype):
        if stype == "default":
            return self
        from ..ndarray import sparse as _sparse
        if stype == "row_sparse":
            return _sparse.row_sparse_array(self)
        if stype == "csr":
            return _sparse.csr_matrix(self)
        raise MXNetError(f"unknown storage type {stype!r}")

    @property
    def stype(self):
        return "default"


def _from_numpy_reduce(arr):
    return _wrap(jnp.asarray(arr))


def _unwrap_key(key):
    if isinstance(key, ndarray):
        return key._data
    if isinstance(key, tuple):
        return tuple(_unwrap_key(k) for k in key)
    if isinstance(key, list):
        return onp.asarray(key)
    return key


def _key_is_64bit(key):
    if isinstance(key, tuple):
        return any(_key_is_64bit(k) for k in key)
    return _leaf_is_64bit(key)


# ---------------------------------------------------------------------------
# creation functions (reference: numpy/multiarray.py zeros/ones/... wrappers)
# ---------------------------------------------------------------------------

def _place(raw, ctx, device):
    ctx = device if device is not None else ctx
    if ctx is not None:
        raw = jax.device_put(raw, Context(ctx).jax_device)
    return _wrap(raw)


def _x64_scope(dt):
    """Scoped x64 mode when a 64-bit dtype is explicitly requested."""
    return _enable_x64(True) if _wants_x64(dt) else contextlib.nullcontext()


def array(obj, dtype=None, ctx=None, device=None):
    if isinstance(obj, ndarray):
        obj = obj._data
    if dtype is None and isinstance(obj, onp.ndarray) and \
            onp.dtype(obj.dtype).name in ("int64", "uint64"):
        # preserve host-numpy 64-bit integer dtypes (index arrays); floats
        # keep the 32-bit TPU-native default unless explicitly requested
        dtype = obj.dtype
    with _x64_scope(dtype):
        raw = jnp.asarray(obj, dtype=np_dtype(dtype))
    return _place(raw, ctx, device)


def fromnumpy(a):
    return array(a)


def from_dlpack(x):
    return _wrap(jnp.from_dlpack(x))


def empty(shape, dtype=None, ctx=None, device=None, order="C"):
    return zeros(shape, dtype, ctx, device)


def zeros(shape, dtype=None, ctx=None, device=None, order="C"):
    with _x64_scope(dtype):
        raw = jnp.zeros(shape, np_dtype(dtype) or jnp.float32)
    return _place(raw, ctx, device)


def ones(shape, dtype=None, ctx=None, device=None, order="C"):
    with _x64_scope(dtype):
        raw = jnp.ones(shape, np_dtype(dtype) or jnp.float32)
    return _place(raw, ctx, device)


def full(shape, fill_value, dtype=None, ctx=None, device=None, order="C"):
    if isinstance(fill_value, ndarray):
        fill_value = fill_value._data
    with _x64_scope(dtype):
        raw = jnp.full(shape, fill_value, np_dtype(dtype))
    return _place(raw, ctx, device)


def zeros_like(a, dtype=None, ctx=None, device=None):
    return _invoke(lambda x: jnp.zeros_like(x, np_dtype(dtype)), (a,),
                   x64=_wants_x64(dtype))


def ones_like(a, dtype=None, ctx=None, device=None):
    return _invoke(lambda x: jnp.ones_like(x, np_dtype(dtype)), (a,),
                   x64=_wants_x64(dtype))


def full_like(a, fill_value, dtype=None, ctx=None, device=None):
    return _invoke(lambda x: jnp.full_like(x, fill_value, np_dtype(dtype)),
                   (a,), x64=_wants_x64(dtype))


def empty_like(a, dtype=None, ctx=None, device=None):
    return zeros_like(a, dtype, ctx, device)


def arange(start, stop=None, step=1, dtype=None, ctx=None, device=None):
    with _x64_scope(dtype):
        raw = jnp.arange(start, stop, step, np_dtype(dtype))
    return _place(raw, ctx, device)


def linspace(start, stop, num=50, endpoint=True, retstep=False, dtype=None,
             axis=0, ctx=None, device=None):
    with _x64_scope(dtype):
        out = jnp.linspace(start, stop, num, endpoint, retstep,
                           np_dtype(dtype), axis)
    if retstep:
        return _place(out[0], ctx, device), out[1]
    return _place(out, ctx, device)


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None,
             axis=0, ctx=None, device=None):
    with _x64_scope(dtype):
        raw = jnp.logspace(start, stop, num, endpoint, base,
                           np_dtype(dtype), axis)
    return _place(raw, ctx, device)


def eye(N, M=None, k=0, dtype=None, ctx=None, device=None):
    with _x64_scope(dtype):
        raw = jnp.eye(N, M, k, np_dtype(dtype) or jnp.float32)
    return _place(raw, ctx, device)


def identity(n, dtype=None, ctx=None, device=None):
    return eye(n, dtype=dtype, ctx=ctx, device=device)
