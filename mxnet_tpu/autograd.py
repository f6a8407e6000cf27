"""Autograd: imperative tape + backward.

Reference parity: python/mxnet/autograd.py (record/pause/train_mode/
predict_mode/backward/grad/mark_variables, custom Function) over
src/imperative/imperative.cc (RecordOp tape at :235, Backward at :438).

TPU-native design: instead of taping NNVM nodes and running an nnvm Gradient
pass, every recorded op captures a VJP closure at dispatch time via
``jax.vjp`` (the linearization runs on-device, async, alongside the forward).
``backward()`` walks the tape in reverse creation order — tape order is a
valid topological order — feeding output cotangents through each node's VJP
and accumulating into marked variables per their ``grad_req``. A hybridized
block's whole compiled forward is one tape node, exactly like the reference's
``_CachedOp`` tape entry (src/imperative/cached_op.cc:968).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax import enable_x64 as _enable_x64

from .base import MXNetError

# ---------------------------------------------------------------------------
# thread-local modes (reference: Imperative thread-local is_train_/is_recording_
# src/imperative/imperative.cc:33-41)
# ---------------------------------------------------------------------------

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = []          # list of _TapeNode in creation order
        _state.counter = 0
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_rec):
    st = _st()
    prev, st.recording = st.recording, bool(is_rec)
    return prev


def set_training(train_mode):
    st = _st()
    prev, st.training = st.training, bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._rec, self._train = is_record, train_mode
        self._prev = None

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._rec is not None:
            st.recording = self._rec
        if self._train is not None:
            st.training = self._train
        return self

    def __exit__(self, *exc):
        st = _st()
        st.recording, st.training = self._prev


def record(train_mode=True):
    """Scope in which ops are taped (reference: autograd.py:121)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# tape structure
# ---------------------------------------------------------------------------

class _TapeNode:
    """One recorded op: VJP closure + links to input entries.

    parents[i] is the _Entry the i-th differentiable input carried (or None
    for constants); vjp_fn maps output cotangents -> input cotangents.
    """
    __slots__ = ("vjp_fn", "parents", "n_out", "out_shapes", "out_dtypes",
                 "seq", "name", "saved", "out_treedef", "fun", "raw_args",
                 "x64")

    def __init__(self, vjp_fn, parents, outputs, name, out_treedef=None,
                 fun=None, raw_args=None, x64=False):
        st = _st()
        self.vjp_fn = vjp_fn
        self.parents = parents
        self.n_out = len(outputs)
        self.out_shapes = [o.shape for o in outputs]
        self.out_dtypes = [o.dtype for o in outputs]
        self.seq = st.counter
        st.counter += 1
        self.name = name
        self.saved = None
        # pytree structure of the primal output (list/tuple/dict containers):
        # the VJP's cotangent argument must match it exactly
        self.out_treedef = out_treedef
        # pure function of the raw differentiable inputs + those inputs:
        # kept so create_graph=True can re-linearize (jax.vjp of the vjp)
        # for higher-order gradients (reference: Imperative::Backward with
        # create_graph, src/imperative/imperative.cc:438).
        self.fun = fun
        self.raw_args = raw_args
        self.x64 = x64
        st.tape.append(self)


class _Entry:
    """Autograd entry attached to an ndarray (reference: NDArray
    autograd_entry_, include/mxnet/ndarray.h:84). node None => leaf variable
    (holds weakly the variable ndarray for grad writeback)."""
    __slots__ = ("node", "index", "variable")

    def __init__(self, node, index, variable=None):
        self.node = node
        self.index = index
        self.variable = variable


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach grad buffers; start of the tape (reference: autograd.py:356,
    Imperative::MarkVariables imperative.cc)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, grad, req in zip(variables, gradients, grad_reqs):
        var._mark_variable(grad, req)


def _record_op(vjp_fn, array_inputs, outputs, name, out_treedef=None,
               fun=None, raw_args=None, x64=False):
    """Called by the dispatcher for every op executed under record()."""
    parents = [getattr(a, "_entry", None) for a in array_inputs]
    node = _TapeNode(vjp_fn, parents, outputs, name, out_treedef,
                     fun=fun, raw_args=raw_args, x64=x64)
    for i, o in enumerate(outputs):
        o._entry = _Entry(node, i)
    return node


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t. every marked variable on the tape.

    Reference: autograd.py:245 -> Imperative::Backward (imperative.cc:438).
    """
    from .numpy.multiarray import ndarray as _nd  # late import (cycle)
    if isinstance(heads, _nd):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, _nd):
        head_grads = [head_grads]

    _run_backward(heads, head_grads, retain_graph, accumulate_to_vars=True)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Return grads of heads wrt variables without touching their .grad
    (reference: autograd.py:303). ``create_graph`` (higher order) is supported
    by re-recording the VJP computation onto the tape."""
    from .numpy.multiarray import ndarray as _nd
    single = isinstance(variables, _nd)
    if single:
        variables = [variables]
    if isinstance(heads, _nd):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, _nd):
        head_grads = [head_grads]
    if retain_graph is None:
        retain_graph = create_graph

    if create_graph:
        # the backward pass itself is recorded: every VJP application and
        # cotangent accumulation becomes a taped op, so the returned grads
        # are differentiable (reference: imperative.cc:438 create_graph)
        with _RecordingStateScope(True, train_mode):
            grads = _run_backward(heads, head_grads, retain_graph,
                                  accumulate_to_vars=False, wrt=variables,
                                  create_graph=True)
    else:
        grads = _run_backward(heads, head_grads, retain_graph,
                              accumulate_to_vars=False, wrt=variables,
                              create_graph=False)
    return grads[0] if single else grads


def _run_backward(heads, head_grads, retain_graph, accumulate_to_vars,
                  wrt=None, create_graph=False):
    from .numpy.multiarray import ndarray as _nd, _wrap
    st = _st()

    # seed cotangents keyed by id(entry)
    cot = {}
    roots = []
    for h, hg in zip(heads, head_grads):
        entry = getattr(h, "_entry", None)
        if entry is None:
            raise MXNetError(
                "cannot differentiate a head that is not the output of a "
                "recorded computation (did you forget autograd.record()?)")
        seed = (jnp.ones(h.shape, h.dtype) if hg is None
                else (hg._data if isinstance(hg, _nd) else jnp.asarray(hg)))
        key = (_outkey(entry.node, entry.index) if entry.node is not None
               else id(entry))
        cot[key] = cot[key] + seed if key in cot else seed
        roots.append(entry)

    # collect reachable nodes
    reachable = {}
    stack = [e.node for e in roots if e.node is not None]
    while stack:
        node = stack.pop()
        if node is None or node.seq in reachable:
            continue
        reachable[node.seq] = node
        for p in node.parents:
            if p is not None and p.node is not None:
                stack.append(p.node)

    # entry-indexed cotangent store; process nodes in reverse creation order
    var_grads = {}  # id(entry of leaf) -> (variable, grad)
    for seq in sorted(reachable, reverse=True):
        node = reachable[seq]
        # gather output cotangents for this node
        outs = []
        has_any = False
        for i in range(node.n_out):
            # entries of outputs are unique per (node, i): we key by node+index
            key = _outkey(node, i)
            g = cot.pop(key, None)
            if g is None:
                g = _zero_cot(node.out_shapes[i], node.out_dtypes[i])
            else:
                has_any = True
            outs.append(g)
        if not has_any:
            continue
        in_cots = _apply_vjp(node, outs, create_graph)
        for p, ig in zip(node.parents, in_cots):
            if p is None or ig is None:
                continue
            if _is_float0(ig):
                continue
            if p.node is None:
                # leaf variable
                key = id(p)
                if key in var_grads:
                    var_grads[key] = (p, _accum(var_grads[key][1], ig))
                else:
                    var_grads[key] = (p, ig)
            else:
                key = _outkey(p.node, p.index)
                cot[key] = _accum(cot[key], ig) if key in cot else ig
        if not retain_graph:
            node.vjp_fn = None   # free residuals
            node.fun = None      # and the re-linearization closure
            node.raw_args = None  # and the pinned primal buffers

    # head that is itself a leaf variable
    for e, h in zip(roots, heads):
        if e.node is None:
            key = id(e)
            seedkey = id(e)
            g = cot.get(seedkey)
            if g is not None:
                if key in var_grads:
                    var_grads[key] = (e, var_grads[key][1] + g)
                else:
                    var_grads[key] = (e, g)

    if accumulate_to_vars:
        for entry, g in var_grads.values():
            var = entry.variable() if callable(entry.variable) else entry.variable
            if var is None:
                continue
            var._write_grad(g)
        if not retain_graph:
            st.tape.clear()
        return None

    # grad() path: return requested grads
    results = []
    for v in wrt:
        e = getattr(v, "_entry", None)
        leaf_e = e if (e is not None and e.node is None) else None
        g = None
        if leaf_e is not None and id(leaf_e) in var_grads:
            g = var_grads[id(leaf_e)][1]
        elif e is not None and e.node is not None:
            g = cot.get(_outkey(e.node, e.index))
        if g is None:
            g = jnp.zeros(v.shape, _float_or(v.dtype))
        # create_graph cotangents are already recorded ndarrays
        results.append(g if isinstance(g, _nd) else _wrap(g))
    if not retain_graph:
        st.tape.clear()
    return results


def _apply_vjp(node, out_cots, create_graph):
    if node.vjp_fn is None:
        raise MXNetError(
            "backward through a freed graph: pass retain_graph=True to keep "
            "intermediate state for a second backward")
    if create_graph:
        return _apply_vjp_create_graph(node, out_cots)
    if node.out_treedef is not None:
        cots = jax.tree_util.tree_unflatten(node.out_treedef, list(out_cots))
    else:
        cots = tuple(out_cots) if node.n_out > 1 else out_cots[0]
    return node.vjp_fn(cots)


def _apply_vjp_create_graph(node, out_cots):
    """Apply a node's VJP as a *recorded* op so grad-of-grad works.

    Reference semantics: ``autograd.grad(..., create_graph=True)`` records the
    backward pass itself so its outputs are differentiable
    (python/mxnet/autograd.py:303 over src/imperative/imperative.cc:438).

    TPU-native mechanism: the node kept its pure forward ``fun`` and raw
    primal inputs, so the whole input-cotangent computation
    ``h(primals, cots) = vjp(fun at primals)(cots)`` is itself a pure jax
    function.  We run ``jax.vjp(h, ...)`` — giving exact second-order
    linearization wrt BOTH the primals (residual dependence) and the incoming
    cotangents (chain dependence) — and tape one node whose parents are the
    original op's parents plus the cotangents' entries.  Because the new node
    also stores ``h`` as its own ``fun``, third and higher orders compose.

    ``out_cots`` entries are ndarrays (recorded or leaf), raw jax arrays
    (seed cotangents), or float0 numpy arrays (non-inexact outputs, treated
    as non-differentiable constants).
    """
    from .numpy import multiarray as M
    if node.fun is None:
        raise MXNetError(
            f"create_graph=True is not supported through op '{node.name}': "
            "it was recorded without a re-differentiable pure function "
            "(custom autograd.Function or a vjp-only fallback). Use "
            "first-order grad(), or express the op with built-in operators.")
    raw_cots = [c._data if isinstance(c, M.ndarray) else c for c in out_cots]
    # differentiable cotangent slots (float0 => constant)
    diff_idx = [i for i, c in enumerate(raw_cots) if not _is_float0(c)]
    n_primal = len(node.raw_args)
    fun, out_treedef, n_out = node.fun, node.out_treedef, node.n_out

    def h(*flat):
        primals = flat[:n_primal]
        dcots = flat[n_primal:]
        cs = list(raw_cots)
        for j, i in enumerate(diff_idx):
            cs[i] = dcots[j]
        if out_treedef is not None:
            cstruct = jax.tree_util.tree_unflatten(out_treedef, cs)
        else:
            cstruct = tuple(cs) if n_out > 1 else cs[0]
        _, vjp = jax.vjp(fun, *primals)
        return tuple(vjp(cstruct))

    h_args = tuple(node.raw_args) + tuple(raw_cots[i] for i in diff_idx)
    x64_scope = _enable_x64(True) if node.x64 else contextlib.nullcontext()
    with x64_scope:
        in_cots, h_vjp = jax.vjp(h, *h_args)
    if node.x64:
        _inner = h_vjp

        def h_vjp(ct, _i=_inner):
            with _enable_x64(True):
                return _i(ct)

    out_nds = [M._wrap(r) for r in in_cots]
    cot_parents = [
        out_cots[i]._entry if isinstance(out_cots[i], M.ndarray) else None
        for i in diff_idx]
    _record_op(h_vjp, [], out_nds, "grad_" + node.name,
               out_treedef=jax.tree_util.tree_structure(tuple(in_cots)),
               fun=h, raw_args=h_args, x64=node.x64)
    # _record_op derived parents from an empty input list; install the true
    # parent entries (primal entries + cotangent entries) directly — the
    # primal wrappers may be gone but their _Entry objects live on the node.
    new_node = out_nds[0]._entry.node if out_nds else None
    if new_node is not None:
        new_node.parents = list(node.parents) + cot_parents
    return out_nds


def _outkey(node, i):
    return (node.seq << 8) | i if i < 256 else (node.seq, i)


def _float_or(dt):
    return dt if jnp.issubdtype(dt, jnp.floating) or jnp.issubdtype(dt, jnp.complexfloating) else jnp.float32


def _zero_cot(shape, dt):
    """Zero cotangent matching jax.vjp's expectation: float0 for non-inexact
    outputs (e.g. argmax), same-dtype zeros otherwise."""
    import numpy as onp
    if jnp.issubdtype(dt, jnp.inexact):
        return jnp.zeros(shape, dt)
    return onp.zeros(shape, jax.dtypes.float0)


def _is_float0(x):
    return getattr(x, "dtype", None) == jax.dtypes.float0


def _accum(a, b):
    """Cotangent accumulation; row-sparse cotangents (embedding
    sparse_grad) merge through sparse.add instead of jnp +."""
    from .ndarray import sparse as _sp
    if isinstance(a, _sp.BaseSparseNDArray) or \
            isinstance(b, _sp.BaseSparseNDArray):
        out = _sp.add(a, b)
        return out if isinstance(out, _sp.BaseSparseNDArray) else \
            (out._data if hasattr(out, "_data") else out)
    from .numpy.multiarray import ndarray as _nd, _wrap
    if isinstance(a, _nd) != isinstance(b, _nd):
        # create_graph mode mixes recorded ndarray cotangents with raw seed
        # arrays; wrap the raw side so + dispatches through _invoke (taped)
        # instead of jax coercing the ndarray wrapper to a constant
        a = a if isinstance(a, _nd) else _wrap(jnp.asarray(a))
        b = b if isinstance(b, _nd) else _wrap(jnp.asarray(b))
    return a + b


def get_symbol(x):
    """Reference autograd.get_symbol returns the traced graph; here the tape
    has no symbolic form — use HybridBlock/hybridize for graph extraction."""
    raise MXNetError("get_symbol: use hybridize()/jax tracing for graphs")


# ---------------------------------------------------------------------------
# custom Function (reference: autograd.py:369 class Function)
# ---------------------------------------------------------------------------

class Function:
    """User-defined differentiable function with explicit backward.

    Subclass and implement forward(self, *inputs) and backward(self, *ograds),
    both taking/returning ndarrays. Reference: python/mxnet/autograd.py:369.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def __call__(self, *inputs):
        from .numpy.multiarray import ndarray as _nd
        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, _nd)
        outs = [outputs] if single else list(outputs)
        if is_recording():
            fn = self

            def vjp_fn(out_cots):
                cots = out_cots if isinstance(out_cots, tuple) else (out_cots,)
                from .numpy.multiarray import _wrap
                with pause():
                    igrads = fn.backward(*[_wrap(c) for c in cots])
                if isinstance(igrads, _nd):
                    igrads = (igrads,)
                return tuple(g._data if isinstance(g, _nd) else g for g in igrads)

            arr_inputs = [a for a in inputs if isinstance(a, _nd)]
            _record_op(vjp_fn, arr_inputs, outs, type(self).__name__)
        return outputs if single else tuple(outs)

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError
