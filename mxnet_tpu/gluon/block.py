"""gluon.Block / HybridBlock.

Reference parity: python/mxnet/gluon/block.py (Block :202, HybridBlock :997,
SymbolBlock :1638). The reference traces a hybridized block with deferred
compute into an NNVM graph and replays it through CachedOp
(src/imperative/cached_op.cc); shape-specialized re-planning happens in
SetForwardGraph (cached_op.cc:169).

TPU-native design: ``hybridize()`` makes ``__call__`` run the user's
``forward`` inside ``jax.jit`` — the trace *is* the graph, XLA does memory
planning/fusion, and the executable cache keyed by input shapes/dtypes is the
CachedOp shape-signature cache. Mutable aux state (BatchNorm running stats)
is handled functionally: the traced function returns the set of parameters it
mutated, and the wrapper writes them back — the analog of CachedOp's mutable
input handling. Under ``autograd.record()`` the whole compiled forward is one
tape node (reference: CachedOp registers itself as one ``_CachedOp`` tape
node, cached_op.cc:968,1276).
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time
import weakref

import jax
import jax.numpy as jnp

from .. import autograd
from .. import insight as _insight
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..numpy.multiarray import ndarray, _wrap
from .parameter import Parameter, DeferredInitializationError
from .. import random as _random

#: per-thread _CachedGraph call depth — telemetry records only the
#: outermost hybridized call (children traced inside a parent are part
#: of that one compile)
_tele_tls = threading.local()


def _is_nd(x):
    return isinstance(x, ndarray)


#: sentinel for "rematerialization disabled" (a policy of None is meaningful
#: to jax.checkpoint: it means save nothing, i.e. full remat)
_REMAT_OFF = object()

_REMAT_POLICIES = {
    "dots": "dots_saveable",
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims": "dots_with_no_batch_dims_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    "nothing": "nothing_saveable",
    "everything": "everything_saveable",
}


def resolve_remat_policy(remat):
    """Map a ``hybridize(remat=...)`` value onto a ``jax.checkpoint`` policy.

    ``False``/``None`` — off. ``True`` — full rematerialization (only the
    inputs are saved). ``'dots'`` — matmul/einsum outputs are saved, cheap
    elementwise ops recompute (``jax.checkpoint_policies.dots_saveable``).
    ``'dots_with_no_batch_dims'`` — save only weight-stationary matmuls.
    A list or tuple of names — ``save_these``. A callable is the policy.
    """
    if remat is None or remat is False:
        return _REMAT_OFF
    if remat is True:
        return None
    if isinstance(remat, (list, tuple)):
        return save_these(*remat)
    if callable(remat):
        return remat
    attr = _REMAT_POLICIES.get(remat)
    if attr is None or not hasattr(jax.checkpoint_policies, attr):
        raise MXNetError(
            f"unknown remat policy {remat!r}: expected True/False, one of "
            f"{sorted(set(_REMAT_POLICIES))}, or a policy callable")
    return getattr(jax.checkpoint_policies, attr)


def _flatten_args(args):
    leaves, treedef = jax.tree_util.tree_flatten(args, is_leaf=_is_nd)
    return leaves, treedef


class Block:
    """Base neural-network container (reference: gluon/block.py:202).

    Child blocks and Parameters are discovered through attribute assignment,
    MXNet-2.0-style (no name_scope); structural names are attribute paths.
    """

    def __init__(self, prefix=None, params=None):
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    # -- registration ------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            existing = self.__dict__.get("_reg_params")
            if existing is not None:
                existing[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    # -- parameter management ----------------------------------------------
    def collect_params(self, select=None):
        """dict structural-name -> Parameter (reference: block.py
        collect_params; select is a regex like '.*weight')."""
        out = {}
        self._collect_params(out, "")
        if select is not None:
            pattern = re.compile(select)
            out = {k: v for k, v in out.items() if pattern.match(k)}
        return out

    def _collect_params(self, out, prefix):
        for name, p in self._reg_params.items():
            full = f"{prefix}{name}"
            p._structure_name = full
            out[full] = p
        for cname, child in self._children.items():
            child._collect_params(out, f"{prefix}{cname}.")

    @property
    def params(self):
        return dict(self._reg_params)

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False, device=None):
        """Initialize all parameters (reference: block.py initialize)."""
        for p in self.collect_params().values():
            p.initialize(init=p.init, ctx=device if device is not None else ctx,
                         default_init=init, force_reinit=force_reinit)
        return self

    def setattr(self, name, value):
        for p in self.collect_params().values():
            setattr(p, name, value)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def cast(self, dtype):
        """Cast parameters (+ future inputs) to dtype (reference: block.py
        cast; the AMP bf16 path uses this)."""
        for p in self.collect_params().values():
            p.cast(dtype)
        self._dtype = dtype
        return self

    def reset_ctx(self, ctx):
        for p in self.collect_params().values():
            p.reset_ctx(ctx)

    reset_device = reset_ctx

    def zero_grad(self):
        for p in self.collect_params().values():
            p.zero_grad()

    def share_parameters(self, shared):
        """Reference: block.py share_parameters (dict name->Parameter)."""
        mine = self.collect_params()
        for name, p in shared.items():
            if name in mine:
                self._set_param_by_path(name, p)
        return self

    def _set_param_by_path(self, path, p):
        parts = path.split(".")
        obj = self
        for part in parts[:-1]:
            obj = obj._children[part] if part in obj._children else getattr(obj, part)
        setattr(obj, parts[-1], p)

    # -- save / load -------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """npz of structural-name -> value (reference: block.py:340 over
        src/serialization/cnpy.cc); ``.safetensors`` filenames write the
        portable safetensors format (mxnet_tpu.serialization).

        Writes are crash-atomic (same-dir temp + fsync + ``os.replace``,
        stale temps from earlier crashes cleaned up): a crash mid-save
        can never tear an existing checkpoint."""
        import io
        import numpy as onp
        from .. import serialization
        params = self.collect_params()
        arrays = {}
        for name, p in params.items():
            if p._data is not None:
                arrays[name] = p.data().asnumpy()
        if filename.endswith(".safetensors"):
            serialization.save_safetensors(filename, arrays)
            return
        buf = io.BytesIO()
        onp.savez(buf, **arrays)
        serialization.atomic_write_bytes(filename, buf.getvalue())

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current", device=None):
        """Reference: block.py:378.

        When a ``.sha256`` sidecar exists (CheckpointHandler writes one
        per checkpoint), the file is validated against it first, so a
        torn/corrupt checkpoint raises instead of silently loading
        garbage weights."""
        import numpy as onp
        from ..numpy import array
        from .. import serialization
        real = filename if os.path.exists(filename) else filename + ".npz"
        if os.path.exists(real):
            serialization.verify_checksum(real)
        if filename.endswith(".safetensors"):
            loaded = serialization.load_safetensors(filename)
        elif os.path.exists(filename) \
                and serialization.is_legacy_params(filename):
            # a .params file written by Apache MXNet (legacy binary);
            # 1.x prefixes names with 'arg:'/'aux:' — strip them
            loaded = serialization.load_legacy_params(filename)
            if isinstance(loaded, list):
                raise MXNetError(
                    f"{filename} holds unnamed arrays; parameters need "
                    "names to load into a Block (save with a dict)")
            stripped = {}
            for k, v in loaded.items():
                base = k.split(":", 1)[1] if k.startswith(("arg:", "aux:")) \
                    else k
                if base in stripped:
                    # the reference keeps arg/aux as separate dicts; a name
                    # in both would silently lose one here — refuse
                    raise MXNetError(
                        f"{filename}: parameter {base!r} appears as both "
                        "arg: and aux:; cannot merge into one namespace")
                stripped[base] = v
            loaded = stripped
        else:
            path = filename if os.path.exists(filename) \
                else filename + ".npz"
            with onp.load(path, allow_pickle=False) as data:
                loaded = {k: data[k] for k in data.files}
        params = self.collect_params()
        for name, p in params.items():
            if name in loaded:
                p.set_data(array(loaded[name]))
            elif not allow_missing:
                raise MXNetError(f"parameter {name} missing in {filename}")
        extra = set(loaded) - set(params)
        if extra and not ignore_extra:
            raise MXNetError(f"file {filename} has extra parameters {sorted(extra)}")
        if ctx is not None or device is not None:
            self.reset_ctx(device if device is not None else ctx)

    def save(self, prefix):
        """Structural checkpoint (reference: block.py:576)."""
        self.save_parameters(prefix + "-model.params")

    def load(self, prefix):
        self.load_parameters(prefix + "-model.params")

    # -- execution ---------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """No-op on plain Blocks except recursing into children (reference:
        block.py Block.hybridize)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        params = self.collect_params()
        lines = [f"{type(self).__name__}:"]
        total = 0
        for name, p in params.items():
            n = 1
            for s in (p.shape or ()):
                n *= max(s, 0)
            total += n
            lines.append(f"  {name:60s} {str(p.shape):20s} {n}")
        lines.append(f"Total params: {total}")
        print("\n".join(lines))

    def __repr__(self):
        s = f"{type(self).__name__}("
        for name, child in self._children.items():
            s += f"\n  ({name}): {repr(child)}"
        return s + ("\n)" if self._children else ")")


class _CachedGraph:
    """Compiled forward for one (block, train_mode): the CachedOp analog.

    One jax.jit'd pure function; XLA's executable cache keyed on input
    shapes/dtypes replaces CachedOp::SetForwardGraph shape re-planning.
    """

    def __init__(self, block, train_mode):
        self.block = block
        self.train_mode = train_mode
        params = block.collect_params()
        self.param_names = [n for n, p in params.items() if p._data is not None]
        self.params = {n: params[n] for n in self.param_names}
        self.trainable = [n for n in self.param_names
                          if self.params[n].grad_req != "null"]
        self.aux = [n for n in self.param_names
                    if self.params[n].grad_req == "null"]
        pure = self._pure
        if getattr(block, "_backend", None):
            # subgraph backend transform (reference: optimize_for partition
            # hook, block.py:1160; here a rewrite of the traced forward)
            from .. import library
            transform = library.subgraph_backend(block._backend)
            pure = transform(pure, block,
                             **(block._flags.get("backend_opts") or {}))
        policy = resolve_remat_policy(block._flags.get("remat")) \
            if getattr(block, "_flags", None) else _REMAT_OFF
        if policy is not _REMAT_OFF:
            # selective rematerialization: under autograd the whole forward
            # replays per the policy instead of saving every activation
            import functools as _ft
            inner_pure = pure

            def pure(trainable_raws, aux_raws, input_raws, rng_key,
                     sig_key):
                fn = _ft.partial(inner_pure, sig_key=sig_key)
                return jax.checkpoint(fn, policy=policy)(
                    trainable_raws, aux_raws, input_raws, rng_key)
        self._jit = jax.jit(pure, static_argnames=("sig_key",))
        self._signatures = {}  # sig_key -> (treedef, static_leaves)
        self._out_trees = {}   # sig_key -> output treedef (set at trace time)
        # guards the two trace-time side channels above: the reference ships
        # a dedicated thread-safe executor (src/imperative/
        # cached_op_threadsafe.cc); here the jit itself is thread-safe and
        # only the signature bookkeeping needs the lock
        self._sig_lock = threading.Lock()
        # trace (param-buffer rebinding) vs replay isolation
        self._rw = _RWLock()
        # sig_key -> number of calls currently using it: a cache flush must
        # not evict the trace state of a call in progress
        self._inflight = {}


    def _pure(self, trainable_raws, aux_raws, input_raws, rng_key, sig_key):
        """The forward as a pure function of the parameters' raws, the
        call's array arguments and an RNG key -> (output raws, {aux name:
        the raw the forward rebound it to}).  The call's static leaves and
        tree are ``_signatures[sig_key]``'s; the output's tree is left in
        ``_out_trees[sig_key]``.  The parameters' storage holds the traced
        raws while the forward runs; the trace itself is ``_trace_body``
        (the file's end), which a boundary at a child block runs too."""
        if self._rw._readers:
            # tracing rebinds the shared Parameter buffers to tracers; doing
            # that while replays hold the read lock (including our own
            # reader slot — we mispredicted a cache hit) would leak tracers
            # into other threads. Abort; the caller retries as a writer.
            raise _SignatureEvicted(sig_key)
        sig = self._signatures.get(sig_key)
        if sig is None:
            # evicted between registration and (re-)trace — caller retries
            raise _SignatureEvicted(sig_key)

        def run(*fargs, **fkwargs):
            with autograd._RecordingStateScope(False, self.train_mode):
                return self.block.forward(*fargs, **fkwargs)

        saved = {}
        try:
            for n in self.param_names:
                p = self.params[n]
                saved[n] = p._data._data
                p._data._data = (trainable_raws[n] if n in trainable_raws
                                 else aux_raws[n])
            out_raws, out_tree, mutated = _trace_body(
                run, {n: self.params[n] for n in self.aux}, sig, input_raws,
                rng_key)
            with self._sig_lock:  # serialize vs cache-flush dict swaps
                self._out_trees[sig_key] = out_tree
            return out_raws, mutated
        finally:
            for n, raw in saved.items():
                self.params[n]._data._data = raw

    def __call__(self, args):
        from .. import profiler as _profiler
        if _profiler._state["running"] and \
                _profiler._config["profile_symbolic"]:
            # one span per compiled-forward replay (the reference profiles
            # CachedOp as a single engine op)
            with _profiler.span(f"CachedOp:{type(self.block).__name__}",
                                "symbolic"):
                return self._call_impl(args)
        return self._call_impl(args)

    def _call_impl(self, args):
        import numpy as onp
        leaves, treedef = _flatten_args(args)
        input_raws, static_leaves = [], []
        for i, l in enumerate(leaves):
            if isinstance(l, (jax.Array, onp.ndarray)) and not _is_nd(l):
                # raw arrays (e.g. kwarg masks) must be traced inputs —
                # keyed by repr() they would silently bake in as constants
                leaves[i] = l = _wrap(jnp.asarray(l))
            if _is_nd(l):
                input_raws.append(l._data)
                static_leaves.append(_ARR)
            else:
                static_leaves.append(l)
        from .. import amp as _amp
        from .. import config as _config
        # the full tuple (not its hash) is the key: equality comparison
        # makes collisions impossible (long static reprs are digested — a
        # 128-bit collision is not a realistic event); jax.jit's own cache
        # grows with the same signatures, so this adds no asymptotic memory
        sig_key = (str(treedef),
                   tuple("A" if l is _ARR else _static_repr(l)
                         for l in static_leaves),
                   tuple((tuple(r.shape), str(r.dtype)) for r in input_raws),
                   # dtype policy is applied inside _invoke at trace time, so
                   # a policy change must invalidate the cached trace
                   (_amp.is_active(), str(_amp.target_dtype())))
        with self._sig_lock:
            self._inflight[sig_key] = self._inflight.get(sig_key, 0) + 1
            is_new_sig = sig_key not in self._signatures
            if is_new_sig and \
                    len(self._signatures) >= \
                    _config.get("cached_graph.max_signatures"):
                # flush executables, out-trees and signatures together so
                # they stay consistent (reference: CachedOp bounds this
                # blowup via config, cached_op.h:412-459) — but keep the
                # entries of calls currently in flight on other threads
                keep = set(self._inflight)
                self._signatures = {k: v for k, v in self._signatures.items()
                                    if k in keep}
                self._out_trees = {k: v for k, v in self._out_trees.items()
                                   if k in keep}
                self._jit.clear_cache()
            self._signatures[sig_key] = (treedef, static_leaves)

        rng = _random._next_key()

        nd_leaves = [l for l in leaves if _is_nd(l)]
        arr_inputs = [l for l in nd_leaves
                      if jnp.issubdtype(l.dtype, jnp.inexact)]
        param_arrays = [self.params[n]._data for n in self.trainable]
        recording = autograd.is_recording() and (
            any(a._entry is not None for a in arr_inputs)
            or any(a._entry is not None for a in param_arrays))
        diff_input_raws = [l._data for l in arr_inputs]

        # an untraced signature means the next jit call traces, and tracing
        # temporarily rebinds the shared Parameter buffers to tracers —
        # exclusive (writer). Replays only read the param raws — shared.
        # _out_trees membership == "trace completed" (set at trace time).
        need_trace = is_new_sig or sig_key not in self._out_trees
        # telemetry covers only the OUTERMOST hybridized call on this
        # thread: children re-tracing inside a parent's trace are an
        # implementation detail of that one user-visible compile, and
        # per-child recompile warnings would be noise for one root cause
        outermost = not getattr(_tele_tls, "depth", 0)
        if _telemetry._active and outermost:
            # per-signature compile/cache accounting + the recompilation
            # detector (shape-polymorphism pitfall: every new signature
            # costs a full XLA compile on TPU)
            _telemetry.inc("cached_graph.cache_miss_total" if need_trace
                           else "cached_graph.cache_hit_total",
                           block=type(self.block).__name__)
        _tele_tls.depth = getattr(_tele_tls, "depth", 0) + 1
        try:
            for _attempt in (0, 1):
                acquired_write = need_trace
                if acquired_write:
                    self._rw.acquire_write()
                else:
                    self._rw.acquire_read()
                _t_trace = (time.perf_counter()
                            if acquired_write and outermost
                            and _telemetry._active
                            else None)
                try:
                    trainable_raws = {n: self.params[n]._data._data
                                      for n in self.trainable}
                    aux_raws = {n: self.params[n]._data._data
                                for n in self.aux}
                    if recording:
                        def fn(tr, diff_inp):
                            raws, di = list(input_raws), 0
                            for i, l in enumerate(nd_leaves):
                                if jnp.issubdtype(l.dtype, jnp.inexact):
                                    raws[i] = diff_inp[di]
                                    di += 1
                            return self._jit(tr, aux_raws, raws, rng,
                                             sig_key=sig_key)

                        (out_raws, mutated), vjp_fn = jax.vjp(
                            fn, trainable_raws, diff_input_raws)
                    else:
                        out_raws, mutated = self._jit(
                            trainable_raws, aux_raws, input_raws, rng,
                            sig_key=sig_key)
                    out_tree = self._out_trees.get(sig_key)
                    if out_tree is None:
                        # executable survived a flush that dropped its
                        # out-tree: force a clean re-trace
                        self._jit.clear_cache()
                        raise _SignatureEvicted(sig_key)
                    if _t_trace is not None:
                        _telemetry.note_compile(
                            self.block, type(self.block).__name__,
                            time.perf_counter() - _t_trace,
                            signatures=len(self._signatures))
                    if _insight._active and acquired_write:
                        # attribution for the fresh signature: trace-only
                        # re-lower (HLO cost analysis), no second backend
                        # compile and no note_compile
                        _insight.capture_jit(
                            f"cached_graph.{type(self.block).__name__}",
                            self._jit,
                            (trainable_raws, aux_raws, input_raws, rng),
                            kind="cached_graph", sig_key=sig_key)
                    break
                except _SignatureEvicted:
                    if _attempt:
                        raise MXNetError(
                            "compiled-forward signature cache thrashing: "
                            "raise mx.config cached_graph.max_signatures")
                    with self._sig_lock:
                        self._signatures[sig_key] = (treedef, static_leaves)
                    need_trace = True
                finally:
                    if acquired_write:
                        self._rw.release_write()
                    else:
                        self._rw.release_read()
        finally:
            _tele_tls.depth -= 1
            with self._sig_lock:
                self._inflight[sig_key] -= 1
                if not self._inflight[sig_key]:
                    del self._inflight[sig_key]

        # write back mutated aux state (BatchNorm running stats etc.) — the
        # analog of CachedOp mutable inputs
        for n, raw in mutated.items():
            self.params[n]._data._rebind(raw)

        out_wrapped = [_wrap(r) for r in out_raws]
        out = jax.tree_util.tree_unflatten(out_tree, out_wrapped)

        if recording:
            mut_shapes = {n: (raw.shape, raw.dtype) for n, raw in mutated.items()}
            trainable_names = list(self.trainable)

            def node_vjp(cots, _vjp=vjp_fn):
                cots = cots if isinstance(cots, tuple) else (cots,)
                mut_zeros = {n: jnp.zeros(s, d) for n, (s, d) in mut_shapes.items()}
                tr_cots, inp_cots = _vjp((list(cots), mut_zeros))
                return tuple(tr_cots[n] for n in trainable_names) + tuple(inp_cots)

            n_tr = len(trainable_names)

            def fun_flat(*flat, _fn=fn, _sig=sig_key, _td=treedef,
                         _sl=static_leaves):
                # flat = trainable raws + diff input raws; re-runs the jitted
                # forward so create_graph can jax.vjp through the whole
                # graph. This runs outside _call_impl's retry loop, so it
                # must re-register the signature (a flush may have evicted
                # it) and hold the write lock in case the re-entry traces.
                tr = dict(zip(trainable_names, flat[:n_tr]))
                for _attempt in (0, 1):
                    with self._sig_lock:
                        self._signatures[_sig] = (_td, _sl)
                    self._rw.acquire_write()
                    try:
                        out_raws2, _mut = _fn(tr, list(flat[n_tr:]))
                        return tuple(out_raws2)
                    except _SignatureEvicted:
                        if _attempt:
                            raise MXNetError(
                                "signature cache thrashing during "
                                "create_graph backward: raise mx.config "
                                "cached_graph.max_signatures")
                    finally:
                        self._rw.release_write()

            autograd._record_op(
                node_vjp, param_arrays + arr_inputs, out_wrapped,
                f"CachedOp:{type(self.block).__name__}",
                out_treedef=jax.tree_util.tree_structure(tuple(out_raws)),
                fun=fun_flat,
                raw_args=tuple(trainable_raws[n] for n in trainable_names)
                + tuple(diff_input_raws))
        return out


class _ArrSentinel:
    pass


_ARR = _ArrSentinel()


class _SignatureEvicted(Exception):
    """Trace-time side channel lost its entry (cache flush race); retry."""


class _RWLock:
    """Minimal readers-writer lock: traces are writers (exclusive — they
    temporarily rebind shared Parameter buffers to tracers), compiled
    replays are readers (shared). The reference isolates this class of race
    in a dedicated executor (src/imperative/cached_op_threadsafe.cc)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False

    def acquire_read(self):
        with self._cond:
            while self._writer:
                self._cond.wait()
            self._readers += 1

    def release_read(self):
        with self._cond:
            self._readers -= 1
            if not self._readers:
                self._cond.notify_all()

    def acquire_write(self):
        with self._cond:
            while self._writer or self._readers:
                self._cond.wait()
            self._writer = True

    def release_write(self):
        with self._cond:
            self._writer = False
            self._cond.notify_all()


def _static_repr(l):
    """Signature token for a static (non-array) call leaf; long reprs are
    digested so one huge python literal doesn't bloat every key."""
    r = repr(l)
    if len(r) > 128:
        # sha256: FIPS-approved (md5 raises on FIPS-enabled builds)
        return "H" + hashlib.sha256(r.encode()).hexdigest()
    return r


def _hashable(x):
    try:
        hash(x)
        return True
    except TypeError:
        return False


class HybridBlock(Block):
    """Traceable block (reference: gluon/block.py:997)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_graphs = {}
        self._flags = {}
        self._backend = None
        self._last_input_sig = None

    def __deepcopy__(self, memo):
        """Copies drop the compiled cache: _CachedGraph holds locks and
        jit executables that are process-local, and a copied net must
        re-trace against its OWN (copied) parameters anyway. The
        reference rebuilds CachedOp on copy the same way; quantize_net
        deep-copies hybridized nets through here."""
        import copy as _copy
        cls = self.__class__
        new = cls.__new__(cls)
        memo[id(self)] = new
        for k, v in self.__dict__.items():
            new.__dict__[k] = {} if k == "_cached_graphs" \
                else _copy.deepcopy(v, memo)
        return new

    def hybridize(self, active=True, backend=None, backend_opts=None,
                  clear=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Reference: block.py hybridize. static_alloc/static_shape map to
        XLA buffer donation/compiled executables — both are automatic here;
        the flags are accepted for compatibility.

        ``remat=`` (``resolve_remat_policy``: True, 'dots', names to save, a
        callable) recomputes this block's forward in the backward pass: the
        compiled forward under autograd, and each call inside a trace (a
        region: ``_boundary_call``).  Under names the flagged blocks a
        region calls are regions inside it — a faster schedule for more
        memory, +6 % to +41 % where it was read — and ``hybridize()`` on
        the children afterwards takes their flags: one region again.
        """
        resolve_remat_policy(kwargs.get("remat"))  # fail fast on bad values
        self._active = active
        if backend is not None:
            from .. import library
            library.subgraph_backend(backend)  # fail fast on unknown names
        self._backend = backend
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape,
                           backend_opts=backend_opts, **kwargs)
        if clear:
            self._cached_graphs = {}
        super().hybridize(active, backend=backend, backend_opts=backend_opts,
                          static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def optimize_for(self, x, *args, backend=None, clear=True, **kwargs):
        """Reference: block.py optimize_for — compiles for a backend then
        runs once. XLA is the only backend; equivalent to hybridize+call."""
        self.hybridize(True, backend=backend, clear=clear, **kwargs)
        return self(x, *args)

    def _ensure_init(self, *args):
        """Run deferred shape inference by executing forward eagerly once."""
        params = self.collect_params()
        pending = [p for p in params.values()
                   if p._data is None and p._deferred_init is not None]
        uninit = [p for p in params.values()
                  if p._data is None and p._deferred_init is None]
        if uninit:
            raise MXNetError(
                f"parameters {[p.name for p in uninit]} not initialized; "
                "call .initialize()")
        return bool(pending)

    def __call__(self, *args, **kwargs):
        if not kwargs and all(_is_nd(a) for a in args):
            # remembered for export(): the traced input signature
            self._last_input_sig = [(tuple(a.shape), str(a.dtype))
                                    for a in args]
        if not self._active:
            return super().__call__(*args, **kwargs)
        if self._flags.get("remat") and _traced(args, kwargs):
            return _boundary_call(self, args, kwargs)
        if self._ensure_init(*args):
            # first call: eager, triggers deferred init (the reference's
            # _build_cache also runs a traced forward first, block.py:1095)
            return super().__call__(*args, **kwargs)
        key = self._train_key()
        graph = self._cached_graphs.get(key)
        if graph is None:
            graph = _CachedGraph(self, key)
            self._cached_graphs[key] = graph
        # (args, kwargs) form one pytree: keyword names land in the treedef
        # and therefore in the trace-cache key, so keyword calls compile
        # exactly like positional ones (the reference's _build_cache is
        # positional-only and errors; block.py:1095)
        return graph((args, kwargs))

    @staticmethod
    def _train_key():
        return autograd.is_training()

    # -- export (reference: block.py:1471 export to json+params) -----------
    def export(self, path, epoch=0, remove_amp_cast=True):
        """Save a graph-only model artifact: params npz + serialized
        StableHLO + a manifest json.

        The reference writes NNVM json reloadable by SymbolBlock without the
        python class (gluon/block.py:1471,1638); the TPU-native equivalent
        is a jax.export StableHLO artifact (cross-lowered for cpu+tpu, with
        first-order VJP so the reload stays trainable). Requires at least
        one prior forward call (to know the input signature) — same
        precondition as the reference's deferred-compute export.
        """
        from .. import functional
        from ..base import np_dtype

        params_file = f"{path}-{epoch:04d}.params.npz"
        self.save_parameters(params_file)
        meta = {
            "format": "mxnet_tpu-hybrid-v2",
            "block_class": f"{type(self).__module__}.{type(self).__name__}",
            "params": os.path.basename(params_file),
        }
        if self._last_input_sig is None:
            raise MXNetError(
                "export requires a prior forward call so the input "
                "signature is known (reference: hybridize+forward before "
                "export)")
        from jax import export as jax_export

        params = functional.param_arrays(self)

        def fwd(params, *inputs):
            out, _ = functional.functional_call(self, params, *inputs,
                                                train=False)
            return out

        param_specs = {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for n, a in params.items()}
        in_specs = tuple(jax.ShapeDtypeStruct(s, np_dtype(d))
                         for s, d in self._last_input_sig)
        exported = jax_export.export(
            jax.jit(fwd), platforms=["cpu", "tpu"])(param_specs, *in_specs)
        hlo_file = f"{path}-{epoch:04d}.stablehlo"
        with open(hlo_file, "wb") as f:
            f.write(exported.serialize(vjp_order=1))
        meta["stablehlo"] = os.path.basename(hlo_file)
        meta["inputs"] = self._last_input_sig
        json_file = f"{path}-symbol.json"
        with open(json_file, "w") as f:
            json.dump(meta, f, indent=2)
        return json_file, params_file

    def infer_shape(self, *args):
        """Trigger deferred-shape inference without full compute where
        possible (falls back to an eager forward)."""
        with autograd.pause():
            self(*args)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybrid_forward(self, F, *args, **kwargs):
        raise MXNetError(
            "hybrid_forward(F, ...) is the MXNet 1.x API; implement "
            "forward(self, x) (MXNet 2.0 / Gluon 2 style) instead")


class SymbolBlock(HybridBlock):
    """Run an exported model WITHOUT its python class (reference:
    block.py:1638): the serialized StableHLO artifact from
    ``HybridBlock.export`` is the graph, the params npz is the state.
    Forward dispatches the deserialized program through ``_invoke`` so
    autograd records it (the artifact carries a first-order VJP), making
    reloaded models trainable like the reference's SymbolBlock."""

    def __init__(self, outputs=None, inputs=None, params=None,
                 exported=None):
        """Two construction forms, matching the reference:

        - ``SymbolBlock(outputs_symbol, inputs_symbol(s), params=...)``
          runs a Symbol DAG (reference block.py:1638 primary form; pairs
          with ``mx.model.load_checkpoint``). ``params`` values may be
          ndarrays or Parameters.
        - ``SymbolBlock(exported=...)`` wraps a deserialized StableHLO
          artifact (``SymbolBlock.imports``).
        """
        super().__init__()
        self._symbol = None
        self._input_names = []
        if outputs is not None:
            if not hasattr(outputs, "_eval_with"):
                raise MXNetError(
                    "SymbolBlock outputs must be a Symbol; to wrap a "
                    "StableHLO artifact pass exported= (or use "
                    "SymbolBlock.imports)")
            self._symbol = outputs
            ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
            self._input_names = [getattr(s, "name", s) for s in ins]
            fixed = {}
            for n, v in (params or {}).items():
                if n in self._input_names:
                    continue   # inputs are bound at call time, never stored
                if isinstance(v, Parameter):
                    fixed[n] = v
                else:
                    # trainable by default, like the reference's arg_params
                    p = Parameter(n, shape=tuple(v.shape),
                                  dtype=str(v.dtype), grad_req="write")
                    p.set_data(v if isinstance(v, ndarray)
                               else _wrap(jnp.asarray(v)))
                    fixed[n] = p
            params = fixed
        self._exported = exported
        self._sym_params = dict(params or {})

    def collect_params(self, select=None):
        if select is None:
            return dict(self._sym_params)
        pat = re.compile(select)
        return {n: p for n, p in self._sym_params.items() if pat.search(n)}

    def forward(self, *args):
        if self._symbol is not None:
            if len(args) != len(self._input_names):
                raise MXNetError(
                    f"SymbolBlock expects {len(self._input_names)} inputs "
                    f"{self._input_names}, got {len(args)}")
            bindings = {n: p.data() for n, p in self._sym_params.items()}
            bindings.update(zip(self._input_names, args))  # inputs win
            return self._symbol._eval_with(bindings)
        if self._exported is None:
            raise MXNetError("SymbolBlock has no graph; use SymbolBlock."
                             "imports(symbol_file, ...)")
        from ..numpy.multiarray import _invoke
        names = sorted(self._sym_params)
        pdict = {n: self._sym_params[n].data() for n in names}

        def run(pdict_raw, *input_raws):
            return self._exported.call(pdict_raw, *input_raws)

        return _invoke(run, (pdict, *args), name="SymbolBlock")

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None,
                allow_class_fallback=False):
        """Reload an exported artifact. ``input_names`` is accepted for
        reference-API parity (the artifact embeds its signature)."""
        with open(symbol_file) as f:
            meta = json.load(f)
        base = os.path.dirname(os.path.abspath(symbol_file))
        if meta.get("stablehlo"):
            from jax import export as jax_export
            with open(os.path.join(base, meta["stablehlo"]), "rb") as f:
                exported = jax_export.deserialize(bytearray(f.read()))
            params = {}
            pfile = (param_file
                     or os.path.join(base, meta.get("params", "")))
            if pfile and os.path.exists(pfile):
                import numpy as onp
                from ..numpy import array
                with onp.load(pfile) as data:
                    for name in data.files:
                        p = Parameter(name, shape=data[name].shape)
                        p.set_data(array(data[name]))
                        params[name] = p
            return SymbolBlock(exported=exported, params=params)
        if allow_class_fallback and meta.get("block_class"):
            # v1 manifests (no graph artifact): reconstruct via the class
            mod_name, cls_name = meta["block_class"].rsplit(".", 1)
            import importlib
            cls = getattr(importlib.import_module(mod_name), cls_name)
            block = cls()
            if param_file:
                block.load_parameters(param_file, ctx=ctx)
            return block
        raise MXNetError(
            f"{symbol_file} has no stablehlo graph artifact; re-export with "
            "HybridBlock.export (or pass allow_class_fallback=True)")


# -- a recomputation boundary at a child block ------------------------------
# (kept at the file's end: a line added above ``Block.__call__`` or
# ``HybridBlock.__call__`` moves the call-stack locations a Mosaic kernel's
# body records, and with them every compiled step's cache key)

#: this thread's boundaries: ``open``, the flags of those open now,
#: outermost first; ``regions``, how many regions what it traces holds;
#: ``traced``, block -> the region it was last traced as (weakly keyed)
_boundary_tls = threading.local()


def save_these(*names):
    """The ``jax.checkpoint`` policy ``hybridize(remat=[names...])`` means:
    a value is saved if ``jax.ad_checkpoint.checkpoint_name`` gave it one
    of ``names``, or if the primitive that made it is called one of them
    (``"pallas_call"``: what a Pallas kernel wrote, its residuals
    included); everything else is made again in the backward pass."""
    for n in names:
        if not isinstance(n, str):
            raise MXNetError(f"a remat name is a string, got {n!r}")
    names = frozenset(names)
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.save_only_these_names(*names),
        lambda prim, *_, **__: prim.name in names)


def _trace_body(run, aux, sig, input_raws, rng_key):
    """What every traced forward of a block does, whichever transform
    traces it (``_CachedGraph._pure`` under its own ``jit``,
    ``_boundary_call`` under ``jax.checkpoint`` inside an enclosing
    trace): the call's arguments are put together again from ``sig`` =
    (tree, leaves with ``_ARR`` where an array was) and the traced
    ``input_raws``; ``run`` is called on them with ``rng_key`` — an
    argument of the traced function — as the stream ``_random._next_key``
    splits, so no draw inside leaves a tracer of this trace in an outer
    stream; and what the forward left in the ``aux`` parameters' storage
    ({name: Parameter}) is gathered to leave the trace as a result.
    -> (output raws, the output's tree, {name: raw} of the aux rebound).
    The caller owns the storage: it binds what the forward is to read
    before, and puts back what was there after."""
    treedef, static_leaves = sig
    markers = {n: p._data._data for n, p in aux.items()}
    it = iter(input_raws)
    leaves = [_wrap(next(it)) if l is _ARR else l for l in static_leaves]
    fargs, fkwargs = jax.tree_util.tree_unflatten(treedef, leaves)
    with _random.trace_key_scope(rng_key):
        out = run(*fargs, **fkwargs)
    out_leaves, out_tree = _flatten_args(out)
    out_raws = [l._data if _is_nd(l) else l for l in out_leaves]
    mutated = {n: p._data._data for n, p in aux.items()
               if p._data._data is not markers[n]}
    return out_raws, out_tree, mutated


def _traced(args, kwargs):
    """Is any array among the arguments a tracer, i.e. is the block being
    called inside an enclosing trace (a train step's ``functional_call``,
    a hybridized parent's compiled forward)?"""
    return any(_is_nd(l) and isinstance(l._data, jax.core.Tracer)
               for l in _flatten_args((args, kwargs))[0])


def _held_params(block):
    """The parameters that hold a value, of ``block`` and its
    descendants — walked without ``collect_params``, which renames every
    parameter by its path from the block it is called on (an fp8 step
    finds a ``Dense``'s site by that name)."""
    out = [p for p in block._reg_params.values() if p._data is not None]
    for child in block._children.values():
        out += _held_params(child)
    return out


def _boundary_call(block, args, kwargs):
    """``block`` carries ``hybridize(remat=...)`` and is called inside an
    enclosing trace: its forward runs inline under ``jax.checkpoint`` with
    the flag's policy, so the enclosing backward keeps only what the
    policy saves of this call and makes the rest again when it reaches
    it.  The outermost flagged block on a call path is the boundary.

    *Regions inside it.*  Where every boundary open round a flagged block
    has a policy of names, the block opens a region of its own, with its
    own flag's policy: what such a region hands on is saved by name, so
    the outer replay reaches the next region without running this one's
    body, and the inner work is made again once (XLA schedules round the
    regions' barriers what it does not round one: 3 % of
    ``ouro-train-8k``'s update, PERF.md section 6, PR 44).  Under every
    other policy (``True``, ``'dots'``, a callable) nothing cuts the
    chain and every level would replay what is inside it: there the
    flagged blocks a boundary calls run plainly inside it.  The flags
    decide, so the way back to one region is to flag the boundary alone
    (``hybridize()`` on its children afterwards takes theirs): regions
    inside a region cost memory, +6 % on ``ouro-train-8k`` and +41 % on
    a stack of kernel products (tests/test_ouro.py).  An fp8 step keeps
    one region whatever the flags say: with regions inside it
    ``ouro-train-8k``'s fp8 control compiled to 18.06 GB against 15.38 of
    16.9 and did not load — the backward's regions are handed the same
    values either way, XLA keeps more of them live round the barriers
    (PERF.md section 7).

    *Side channels.*  Every one crosses the region as an argument or a
    result, never as a tracer left behind: the parameters' values are
    read from their storage, as the enclosing trace bound it, handed in
    and bound for the length of the forward, which finds them where it
    does without the flag (by no other name: an fp8 step finds a
    ``Dense``'s site by it); the RNG key is drawn from the enclosing
    stream and handed in (``_trace_body``); what the forward rebinds of
    the aux state, and the amaxes an fp8 step's ``Dense`` records in its
    scope, are handed out and put in their place outside — for a region
    inside a region that place is the outer region's trace, which hands
    them on in turn.

    *One trace a block and enclosing trace.*  The traced function holds
    no value of the enclosing trace, so a block called again in that
    trace with the same signature (a stack looped on shared weights, or
    not) hands ``jax.checkpoint`` the function it handed it the first
    time, and JAX finds its jaxpr, and the regions inside it, by it: the
    forward's Python runs once, as under ``jit``, and what it reads that
    is no argument, parameter or part of the key below (a Python
    attribute, a global) is what it was at that first call.  (Tracing
    regions inside regions anew each pass took 13 s on the chip's host
    where one region an application took 3.5.)  The function is kept
    with the block, for as long as the block lives, and used only in
    the trace it was made in."""
    from .. import amp as _amp
    from ..amp import fp8 as _fp8
    open_, scope = getattr(_boundary_tls, "open", ()), _fp8.current()
    if open_ and not (scope is None and all(
            isinstance(f, (list, tuple)) for f in open_)):
        return Block.__call__(block, *args, **kwargs)
    flag = block._flags["remat"]
    leaves, treedef = _flatten_args((args, kwargs))
    sig = (treedef, [_ARR if _is_nd(l) else l for l in leaves])
    held = _held_params(block)
    key = (jax.core.get_opaque_trace_state(), treedef,
           tuple((l.shape, str(l.dtype)) if _is_nd(l) else _static_repr(l)
                 for l in leaves), tuple(map(id, held)), open_, id(scope),
           autograd.is_training(), _amp.is_active(),
           str(_amp.target_dtype()))
    traced = _boundary_tls.__dict__.setdefault(
        "traced", weakref.WeakKeyDictionary())
    seen = traced.get(block)
    if seen is None or seen["key"] != key:
        seen = traced[block] = _region(weakref.ref(block), sig, held)
        seen["key"], seen["policy"] = key, resolve_remat_policy(flag)

    # ``regions``: this thread's count of regions in what it traces
    counted = getattr(_boundary_tls, "regions", 0)
    _boundary_tls.regions = counted + 1
    _boundary_tls.open = open_ + (flag,)
    try:
        out_raws, mutated, amax = jax.checkpoint(
            seen["fn"], policy=seen["policy"])(
            [l._data for l in leaves if _is_nd(l)], _random._next_key(),
            [p._data._data for p in held])
    finally:
        _boundary_tls.open = open_
    # the regions inside counted themselves while this one was traced;
    # where its trace is used again they are there again, uncounted
    if seen.pop("ran", False):
        seen["inside"], again = _boundary_tls.regions - counted - 1, 0
    else:
        again = seen["inside"]
        _boundary_tls.regions += again
    _telemetry.inc("block.boundary_regions_total", 1 + again)
    if again or open_:
        _telemetry.inc("block.boundary_nested_total", again + bool(open_))
    for n, raw in mutated.items():
        held[n]._data._rebind(raw)
    for site, v in amax.items():
        _fp8.record(site, *v)
    return jax.tree_util.tree_unflatten(
        seen["out_tree"], [_wrap(r) for r in out_raws])


def _region(block, sig, held):
    """What ``_boundary_call`` hands ``jax.checkpoint`` for ``block`` (a
    weak reference) called as ``sig``: a function of the call's arrays,
    its RNG key and the values of ``held``, its parameters, that closes
    over nothing of a trace.  -> {"fn"}; running ``fn`` leaves "ran" and
    the output's tree there."""
    from ..amp import fp8 as _fp8
    seen = {}
    aux = {n: p for n, p in enumerate(held) if p.grad_req == "null"}

    def fn(input_raws, rng_key, held_raws):
        seen["ran"], scope = True, _fp8.current()
        before = [p._data._data for p in held]
        amax_before = dict(scope.amax) if scope is not None else {}
        try:
            for p, raw in zip(held, held_raws):
                p._data._data = raw
            if scope is not None:
                scope.amax.clear()
            out_raws, seen["out_tree"], mutated = _trace_body(
                lambda *a, **k: Block.__call__(block(), *a, **k), aux, sig,
                input_raws, rng_key)
            amax = dict(scope.amax) if scope is not None else {}
        finally:
            for p, raw in zip(held, before):
                p._data._data = raw
            if scope is not None:
                scope.amax.clear()
                scope.amax.update(amax_before)
        return out_raws, mutated, amax

    seen["fn"] = fn
    return seen
