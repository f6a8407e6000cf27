"""Sharded training step — the whole Trainer.step path as one XLA program.

Reference parity: python/mxnet/gluon/trainer.py:334 (step = backward grads →
kvstore pushpull allreduce → optimizer update, overlapped by the dependency
engine) and the KVStore reduce machinery (src/kvstore/comm.h). TPU-native:
forward + backward + gradient allreduce + optimizer update compile into ONE
jit program over a jax.sharding.Mesh — XLA inserts the collectives from the
shardings (data-parallel psum over 'dp', Megatron tensor-parallel
allreduces over 'tp', sequence sharding over 'sp') and its latency-hiding
scheduler overlaps comm with compute, which is the engine's compute/comm
overlap re-created at compile time.
"""
from __future__ import annotations

import contextlib
import functools
import re

import numpy as onp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import blackbox as _blackbox
from .. import config as _config
from .. import functional
from .. import insight as _insight
from .. import pipeline as _pipeline
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..amp import fp8 as _fp8
from ..base import MXNetError
from ..numpy.multiarray import ndarray, _wrap
from . import mesh as _pmesh
from .mesh import MeshConfig, activation_sharding

_telemetry.declare_metric(
    "zero.reduce_scatter_bytes_total", "counter",
    "logical bytes reduce-scattered over the dp axis by ZeRO gradient "
    "partitioning (per optimizer update, padded flat layout)")
_telemetry.declare_metric(
    "zero.all_gather_bytes_total", "counter",
    "logical bytes all-gathered over the dp axis re-assembling ZeRO-updated "
    "parameters")
_telemetry.declare_metric(
    "mesh.dp_gradient_bytes_total", "counter",
    "logical gradient bytes reduced over the dp axis per optimizer update "
    "(total trainable bytes; overlaps the zero.* counters when ZeRO folds "
    "the reduction into its reduce-scatter)")
_telemetry.declare_metric(
    "mesh.tp_allreduce_bytes_total", "counter",
    "estimated activation bytes allreduced over the tp axis per step "
    "(row-parallel layer outputs x tokens; logical estimate for "
    "token-shaped inputs)")
_telemetry.declare_metric(
    "mesh.pp_stage_transfer_bytes_total", "counter",
    "estimated residual-stream bytes handed stage-to-stage over the pp "
    "axis per step (forward + backward; logical estimate)")
_telemetry.declare_metric(
    "mesh.collective_bytes_total", "counter",
    "per-axis breakdown of logical collective bytes moved by the training "
    "step, labeled axis=dp|tp|pp; the dp sample counts WIRE bytes at the "
    "compressed width when gradient compression is on, so the >=2x dp cut "
    "is directly observable against mesh.dp_gradient_bytes_total")
_telemetry.declare_metric(
    "zero.collective_bytes_total", "counter",
    "per-op breakdown of the ZeRO dp collectives, labeled "
    "op=reduce_scatter|all_gather (same logical bytes the unlabeled "
    "zero.*_bytes_total counters accumulate)")
_telemetry.declare_metric(
    "comm.compressed_bytes_total", "counter",
    "dp gradient bytes actually placed on the wire by error-feedback "
    "compression (int8 payload + one fp32 scale per bucket per rank)")
_telemetry.declare_metric(
    "comm.uncompressed_bytes_total", "counter",
    "dp gradient bytes that WOULD have moved without compression (fp32 "
    "per-microbatch reduce) — the denominator of the compression ratio")

# params whose structural name matches <prefix>layer<i>.<suffix> with
# identical shapes across i are the pipeline-stackable layer family
_PP_LAYER_RE = re.compile(r"^(?P<pre>.*\blayer)(?P<idx>\d+)\.(?P<suf>.+)$")


def _pp_layer_groups(names):
    """Group param names by (prefix, suffix) around a 'layerN.' segment:
    {(pre, suf): {idx: name}}."""
    groups = {}
    for n in names:
        m = _PP_LAYER_RE.match(n)
        if m:
            key = (m.group("pre"), m.group("suf"))
            groups.setdefault(key, {})[int(m.group("idx"))] = n
    return groups


def _insert_dp(spec, shape, dp_axis, dp_n):
    """Optimizer-state spec for a tensor-sharded param under ZeRO: the
    param's spec with ``dp_axis`` partitioning its largest free
    (replicated, evenly divisible) dimension — the reduce-scatter target.
    None when no dimension can take the dp axis (state then shards like
    the weight)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    flat = []
    for e in entries:
        flat.extend(e if isinstance(e, tuple) else (e,))
    if dp_axis in flat:
        return None
    free = [i for i, e in enumerate(entries)
            if e is None and shape[i] % dp_n == 0 and shape[i] >= dp_n]
    if not free:
        return None
    best = max(free, key=lambda i: shape[i])
    entries[best] = dp_axis
    return P(*entries)

# name-pattern Megatron rules for the transformer family
# (column-parallel: shard Dense units; row-parallel: shard in_units, psum)
_COLUMN_SUFFIXES = ("query_proj.weight", "key_proj.weight",
                    "value_proj.weight", "ffn_1.weight")
_ROW_SUFFIXES = ("out_proj.weight", "ffn_2.weight")
_COLUMN_BIAS = ("query_proj.bias", "key_proj.bias", "value_proj.bias",
                "ffn_1.bias")


def megatron_specs(param_shapes, tp_axis="tp"):
    """PartitionSpecs for transformer params by structural-name pattern."""
    specs = {}
    for name, shape in param_shapes.items():
        if any(name.endswith(s) for s in _COLUMN_SUFFIXES) and len(shape) == 2:
            specs[name] = P(tp_axis, None)
        elif any(name.endswith(s) for s in _ROW_SUFFIXES) and len(shape) == 2:
            specs[name] = P(None, tp_axis)
        elif any(name.endswith(s) for s in _COLUMN_BIAS):
            specs[name] = P(tp_axis)
        else:
            specs[name] = P()
    return specs


class FunctionalOptimizer:
    """Pure-functional adapter over a mxnet_tpu Optimizer instance so its
    update rule can run inside a jit/pjit trace (the analog of the fused
    multi-tensor update ops, src/operator/optimizer_op.cc:352)."""

    def __init__(self, optimizer):
        self.opt = optimizer

    def init(self, raw_params):
        states = {}
        for name in raw_params:
            # states/settings key by STRUCTURAL NAME, not position: dict
            # ordering through a jit boundary is canonicalized, so a
            # positional index could bind lr_mult/wd_mult to the wrong
            # parameter vs the eager Trainer (collect_params order)
            s = self.opt.create_state(name, _wrap(raw_params[name]))
            states[name] = jax.tree_util.tree_map(
                lambda x: x._data if isinstance(x, ndarray) else x, s,
                is_leaf=lambda x: isinstance(x, ndarray))
        return states

    def update(self, raw_params, raw_grads, states, lr=None, t=None):
        new_p, new_s = {}, {}
        saved_count = self.opt.num_update
        if t is not None:
            # thread the (traced) step count into the update rules so
            # Adam-family bias correction advances inside the compiled step;
            # restored below so host-side bookkeeping never sees a tracer
            self.opt.num_update = t
        try:
            for name in raw_params:
                if name not in raw_grads:
                    new_p[name] = raw_params[name]
                    new_s[name] = states[name]
                    continue
                wd = self.opt._get_wd(name)
                lr_i = lr if lr is not None else self.opt._get_lr(name)
                wrapped = jax.tree_util.tree_map(
                    _wrap, states[name],
                    is_leaf=lambda x: x is None)
                w, s = self.opt._update_impl(
                    raw_params[name], raw_grads[name], wrapped, lr_i, wd)
                new_p[name] = w.astype(raw_params[name].dtype)
                new_s[name] = jax.tree_util.tree_map(
                    lambda x: x._data if isinstance(x, ndarray) else x, s,
                    is_leaf=lambda x: isinstance(x, ndarray))
        finally:
            if t is not None:
                self.opt.num_update = saved_count
        return new_p, new_s


def scan_steps(step_fn, n_state):
    """Fuse K training steps into one compiled program with ``lax.scan``.

    ``step_fn(*state, *batch) -> (*state', metric)`` becomes
    ``loop(*state, *stacked) -> (*state', metric_mean)`` where each array
    in ``stacked`` carries a leading steps axis.  One executable launch
    then performs K steps — amortizing per-launch dispatch latency, the
    step-level analog of the reference engine's op bulking
    (src/engine/threaded_engine.h:433; there ops are batched into one
    engine op, here whole steps into one XLA program).
    """
    from jax import lax

    def loop(*args):
        state, batches = args[:n_state], args[n_state:]

        def body(carry, xs):
            out = step_fn(*carry, *xs)
            return tuple(out[:n_state]), out[-1]

        state, metrics = lax.scan(body, tuple(state), tuple(batches))
        return (*state, jnp.mean(metrics))

    return loop


class ShardedTrainStep:
    """Compiled data/tensor/sequence-parallel training step for a Block.

    block: initialized (Hybrid)Block.
    loss_fn(outputs, *labels) -> scalar (raw jax values).
    optimizer: mxnet_tpu Optimizer instance (or name via opt.create).
    mesh: a MeshConfig (the composed dp×tp×pp×sp entry point — builds
        the Mesh, derives activation rules for sp, and turns on layer
        stacking for pp) or a raw jax.sharding.Mesh; dp_axis must exist
        for zero>0; tp/pp/sp optional.
    batch_specs: PartitionSpec per batch arg (inputs then labels),
        e.g. (P('dp', 'sp'), P('dp',)) — or ``cfg.batch_specs(...)``.
    param_specs: dict name -> PartitionSpec; defaults to megatron_specs
        when the mesh has a tp axis else fully replicated.
    zero: ZeRO optimizer-state partitioning level over the dp axis.
        0 — state shards like its weight (replicated under pure dp).
        1 — optimizer state lives in 1/dp flat shards; each step
        reduce-scatters grads, updates the local shard, all-gathers the
        new params — all inside the one jitted program so XLA overlaps
        the collectives with compute.  Params that are already tensor-
        sharded (tp/ep/pp) partition their REPLICATED sub-axis instead:
        the optimizer state carries the param's spec with 'dp' inserted
        into a free dimension, grads reduce-scatter onto it, the
        elementwise update runs on the (tp×dp)-sharded chunk, and the
        new params gather back to the tp-sharded layout — ZeRO×TP in
        one program.
        2 — additionally keeps reduced gradients (incl. the grad_accum
        accumulator) laid out in the same dp shards, so full gradients
        never materialize replicated.
    grad_accum: accumulate gradients over K lax.scan microbatches before
        ONE optimizer update (batch arrays gain a leading K axis).
        Distinct from steps_per_call, which applies an update every step.
    remat: activation rematerialization for the fwd/bwd inside the step —
        same values as ``HybridBlock.hybridize(remat=...)`` (True,
        'dots', a policy callable); None inherits the block's hybridize
        flag.
    precision: "fp32" (default) or "fp8" — fp8 runs eligible Dense
        matmuls e4m3-forward / e5m2-backward with per-tensor delayed
        scaling (mx.amp.fp8); the amax histories thread through the step
        as donated state and checkpoint with the optimizer bundle.
        Master weights, accumulation and the optimizer update stay fp32.
    grad_compress: None (read the ``comm.compress`` knob), "none",
        "int8" or "bf16" — error-feedback compression of the per-
        microbatch dp gradient all-reduce.  Gradients flatten into
        ``comm.bucket_mb`` buckets; each bucket quantizes (shared scale
        = pmax over ranks), psums at the wire width and carries the
        quantization error into the next step's gradient (EF-SGD), so
        the compression error telescopes instead of accumulating.  The
        independent per-bucket collectives are what XLA's latency-hiding
        scheduler overlaps with backward compute.  Requires a pure-dp
        mesh (tp=pp=sp=1) and every batch arg sharded over dp; silently
        off at dp=1.
    """

    def __init__(self, block, loss_fn, optimizer, mesh, batch_specs,
                 n_labels=1, param_specs=None, donate=True,
                 steps_per_call=1, zero=0, grad_accum=1, remat=None,
                 dp_axis="dp", precision="fp32", grad_compress=None):
        from ..optimizer import optimizer as opt_mod
        from ..gluon.block import resolve_remat_policy, _REMAT_OFF
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer)
        self.block = block
        self.loss_fn = loss_fn
        self.mesh_config = mesh if isinstance(mesh, MeshConfig) else None
        if self.mesh_config is not None:
            mesh = self.mesh_config.build()
        self.mesh = mesh
        # sp flows through the activation_sharding scope: the rules are
        # installed around every _step call so layer `constrain` hooks and
        # the ring-attention routing see them at trace time
        self._act_rules = (self.mesh_config.activation_rules()
                           if self.mesh_config is not None else {})
        if _blackbox._active and self.mesh_config is not None:
            # postmortems answer "what mesh was this host running?"
            _blackbox.note_mesh(self.mesh_config)
        self.n_labels = n_labels
        self.dp_axis = dp_axis
        # per-update specs as given (before the grad_accum/steps_per_call
        # lead axes are folded in below) — autotune() rebuilds steps with
        # different lead-axis geometry from these
        self.batch_specs = tuple(batch_specs)
        self.zero = int(zero)
        self.grad_accum = int(grad_accum)
        self.steps_per_call = int(steps_per_call)
        if self.zero not in (0, 1, 2):
            raise MXNetError(f"zero must be 0, 1 or 2, got {zero}")
        if self.grad_accum < 1:
            raise MXNetError(f"grad_accum must be >= 1, got {grad_accum}")
        self.precision = str(precision)
        if self.precision not in ("fp32", "fp8"):
            raise MXNetError(
                f"precision must be 'fp32' or 'fp8', got {precision!r}")
        self._fp8 = self.precision == "fp8"
        if grad_compress is None:
            grad_compress = _config.get("comm.compress")
        self._compress = str(grad_compress or "none").lower()
        if self._compress not in ("none", "int8", "bf16"):
            raise MXNetError(
                "grad_compress must be 'none', 'int8' or 'bf16', got "
                f"{grad_compress!r}")
        if self._compress != "none":
            others = {a: s for a, s in dict(mesh.shape).items()
                      if a != dp_axis and int(s) > 1}
            if others:
                raise MXNetError(
                    f"grad_compress='{self._compress}' needs a pure-dp "
                    f"mesh (the compressed reduce runs in a shard_map "
                    f"over '{dp_axis}' only); mesh also has {others}")
            for s in self.batch_specs:
                flat = []
                for e in tuple(s):
                    flat.extend(e if isinstance(e, tuple) else (e,))
                if dp_axis not in flat:
                    raise MXNetError(
                        f"grad_compress='{self._compress}' requires every "
                        f"batch arg sharded over '{dp_axis}'; got spec {s}")
            if int(mesh.shape.get(dp_axis, 1)) <= 1:
                self._compress = "none"   # nothing to reduce: plain path
        if remat is None and isinstance(getattr(block, "_flags", None), dict):
            remat = block._flags.get("remat")
        # kept as given so rebuild() can re-construct an equivalent step
        # around a different MeshConfig (fleet degrade/re-expand)
        self._donate = bool(donate)
        self._remat_arg = remat
        self._remat_policy = resolve_remat_policy(remat)
        self._remat_on = self._remat_policy is not _REMAT_OFF
        trainable, aux = functional.split_params(block)
        shapes = {n: v.shape for n, v in trainable.items()}
        shapes.update({n: v.shape for n, v in aux.items()})
        if param_specs is None:
            if "tp" in mesh.shape:
                param_specs = megatron_specs(shapes)
            else:
                param_specs = {n: P() for n in shapes}

        # -- pipeline stacking: layer families become one (S*k, ...) leaf --
        # Each repeated `<prefix>layerN.<suffix>` family stacks into a
        # single leaf whose leading (layer) dim shards over 'pp': every pp
        # group stores only its contiguous block of layers, and the static
        # per-layer index in the model's forward loop is the stage handoff
        # GSPMD lowers to a collective-permute — gpipe's ppermute schedule
        # expressed as sharding instead of shard_map, so it composes with
        # dp/tp/sp and the grad_accum microbatch scan.
        pp_n = int(mesh.shape.get("pp", 1))
        self._pp_groups = {}
        if pp_n > 1:
            param_specs = dict(param_specs)
            for d in (trainable, aux):
                for (pre, suf), idx_map in _pp_layer_groups(d).items():
                    L = len(idx_map)
                    if sorted(idx_map) != list(range(L)):
                        continue   # holes in the index range: not a family
                    members = [idx_map[i] for i in range(L)]
                    if len({tuple(d[m].shape) for m in members}) != 1:
                        continue
                    if L % pp_n:
                        raise MXNetError(
                            f"pp={pp_n}: layer family '{pre}N.{suf}' has "
                            f"{L} layers — not divisible into {pp_n} "
                            f"pipeline stages")
                    sname = f"{pre}*.{suf}"
                    d[sname] = jnp.stack([d.pop(m) for m in members])
                    base = param_specs.get(members[0], P())
                    param_specs[sname] = P("pp", *tuple(base))
                    self._pp_groups[sname] = {"members": members}
            if not self._pp_groups:
                raise MXNetError(
                    f"pp={pp_n} needs repeated 'layerN.' parameter "
                    "families of identical shape to place on pipeline "
                    "stages; none found in this block")
        self.param_specs = param_specs
        self.fopt = FunctionalOptimizer(optimizer)

        def sh(spec):
            return NamedSharding(mesh, spec)

        self.trainable = {
            n: jax.device_put(v, sh(param_specs.get(n, P())))
            for n, v in trainable.items()}
        self.aux = {
            n: jax.device_put(v, sh(param_specs.get(n, P())))
            for n, v in aux.items()}

        # -- ZeRO layout: which params get dp-partitioned optimizer state --
        if self.zero and dp_axis not in mesh.shape:
            raise MXNetError(
                f"zero={self.zero} requires a '{dp_axis}' mesh axis; "
                f"mesh has {tuple(mesh.shape)}")
        if self.zero and not type(self.fopt.opt)._zero_partitionable:
            raise MXNetError(
                f"{type(self.fopt.opt).__name__} is not elementwise "
                "(layer-wise norms / per-tensor RNG); it cannot run on "
                "ZeRO shards — use zero=0")
        dp_n = int(mesh.shape[dp_axis]) if self.zero else 1
        # Two ZeRO layouts:
        #   _zero: name -> (shape, size, padded_size) — fully-replicated
        #     params partition into flat 1/dp shards (padded ravel).
        #   _zero_tp: name -> state PartitionSpec — tensor-sharded
        #     (tp/ep/pp) params partition their REPLICATED sub-axis: the
        #     state carries the param spec with dp inserted into a free
        #     dim, grads reduce-scatter onto it, the elementwise update
        #     runs on the chunk and the new params gather back to the
        #     tensor-sharded layout (ZeRO x TP).
        self._zero = {}
        self._zero_tp = {}
        if self.zero:
            for n, v in self.trainable.items():
                spec = param_specs.get(n, P())
                if any(e is not None for e in spec):
                    sspec = _insert_dp(spec, v.shape, dp_axis, dp_n)
                    if sspec is not None:
                        self._zero_tp[n] = sspec
                    continue
                size = int(v.size)
                padded = -(-size // dp_n) * dp_n
                self._zero[n] = (tuple(v.shape), size, padded)

        states = {}
        for n, v in self.trainable.items():
            zinfo = self._zero.get(n)
            if zinfo is None:
                tspec = self._zero_tp.get(n)
                s = self.fopt.init({n: v})[n]
                if tspec is not None:
                    bad = [l.shape for l in jax.tree_util.tree_leaves(s)
                           if l.shape != v.shape]
                    if bad:
                        raise MXNetError(
                            f"{type(self.fopt.opt).__name__} state for "
                            f"'{n}' is not elementwise (leaf shapes "
                            f"{bad}); zero>0 unsupported")
                states[n] = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        x, sh(tspec if tspec is not None
                              else param_specs.get(n, P())))
                    if x is not None else None, s,
                    is_leaf=lambda x: x is None)
                continue
            shape, size, padded = zinfo
            flat = jnp.pad(jnp.ravel(v), (0, padded - size)) \
                if padded != size else jnp.ravel(v)
            s = self.fopt.init({n: flat})[n]
            bad = [l.shape for l in jax.tree_util.tree_leaves(s)
                   if l.shape != (padded,)]
            if bad:
                raise MXNetError(
                    f"{type(self.fopt.opt).__name__} state for '{n}' is not "
                    f"elementwise (leaf shapes {bad}); zero>0 unsupported")
            states[n] = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sh(P(dp_axis)))
                if x is not None else None, s, is_leaf=lambda x: x is None)
        self.states = states

        # -- fp8 delayed-scaling state (amax histories per eligible site) --
        self._fp8_sites = []
        self._fp8_margin = 1.0
        fp8_state = {}
        if self._fp8:
            tshapes = {n: tuple(v.shape) for n, v in self.trainable.items()}
            self._fp8_sites = _fp8.select_sites(tshapes)
            if not self._fp8_sites:
                raise MXNetError(
                    "precision='fp8' found no eligible sites (2-D "
                    "'*.weight' params with >= amp.fp8_min_elems "
                    f"elements) among {sorted(tshapes)}")
            self._fp8_margin = float(_config.get("amp.fp8_margin"))
            fp8_state = {
                site: {k: jax.device_put(v, sh(P())) for k, v in h.items()}
                for site, h in _fp8.init_state(self._fp8_sites).items()}
            # serve-side engines key quantization guards off this tag
            # (it also rides save_states metadata for cold loads)
            block._fp8_trained = True

        # -- error-feedback compression buckets over the dp axis --
        self._buckets = []
        resid_state = {}
        if self._compress != "none":
            dp_n_c = int(mesh.shape[dp_axis])
            bucket_elems = max(1, int(
                float(_config.get("comm.bucket_mb")) * (1 << 20) / 4))
            cur, cur_sz = [], 0
            for n in sorted(self.trainable):
                v = self.trainable[n]
                size = int(v.size)
                if cur and cur_sz + size > bucket_elems:
                    self._buckets.append(cur)
                    cur, cur_sz = [], 0
                cur.append((n, tuple(v.shape), size))
                cur_sz += size
            if cur:
                self._buckets.append(cur)
            # residuals live as one (dp, bucket) row per rank so the EF
            # error stays rank-local across steps (and across elastic
            # resizes via the canonical sum in state_dict)
            for i, members in enumerate(self._buckets):
                bsz = sum(s for _, _, s in members)
                resid_state[f"bucket{i}"] = jax.device_put(
                    jnp.zeros((dp_n_c, bsz), jnp.float32), sh(P(dp_axis)))
        self.extra = {"fp8": fp8_state, "resid": resid_state}

        # dp wire bytes per UPDATE (for the axis="dp" counter): plain
        # training reduces the full fp32 gradient once per update;
        # compression reduces int8/bf16 payload + one fp32 scale per
        # bucket PER MICROBATCH (EF must apply before accumulation)
        if self._compress == "none":
            self._dp_wire_bytes = sum(
                int(v.size) * jnp.dtype(v.dtype).itemsize
                for v in self.trainable.values())
        else:
            width = 1 if self._compress == "int8" else 2
            payload = sum(sum(s for _, _, s in m) for m in self._buckets)
            self._dp_wire_bytes = (
                (payload * width + 4 * len(self._buckets)) * self.grad_accum)

        param_sh = {n: sh(param_specs.get(n, P())) for n in trainable}
        aux_sh = {n: sh(param_specs.get(n, P())) for n in aux}
        state_sh = {
            n: jax.tree_util.tree_map(
                lambda x: sh(P(dp_axis)) if n in self._zero
                else sh(self._zero_tp[n]) if n in self._zero_tp
                else sh(param_specs.get(n, P())),
                self.states[n], is_leaf=lambda x: x is None)
            for n in self.states}
        # None states have no sharding
        state_sh = {
            n: jax.tree_util.tree_map(
                lambda x, s: None if x is None else s,
                self.states[n], state_sh[n], is_leaf=lambda x: x is None)
            for n in self.states}

        if self._zero:
            self._build_zero_update()
            itemsz = {n: jnp.dtype(self.trainable[n].dtype).itemsize
                      for n in self._zero}
            self._zero_bytes = sum(
                info[2] * itemsz[n] for n, info in self._zero.items())
        else:
            self._zero_bytes = 0
        self._zero_tp_bytes = sum(
            int(self.trainable[n].size)
            * jnp.dtype(self.trainable[n].dtype).itemsize
            for n in self._zero_tp)
        # analytic per-axis traffic (the mesh.* counters __call__ feeds)
        self._trainable_bytes = sum(
            int(v.size) * jnp.dtype(v.dtype).itemsize
            for v in self.trainable.values())
        self._tp_row_out_units = []
        if int(mesh.shape.get("tp", 1)) > 1:
            for n, v in self.trainable.items():
                if not any(n.endswith(s) for s in _ROW_SUFFIXES):
                    continue
                if n in self._pp_groups:
                    self._tp_row_out_units.append(
                        (int(v.shape[0]), int(v.shape[1])))
                else:
                    self._tp_row_out_units.append((1, int(v.shape[0])))
        self._pp_width = 0
        for n, v in self.trainable.items():
            if n in self._pp_groups and n.endswith("ln.gamma"):
                self._pp_width = int(v.shape[-1])
                break

        def base_step(trainable, aux, states, extra, rng, lr, t, *batch):
            inputs = batch[:len(batch) - self.n_labels]
            labels = batch[len(batch) - self.n_labels:]
            scales = (_fp8.scales_from_state(extra["fp8"], self._fp8_margin)
                      if self._fp8 else {})
            loss, mutated, grads, fwd_amax, g_amax, resid = self._fwd_bwd(
                trainable, aux, rng, inputs, labels, scales, extra["resid"])
            new_fp8 = (_fp8.roll_state(extra["fp8"], fwd_amax, g_amax)
                       if self._fp8 else extra["fp8"])
            new_tr, new_states = self._apply_updates(
                trainable, grads, states, lr, t)
            return (new_tr, {**aux, **mutated}, new_states,
                    {"fp8": new_fp8, "resid": resid}, loss)

        spec_list = list(batch_specs)
        step = base_step

        if self.grad_accum > 1:
            from jax import lax
            K = self.grad_accum
            zero2 = self._zero if self.zero >= 2 else {}
            zero2tp = self._zero_tp if self.zero >= 2 else {}

            def step(trainable, aux, states, extra, rng, lr, t, *batches):
                # microbatches carry a leading K axis; ONE update at the end.
                # At zero>=2 the accumulator holds flat dp shards — the
                # long-lived gradient memory is 1/dp per device and each
                # microbatch grad reduce-scatters straight into it.
                # (tensor-sharded params accumulate in their dp-inserted
                # state layout instead of the flat one.)
                def g_init(n, v):
                    if n in zero2:
                        return self._dp_constrain(
                            jnp.zeros((self._zero[n][2],), v.dtype))
                    if n in zero2tp:
                        return self._ztp_constrain(
                            n, jnp.zeros(v.shape, v.dtype))
                    return jnp.zeros(v.shape, v.dtype)

                acc0 = {n: g_init(n, v) for n, v in trainable.items()}
                # scales come from the PRE-update histories once for all
                # microbatches; the history rolls ONCE per update with the
                # max amax over the scan (delayed scaling's contract)
                scales = (_fp8.scales_from_state(
                    extra["fp8"], self._fp8_margin) if self._fp8 else {})
                zf32 = jnp.zeros((), jnp.float32)
                fwd0 = {s: (zf32, zf32) for s in self._fp8_sites}
                g0 = {s: zf32 for s in self._fp8_sites}

                def body(carry, xs):
                    aux_c, acc, resid, fa, ga, i = carry
                    inputs = xs[:len(xs) - self.n_labels]
                    labels = xs[len(xs) - self.n_labels:]
                    loss, mutated, grads, fwd_amax, g_amax, resid = (
                        self._fwd_bwd(
                            trainable, aux_c, jax.random.fold_in(rng, i),
                            inputs, labels, scales, resid))

                    def add(n):
                        g = grads[n]
                        if n in zero2:
                            g = self._dp_constrain(self._flat_pad(n, g))
                        elif n in zero2tp:
                            g = self._ztp_constrain(n, g)
                        return acc[n] + g

                    acc = {n: add(n) for n in acc}
                    fa = _fp8.merge_amax(fa, fwd_amax)
                    ga = _fp8.merge_amax(ga, g_amax)
                    return ({**aux_c, **mutated}, acc, resid, fa, ga,
                            i + 1), loss

                (aux, acc, resid, fa, ga, _), losses = lax.scan(
                    body, (aux, acc0, extra["resid"], fwd0, g0, 0),
                    tuple(batches))
                grads = {n: a / K for n, a in acc.items()}
                zflat = {n: grads.pop(n) for n in zero2} or None
                new_fp8 = (_fp8.roll_state(extra["fp8"], fa, ga)
                           if self._fp8 else extra["fp8"])
                new_tr, new_states = self._apply_updates(
                    trainable, grads, states, lr, t, zero_flat_grads=zflat)
                return (new_tr, aux, new_states,
                        {"fp8": new_fp8, "resid": resid}, jnp.mean(losses))

            spec_list = [P(None, *s) for s in spec_list]

        if self.steps_per_call > 1:
            inner = step

            def step(trainable, aux, states, extra, rng, lr, t, *batches):
                # batches carry a leading steps axis; one launch = K steps
                # (implementation shared with the free function scan_steps)
                def one(tr, ax, st, ex, i, *xs):
                    tr, ax, st, ex, loss = inner(
                        tr, ax, st, ex, jax.random.fold_in(rng, i), lr,
                        t + i, *xs)
                    return tr, ax, st, ex, i + 1, loss

                out = scan_steps(one, n_state=5)(
                    trainable, aux, states, extra, 0, *batches)
                return out[0], out[1], out[2], out[3], out[5]

            spec_list = [P(None, *s) for s in spec_list]

        self.batch_shardings = tuple(sh(s) for s in spec_list)

        extra_sh = {
            "fp8": {site: {k: sh(P()) for k in h}
                    for site, h in self.extra["fp8"].items()},
            "resid": {n: sh(P(dp_axis)) for n in self.extra["resid"]},
        }
        donate_argnums = (0, 1, 2, 3) if donate else ()
        self._step = jax.jit(
            step,
            in_shardings=(param_sh, aux_sh, state_sh, extra_sh, sh(P()),
                          sh(P()), sh(P())) + self.batch_shardings,
            out_shardings=(param_sh, aux_sh, state_sh, extra_sh, sh(P())),
            donate_argnums=donate_argnums)
        self._n_step = 0

    # -- step internals -----------------------------------------------------
    def _expand_pp(self, params):
        """Unstack pipeline families back to per-layer names for the
        block's forward: each static slice of the pp-sharded stack is one
        layer's weights, and consuming it on the next stage's microbatch
        is the stage handoff GSPMD lowers to a collective-permute."""
        if not self._pp_groups:
            return params
        out = dict(params)
        for sname, g in self._pp_groups.items():
            if sname not in out:
                continue
            stacked = out.pop(sname)
            for i, member in enumerate(g["members"]):
                out[member] = stacked[i]
        return out

    def _collapse_pp(self, updates):
        """Inverse of _expand_pp for the mutated-aux dict the forward
        returns (BatchNorm running stats inside pipelined layers)."""
        if not self._pp_groups or not updates:
            return updates
        out = dict(updates)
        for sname, g in self._pp_groups.items():
            members = g["members"]
            hit = [m for m in members if m in out]
            if not hit:
                continue
            if len(hit) != len(members):
                raise MXNetError(
                    f"pipeline family {sname}: forward mutated only "
                    f"{len(hit)}/{len(members)} member layers — stages "
                    "must update aux state uniformly")
            out[sname] = jnp.stack([out.pop(m) for m in members])
        return out

    def _loss_and_grad(self, trainable, aux, rng, inputs, labels):
        def lossf(tr):
            # the one scope of the forward; JAX names its backward
            # transpose(jvp(mx.fwd)) by itself
            with jax.named_scope("mx.fwd"):
                out, mutated = functional.functional_call(
                    self.block, self._expand_pp({**tr, **aux}), *inputs,
                    train=True, rng_key=rng)
                return (self.loss_fn(out, *labels),
                        self._collapse_pp(mutated))

        if self._remat_on:
            lossf = jax.checkpoint(lossf, policy=self._remat_policy)
        return jax.value_and_grad(lossf, has_aux=True)(trainable)

    def _fp8_loss_and_grad(self, trainable, aux, rng, inputs, labels,
                           scales):
        """fp8 forward/backward: the loss closure runs under the fp8
        scope (Dense routes matching sites through amp.fp8.dense_fp8) and
        differentiates w.r.t. BOTH the params and the per-site g_scales —
        the g_scale "cotangents" are the measured gradient amaxes the
        delayed-scaling history roll consumes (see amp/fp8.py)."""
        gsc = {s: scales[s][2] for s in scales}

        def lossf(tr, g):
            sc = {s: (scales[s][0], scales[s][1], g[s]) for s in g}
            with jax.named_scope("mx.fwd"), _fp8.scope(sc) as ctx:
                out, mutated = functional.functional_call(
                    self.block, self._expand_pp({**tr, **aux}), *inputs,
                    train=True, rng_key=rng)
                loss = self.loss_fn(out, *labels)
                amax = dict(ctx.amax)
            return loss, (self._collapse_pp(mutated), amax)

        if self._remat_on:
            lossf = jax.checkpoint(lossf, policy=self._remat_policy)
        (loss, (mutated, fwd_amax)), (grads, g_amax) = jax.value_and_grad(
            lossf, argnums=(0, 1), has_aux=True)(trainable, gsc)
        # fixed pytree structure for scan carries: sites the forward never
        # reached this trace report amax 0 (roll_state treats 0 as "no
        # observation growth")
        zf32 = jnp.zeros((), jnp.float32)
        fwd_amax = {s: fwd_amax.get(s, (zf32, zf32)) for s in gsc}
        return loss, mutated, grads, fwd_amax, g_amax

    def _fwd_bwd(self, trainable, aux, rng, inputs, labels, scales, resid):
        """One microbatch forward+backward; returns
        ``(loss, mutated, grads, fwd_amax, g_amax, new_resid)`` with the
        amax dicts empty unless fp8 and ``new_resid`` passed through
        unchanged unless compression is on."""
        if self._compress != "none":
            return self._compressed_fwd_bwd(
                trainable, aux, rng, inputs, labels, scales, resid)
        if self._fp8:
            loss, mutated, grads, fwd_amax, g_amax = (
                self._fp8_loss_and_grad(
                    trainable, aux, rng, inputs, labels, scales))
            return loss, mutated, grads, fwd_amax, g_amax, resid
        (loss, mutated), grads = self._loss_and_grad(
            trainable, aux, rng, inputs, labels)
        return loss, mutated, grads, {}, {}, resid

    def _compressed_fwd_bwd(self, trainable, aux, rng, inputs, labels,
                            scales, resid):
        """Error-feedback compressed dp gradient reduction.

        A shard_map over the dp axis makes the per-rank gradient explicit
        (outside shard_map the dp reduction is implicit in XLA's psum of
        the batch-sharded backward): each rank runs loss+grad on its
        local microbatch shard, flattens grads into the configured
        buckets, adds its carried residual, quantizes against a SHARED
        scale (pmax over ranks — so dequantization is exact w.r.t. what
        was sent) and psums the int8/bf16 payload.  The residual
        ``c - dequant(sent)`` carries to the next microbatch (EF-SGD),
        so the quantization error telescopes instead of biasing the
        trajectory.  Each bucket's psum is an independent collective —
        exactly the granularity XLA's latency-hiding scheduler overlaps
        with the remaining backward compute.
        """
        from jax import shard_map
        dpx = self.dp_axis
        dp_n = int(self.mesh.shape[dpx])
        mode = self._compress
        buckets = self._buckets
        n_in = len(inputs)

        def local(tr, ax, rngv, res, sc, *batch):
            ins = batch[:n_in]
            labs = batch[n_in:]
            # decorrelate dropout across ranks: outside shard_map the
            # same key spans the global batch, so fold in the rank
            rngl = jax.random.fold_in(rngv, jax.lax.axis_index(dpx))
            if self._fp8:
                loss, mutated, grads, fwd_amax, g_amax = (
                    self._fp8_loss_and_grad(tr, ax, rngl, ins, labs, sc))
            else:
                (loss, mutated), grads = self._loss_and_grad(
                    tr, ax, rngl, ins, labs)
                fwd_amax, g_amax = {}, {}
            pmean = functools.partial(jax.lax.pmean, axis_name=dpx)
            pmax = functools.partial(jax.lax.pmax, axis_name=dpx)
            loss = pmean(loss)
            mutated = jax.tree_util.tree_map(pmean, mutated)
            fwd_amax = jax.tree_util.tree_map(pmax, fwd_amax)
            g_amax = jax.tree_util.tree_map(pmax, g_amax)
            new_res, out_g = {}, {}
            for i, members in enumerate(buckets):
                flat = jnp.concatenate([
                    jnp.ravel(grads[n]).astype(jnp.float32)
                    for n, _, _ in members])
                c = flat + res[f"bucket{i}"][0]
                if mode == "int8":
                    s = pmax(jnp.max(jnp.abs(c))) / 127.0
                    s = jnp.where(s > 0.0, s, jnp.float32(1.0))
                    q = jnp.clip(jnp.round(c / s), -127.0, 127.0)
                    # int8 payload on the wire; the f32 psum of integer
                    # values is exact below 2^24, so dequant-after-reduce
                    # equals the mean of per-rank dequants bitwise
                    sent = q * s
                    red = jax.lax.psum(q, dpx) * s / dp_n
                else:   # bf16: value-snap through bf16, reduce in f32
                    sent = c.astype(jnp.bfloat16).astype(jnp.float32)
                    red = jax.lax.psum(sent, dpx) / dp_n
                new_res[f"bucket{i}"] = (c - sent)[None]
                off = 0
                for n, shape, size in members:
                    out_g[n] = red[off:off + size].reshape(shape).astype(
                        grads[n].dtype)
                    off += size
            return loss, mutated, out_g, fwd_amax, g_amax, new_res

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(), P(), P(dpx), P()) + tuple(self.batch_specs),
            out_specs=(P(), P(), P(), P(), P(), P(dpx)),
            check_vma=False)
        return fn(trainable, aux, rng, resid, scales, *inputs, *labels)

    def _flat_pad(self, n, v):
        _, size, padded = self._zero[n]
        flat = jnp.ravel(v)
        return jnp.pad(flat, (0, padded - size)) if padded != size else flat

    def _dp_constrain(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(self.dp_axis)))

    def _ztp_constrain(self, n, x):
        """Pin x to param n's ZeRO x TP optimizer-state layout (the
        param spec with dp inserted) — on gradients this IS the
        reduce-scatter over dp of the tensor-sharded leaf."""
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self._zero_tp[n]))

    def _param_constrain(self, n, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.param_specs.get(n, P())))

    def _build_zero_update(self):
        from jax import shard_map
        dpx = self.dp_axis
        fopt = self.fopt
        names = list(self._zero)

        # in_spec P(dp) on the (logically fully-reduced) grads IS the
        # reduce-scatter: GSPMD fuses the backward psum with the dp
        # partition into one collective. Params/state arrive as the local
        # 1/dp chunk, the elementwise update runs on it, and the explicit
        # all_gather re-assembles the full params (check_vma=False as in
        # collectives.allgather: the output is replicated but the static
        # varying-axes check can't infer it).
        @functools.partial(shard_map, mesh=self.mesh,
                           in_specs=(P(dpx), P(dpx), P(dpx), P(), P()),
                           out_specs=(P(), P(dpx)), check_vma=False)
        def _zupd(w_flat, g_flat, zstates, lr, t):
            new_w, new_s = fopt.update(w_flat, g_flat, zstates, lr=lr, t=t)
            gathered = {n: jax.lax.all_gather(new_w[n], dpx, tiled=True)
                        for n in names}
            return gathered, new_s

        self._zero_update = _zupd

    @jax.named_scope("mx.optimizer")
    def _apply_updates(self, trainable, grads, states, lr, t,
                       zero_flat_grads=None):
        """Optimizer update dispatch: flat-ZeRO params go through the
        shard_map path, ZeRO x TP params through a sharding-constrained
        elementwise update (reduce-scatter over dp, update the chunk,
        gather back to the tensor-sharded layout), everything else
        through the plain fused update."""
        if not self._zero and not self._zero_tp:
            return self.fopt.update(trainable, grads, states, lr=lr, t=t)
        new_tr, new_st = {}, {}
        rest = {n: v for n, v in trainable.items()
                if n not in self._zero and n not in self._zero_tp}
        if rest:
            p, s = self.fopt.update(
                rest, {n: g for n, g in grads.items() if n in rest},
                {n: states[n] for n in rest}, lr=lr, t=t)
            new_tr.update(p)
            new_st.update(s)
        if self._zero_tp:
            names = list(self._zero_tp)
            tpw = {n: self._ztp_constrain(n, trainable[n]) for n in names}
            tpg = {n: self._ztp_constrain(n, grads[n]) for n in names}
            p, s = self.fopt.update(
                tpw, tpg, {n: states[n] for n in names}, lr=lr, t=t)
            # new weights gather back to the tensor-sharded layout; the
            # state keeps the dp-inserted spec (jit out_shardings pin it)
            new_tr.update({n: self._param_constrain(n, p[n])
                           for n in names})
            new_st.update(s)
        if not self._zero:
            return new_tr, new_st
        if zero_flat_grads is None:
            zero_flat_grads = {n: self._flat_pad(n, grads[n])
                               for n in self._zero}
            if self.zero >= 2:
                # ZeRO-2: pin the flat grads to the dp shards so the full
                # gradient never materializes replicated
                zero_flat_grads = {n: self._dp_constrain(g)
                                   for n, g in zero_flat_grads.items()}
        w_flat = {n: self._flat_pad(n, trainable[n]) for n in self._zero}
        zstates = {n: states[n] for n in self._zero}
        gathered, new_zs = self._zero_update(
            w_flat, zero_flat_grads, zstates, lr, t)
        for n, (shape, size, _) in self._zero.items():
            w = gathered[n][:size].reshape(shape)
            new_tr[n] = w.astype(trainable[n].dtype)
            new_st[n] = new_zs[n]
        return new_tr, new_st

    def _trace_scope(self):
        """The activation_sharding scope the step traces under.  It
        carries the layout's own rules (sp) and — always — the mesh, which
        is how ops that must know it (the flash kernel's shard_map) find
        it.  A raw-Mesh step built inside a caller's scope keeps the
        caller's rules."""
        if not self._act_rules and _pmesh._act_rules is not None:
            return contextlib.nullcontext()
        return activation_sharding(self.mesh, **self._act_rules)

    def _shard_batch(self, batch):
        raws = [b._data if isinstance(b, ndarray) else jnp.asarray(b)
                for b in batch]
        # ensure_sharded skips the re-put when a DevicePrefetcher (see
        # .prefetch) already laid the batch out on the step's shardings —
        # the common case in an overlapped input pipeline
        return [_pipeline.ensure_sharded(r, s)
                for r, s in zip(raws, self.batch_shardings)]

    def lower(self, *batch):
        """Trace the step for ``batch`` without compiling or running it
        (no state advances): the ``jax.stages.Lowered`` whose text shows
        what the compiled step will contain — Mosaic kernels,
        collectives, shardings."""
        args = (self.trainable, self.aux, self.states, self.extra,
                jax.random.PRNGKey(0), jnp.zeros((), jnp.float32),
                jnp.ones((), jnp.float32), *self._shard_batch(batch))
        with self._trace_scope():
            return self._step.lower(*args)

    def __call__(self, *batch):
        """Run one step; returns the (replicated) scalar loss as ndarray.

        The host's part of a step is one span, ``mx/train.call`` in any
        profiler session, holding ``mx/train.shard_batch`` (the batch
        handed over to the step's shardings), ``mx/train.scalars`` (the
        key, ``lr`` and ``t`` helper programs) and ``mx/train.dispatch``
        (trace + lower + load on the first call, the enqueue afterwards).
        """
        with _trace.span("train.call", category="train"):
            return self._call(batch)

    def _call(self, batch):
        from .. import random as _random
        with _trace.span("train.shard_batch", category="train"):
            raws = self._shard_batch(batch)
        opt = self.fopt.opt
        # advance the update count on host (lr schedules / warmup / bias
        # correction used to be frozen at step 0 in the compiled path); the
        # schedule evaluates here in python and the results ride into the
        # jitted step as traced scalars, so no retrace
        base = opt.num_update
        opt.num_update = base + self.steps_per_call
        if _blackbox._active:
            # keep the flight recorder's step current so a crash bundle
            # is named for (and attributes evidence to) the right step
            _blackbox.set_context(step=int(base) + self.steps_per_call)
        with _trace.span("train.scalars", category="train"):
            rng = _random._next_key()
            lr_val = (opt.lr_scheduler(base + 1) if opt.lr_scheduler
                      else opt.lr)
            lr = jnp.asarray(lr_val, jnp.float32)
            t = jnp.asarray(base + 1, jnp.float32)
        if _insight._active and not getattr(self, "_insight_done", False):
            # one-time attribution capture BEFORE dispatch (donation
            # deletes the input buffers): trace-only .lower(), no
            # backend compile and no note_compile, so the recompile
            # detector and compile counters stay untouched
            self._insight_done = True
            label = getattr(self, "_insight_label", "parallel.train_step")
            cap = (self.trainable, self.aux, self.states, self.extra, rng,
                   lr, t, *raws)
            with self._trace_scope():
                _insight.capture_jit(label, self._step, cap, kind="train")
        # the scope surrounds the call so the layers' constrain() hooks,
        # the ring-attention routing and the flash kernel's shard_map see
        # the mesh while jit traces (first call) — no-op afterwards
        with self._trace_scope(), \
                _trace.span("train.dispatch", category="train"):
            out = self._step(
                self.trainable, self.aux, self.states, self.extra, rng,
                lr, t, *raws)
        self.trainable, self.aux, self.states, self.extra, loss = out
        self._n_step += self.steps_per_call
        if (self._zero or self._zero_tp) and _telemetry.active():
            rs_per_update = self.grad_accum if self.zero >= 2 else 1
            zb = self._zero_bytes + self._zero_tp_bytes
            _telemetry.inc("zero.reduce_scatter_bytes_total",
                           zb * self.steps_per_call * rs_per_update)
            _telemetry.inc("zero.all_gather_bytes_total",
                           zb * self.steps_per_call)
            _telemetry.inc("zero.collective_bytes_total",
                           zb * self.steps_per_call * rs_per_update,
                           op="reduce_scatter")
            _telemetry.inc("zero.collective_bytes_total",
                           zb * self.steps_per_call, op="all_gather")
        if _telemetry.active():
            # analytic per-axis mesh traffic (logical estimates, same
            # spirit as the zero.* counters) for the bench mesh rows
            shape = dict(self.mesh.shape)
            if shape.get(self.dp_axis, 1) > 1:
                _telemetry.inc("mesh.dp_gradient_bytes_total",
                               self._trainable_bytes * self.steps_per_call)
                wire = self._dp_wire_bytes * self.steps_per_call
                _telemetry.inc("mesh.collective_bytes_total", wire,
                               axis="dp")
                if self._compress != "none":
                    _telemetry.inc("comm.compressed_bytes_total", wire)
                    _telemetry.inc(
                        "comm.uncompressed_bytes_total",
                        self._trainable_bytes * self.grad_accum
                        * self.steps_per_call)
            tokens = int(raws[0].size) if raws else 0
            if self._tp_row_out_units and tokens:
                act = sum(L * u for L, u in self._tp_row_out_units)
                _telemetry.inc("mesh.tp_allreduce_bytes_total",
                               tokens * act * 4)
                _telemetry.inc("mesh.collective_bytes_total",
                               tokens * act * 4, axis="tp")
            pp_n = shape.get("pp", 1)
            if pp_n > 1 and self._pp_width and tokens:
                pp_bytes = (tokens * self._pp_width * 4
                            * (pp_n - 1) * 2)
                _telemetry.inc("mesh.pp_stage_transfer_bytes_total",
                               pp_bytes)
                _telemetry.inc("mesh.collective_bytes_total", pp_bytes,
                               axis="pp")
        if _insight._active:
            # steady-state loop time from call inter-arrival: measured
            # on wall clocks the caller already pays, no device sync
            _insight.note_step(
                getattr(self, "_insight_label", "parallel.train_step"))
        return _wrap(loss)

    def prefetch(self, batches, depth=None, stall_timeout=None):
        """Wrap a batch iterable in a DevicePrefetcher targeting this
        step's batch shardings: jax.device_put runs on a background
        thread while the previous step computes, and __call__'s
        ensure_sharded detects the layout match and skips the re-put.

            for batch in step.prefetch(loader):
                loss = step(*batch)
        """
        return _pipeline.DevicePrefetcher(
            iter(batches), shardings=self.batch_shardings, depth=depth,
            stall_timeout=stall_timeout)

    def autotune(self, batches=None, sample_batch=None, space=None, **kw):
        """Search the step-config grid around THIS step's model, loss,
        optimizer and mesh (mx.autotune.search) and return
        ``(tuned_step, result)``.

        ``batches`` lends ONE sample batch (shaped like ``__call__``'s
        per-update batch, no lead axes) and is released via
        ``pipeline.take``; pass ``sample_batch=`` to skip the loader.
        Current weights sync to the block first so trials — and the
        returned tuned step — start from this step's training state.  The
        tuned step reuses the caller's optimizer (schedule position
        included); trials only ever run on hermetic clones.  Keyword args
        flow to ``mx.autotune.search`` (space=, hbm_budget=, force=, ...).
        """
        from .. import autotune as _autotune
        if sample_batch is None:
            if batches is None:
                raise MXNetError(
                    "autotune needs `batches` (a loader to borrow one "
                    "batch from) or an explicit `sample_batch`")
            sample_batch = next(iter(_pipeline.take(batches, 1)), None)
            if sample_batch is None:
                raise MXNetError("autotune: batches yielded nothing")
        sample = tuple(onp.asarray(b._data) if isinstance(b, ndarray)
                       else onp.asarray(b) for b in sample_batch)
        self.sync_to_block()
        result = _autotune.search(
            self.block, self.loss_fn, self.fopt.opt, self.mesh,
            self.batch_specs, sample, n_labels=self.n_labels,
            param_specs=self.param_specs, dp_axis=self.dp_axis,
            space=space, **kw)
        cfg = result.config
        if cfg is None:  # every trial failed: keep the caller's config
            return self, result
        mesh = self.mesh_config or self.mesh
        batch_specs, param_specs, dp_axis = (
            self.batch_specs, self.param_specs, self.dp_axis)
        if cfg.get("mesh"):
            # a mesh-axis search won on a different layout: rebuild the
            # step around the winning MeshConfig (specs re-derive)
            mesh = MeshConfig(**cfg["mesh"])
            batch_specs = mesh.batch_specs(
                *[len(s) if s is not None else 2 for s in self.batch_specs])
            param_specs = None
            dp_axis = "dp"
        precision = cfg.get("precision", "fp32")
        tuned = ShardedTrainStep(
            self.block, self.loss_fn, self.fopt.opt, mesh,
            batch_specs, n_labels=self.n_labels,
            param_specs=param_specs,
            steps_per_call=cfg["steps_per_call"], zero=cfg["zero"],
            grad_accum=cfg["grad_accum"], remat=cfg["remat"],
            dp_axis=dp_axis,
            precision=precision if precision in ("fp32", "fp8")
            else self.precision,
            grad_compress=self._compress)
        tuned._n_step = self._n_step
        return tuned, result

    def rebuild(self, mesh=None, sync=True):
        """Re-construct this step around a :class:`MeshConfig` (same
        block / loss / optimizer / zero / grad_accum / remat) — the
        fleet supervisor's degrade/re-expand primitive.  Batch and param
        specs re-derive from the new layout, so the result accepts the
        same per-update batches at a different dp size.

        ``mesh=None`` rebuilds on this step's own mesh — a re-jit in
        place, which is how the autotune Retuner makes freshly published
        kernel block shapes take effect at a checkpoint boundary without
        changing the layout.

        ``sync=True`` writes the current sharded weights back into the
        block first, so the rebuilt step starts from this step's live
        training state; the fleet path passes ``sync=False`` because a
        bitwise bundle restore immediately follows and the dying layout's
        device buffers may no longer be gatherable.
        """
        if mesh is None:
            mesh = self.mesh_config
            if mesh is None:
                raise MXNetError(
                    "rebuild() without a mesh needs a step built from a "
                    "MeshConfig (this one was built from a raw mesh)")
        if not isinstance(mesh, MeshConfig):
            raise MXNetError(
                f"rebuild needs a MeshConfig, got {type(mesh).__name__}")
        if sync:
            self.sync_to_block()
        else:
            # the block may still hold buffers the old step donated away;
            # revive them as zeros of the right shape — the bundle restore
            # that follows supplies the real values
            for p in self.block.collect_params().values():
                if p._data is None:
                    continue
                raw = p._data._data
                if getattr(raw, "is_deleted", lambda: False)():
                    p._data._rebind(jnp.zeros(raw.shape, raw.dtype))
        batch_specs = mesh.batch_specs(
            *[len(s) if s is not None else 2 for s in self.batch_specs])
        rebuilt = ShardedTrainStep(
            self.block, self.loss_fn, self.fopt.opt, mesh,
            batch_specs, n_labels=self.n_labels, param_specs=None,
            donate=self._donate, steps_per_call=self.steps_per_call,
            zero=self.zero, grad_accum=self.grad_accum,
            remat=self._remat_arg, dp_axis="dp",
            precision=self.precision, grad_compress=self._compress)
        rebuilt._n_step = self._n_step
        return rebuilt

    def sync_to_block(self):
        """Write current sharded weights back into the Block's Parameters
        (for save_parameters / eager eval after training)."""
        params = self.block.collect_params()
        for n, v in self._expand_pp({**self.trainable, **self.aux}).items():
            params[n]._data._rebind(v)

    # -- checkpoint / resume ------------------------------------------------
    def state_dict(self):
        """Gather weights + optimizer state to host numpy in a CANONICAL
        topology-independent layout: dp-partitioned (zero>0) state leaves
        are all-gathered, un-padded and reshaped back to their weight's
        shape, tp/sp shards gather to the full weight, and pp-stacked
        layer families unstack back to their per-layer names — a bundle
        saved at one (dp, tp, pp) layout restores bitwise at any other."""
        arrays = {}
        for n, v in self._expand_pp(dict(self.trainable)).items():
            arrays[f"trainable/{n}"] = onp.asarray(v)
        for n, v in self._expand_pp(dict(self.aux)).items():
            arrays[f"aux/{n}"] = onp.asarray(v)
        for n, s in self.states.items():
            zinfo = self._zero.get(n)
            grp = self._pp_groups.get(n)
            for i, leaf in enumerate(jax.tree_util.tree_leaves(s)):
                a = onp.asarray(leaf)
                if zinfo is not None:
                    shape, size, _ = zinfo
                    a = a[:size].reshape(shape)
                if grp is not None:
                    for j, member in enumerate(grp["members"]):
                        arrays[f"state/{member}/{i}"] = a[j]
                else:
                    arrays[f"state/{n}/{i}"] = a
        for site, hist in self.extra["fp8"].items():
            for k, v in hist.items():
                arrays[f"fp8/{site}/{k}"] = onp.asarray(v)
        for bname, v in self.extra["resid"].items():
            # canonical EF residual = the SUM over dp ranks: what the sum
            # of rank-local errors still owes the trajectory.  Restoring
            # it into one rank (load_state_dict) preserves the total
            # exactly at any dp size — f32 x + 0.0 is bitwise x.
            a = onp.asarray(v)
            arrays[f"efresid/{bname}"] = a.sum(axis=0, dtype=a.dtype)
        return {"arrays": arrays, "n_step": int(self._n_step)}

    def load_state_dict(self, bundle):
        """Restore from ``state_dict()``: values re-shard per THIS step's
        param_specs / zero / pipeline layout (which may differ from the
        saving run's — resume on a different (dp, tp, pp) re-stacks,
        re-pads and re-partitions the canonical arrays here)."""
        arrays = bundle["arrays"]

        def sh(n):
            return NamedSharding(self.mesh, self.param_specs.get(n, P()))

        def gather(prefix, n):
            # pp-stacked names re-stack from their canonical per-layer
            # entries; everything else reads directly
            grp = self._pp_groups.get(n)
            if grp is not None:
                return onp.stack([arrays[f"{prefix}/{m}"]
                                  for m in grp["members"]])
            return arrays[f"{prefix}/{n}"]

        for n in self.trainable:
            self.trainable[n] = jax.device_put(gather("trainable", n), sh(n))
        for n in self.aux:
            self.aux[n] = jax.device_put(gather("aux", n), sh(n))
        for n, s in self.states.items():
            leaves, treedef = jax.tree_util.tree_flatten(s)
            zinfo = self._zero.get(n)
            grp = self._pp_groups.get(n)
            tspec = self._zero_tp.get(n)
            new = []
            for i in range(len(leaves)):
                if grp is not None:
                    a = onp.stack([arrays[f"state/{m}/{i}"]
                                   for m in grp["members"]])
                else:
                    a = arrays[f"state/{n}/{i}"]
                if zinfo is not None:
                    _, size, padded = zinfo
                    flat = onp.ravel(a)
                    if padded != size:
                        flat = onp.pad(flat, (0, padded - size))
                    new.append(jax.device_put(
                        flat, NamedSharding(self.mesh, P(self.dp_axis))))
                elif tspec is not None:
                    new.append(jax.device_put(
                        a, NamedSharding(self.mesh, tspec)))
                else:
                    new.append(jax.device_put(a, sh(n)))
            self.states[n] = jax.tree_util.tree_unflatten(treedef, new)
        # fp8 amax histories: replicated scalars, read back directly.
        # Tolerate missing keys (resuming a pre-fp8 bundle into an fp8
        # step keeps the fresh zero history) and a changed history length
        # (clip newest-first / zero-pad oldest).
        fp8_new = {}
        for site, hist in self.extra["fp8"].items():
            fp8_new[site] = {}
            for k, v in hist.items():
                key = f"fp8/{site}/{k}"
                if key not in arrays:
                    fp8_new[site][k] = v
                    continue
                a = onp.asarray(arrays[key]).astype(onp.float32)
                h = int(v.shape[0])
                if a.shape[0] >= h:
                    a = a[:h]
                else:
                    a = onp.pad(a, (0, h - a.shape[0]))
                fp8_new[site][k] = jax.device_put(
                    a, NamedSharding(self.mesh, P()))
        resid_new = {}
        for bname, v in self.extra["resid"].items():
            key = f"efresid/{bname}"
            if key not in arrays:
                resid_new[bname] = v
                continue
            # canonical sum restores into rank 0; other ranks start with
            # zero error debt (bucket layout depends only on param names
            # and comm.bucket_mb, so it is dp-size invariant)
            a = onp.zeros(v.shape, onp.float32)
            a[0] = onp.asarray(arrays[key])
            resid_new[bname] = jax.device_put(
                a, NamedSharding(self.mesh, P(self.dp_axis)))
        self.extra = {"fp8": fp8_new, "resid": resid_new}
        self._n_step = int(bundle["n_step"])
        # keep lr schedules / bias correction on the restored timeline
        self.fopt.opt.num_update = self._n_step

    def save_states(self, fname):
        """Checkpoint weights + optimizer state + step count to one
        safetensors file (reference: Trainer.save_states, trainer.py:482;
        sharded arrays are gathered to host in canonical layout — the
        resume side re-shards them, even at a different dp size).
        safetensors rather than npz so bfloat16 params/state round-trip
        exactly."""
        from .. import serialization
        bundle = self.state_dict()
        return serialization.save_safetensors(
            fname, bundle["arrays"],
            metadata={"n_step": bundle["n_step"], "zero": self.zero,
                      "precision": self.precision,
                      "grad_compress": self._compress})

    def load_states(self, fname):
        """Resume from save_states: values re-sharded per param_specs
        (reference: Trainer.load_states, trainer.py:511)."""
        from .. import serialization
        loaded, meta = serialization.load_safetensors(
            fname, return_metadata=True)
        if str(meta.get("precision", "")) == "fp8":
            # tag survives cold loads so serve engines can apply their
            # quantization interaction guard (serve/engine.py)
            self.block._fp8_trained = True
        self.load_state_dict(
            {"arrays": loaded, "n_step": int(meta.get("n_step", 0))})
