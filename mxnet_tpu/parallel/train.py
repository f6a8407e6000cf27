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

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import blackbox as _blackbox
from .. import config as _config
from .. import functional
from .. import insight as _insight
from .. import pipeline as _pipeline
from .. import trace as _trace
from ..amp import fp8 as _fp8
from ..base import MXNetError
from ..numpy.multiarray import ndarray, _wrap
from . import mesh as _pmesh
from .collectives import quantized_mean
from .layout import DP, FLAT, PARAM, StateLayout, megatron_specs
from .mesh import MeshConfig, activation_sharding


def _unwrap(tree):
    return jax.tree_util.tree_map(
        lambda x: x._data if isinstance(x, ndarray) else x, tree,
        is_leaf=lambda x: isinstance(x, ndarray))


def _leaves_and_bytes(tree):
    """A placed tree's leaf count and global bytes, from shapes and item
    sizes alone (no device call): what the placement spans carry."""
    leaves = jax.tree_util.tree_leaves(tree)
    return {"leaves": len(leaves),
            "bytes": sum(l.size * l.dtype.itemsize for l in leaves)}


class FunctionalOptimizer:
    """Pure-functional adapter over a mxnet_tpu Optimizer instance so its
    update rule can run inside a jit/pjit trace (the analog of the fused
    multi-tensor update ops, src/operator/optimizer_op.cc:352).

    States/settings key by STRUCTURAL NAME, not position: dict ordering
    through a jit boundary is canonicalized, so a positional index could
    bind lr_mult/wd_mult to the wrong parameter vs the eager Trainer
    (collect_params order)."""

    def __init__(self, optimizer):
        self.opt = optimizer

    def init(self, name, raw_param):
        return _unwrap(self.opt.create_state(name, _wrap(raw_param)))

    def update(self, raw_params, raw_grads, states, lr, t):
        new_p, new_s = {}, {}
        saved_count = self.opt.num_update
        # thread the (traced) step count into the update rules so
        # Adam-family bias correction advances inside the compiled step;
        # restored below so host-side bookkeeping never sees a tracer
        self.opt.num_update = t
        try:
            for name in raw_params:
                wrapped = jax.tree_util.tree_map(
                    _wrap, states[name], is_leaf=lambda x: x is None)
                w, s = self.opt._update_impl(
                    raw_params[name], raw_grads[name], wrapped, lr,
                    self.opt._get_wd(name))
                new_p[name] = w.astype(raw_params[name].dtype)
                new_s[name] = _unwrap(s)
        finally:
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
    zero: ZeRO optimizer-state partitioning level over the dp axis
        (the per-leaf plan is ``parallel/layout.py``'s, kept as
        ``step.layout``).
        0 — state shards like its weight (replicated under pure dp).
        1 — optimizer state lives in 1/dp shards; each step
        reduce-scatters grads, updates the local shard, all-gathers the
        new params — all inside the one jitted program so XLA overlaps
        the collectives with compute.
        2 — additionally keeps reduced gradients (incl. the grad_accum
        accumulator) laid out in the same dp shards, so full gradients
        never materialize replicated.
    grad_accum: accumulate gradients over K lax.scan microbatches before
        ONE optimizer update (batch arrays gain a leading K axis).
        Distinct from steps_per_call, which applies an update every step.
    remat: recompute the whole fwd inside the step's bwd — the values of
        ``HybridBlock.hybridize(remat=...)`` (True, 'dots', names, a
        policy); None inherits the block's own flag.  A flag on a *child*
        (``layer.hybridize(remat=...)``) bounds that child's call alone.
    precision: "fp32" (default) or "fp8" — fp8 runs eligible Dense
        matmuls e4m3-forward / e5m2-backward with per-tensor delayed
        scaling (mx.amp.fp8); the amax histories thread through the step
        as donated state and checkpoint with the optimizer bundle.
        Master weights, accumulation and the optimizer update stay fp32.
    grad_compress: None (read the ``comm.compress`` knob), "none",
        "int8" or "bf16" — error-feedback compression of the per-
        microbatch dp gradient all-reduce.  Gradients flatten into
        ``comm.bucket_mb`` buckets; each bucket quantizes (shared scale
        = pmax over ranks), psums the quantized values (as f32
        operands: the lowered program has no 8-bit wire) and carries the
        quantization error into the next step's gradient (EF-SGD), so
        the compression error telescopes instead of accumulating.  The
        independent per-bucket collectives are what XLA's latency-hiding
        scheduler overlaps with backward compute.  Requires a pure-dp
        mesh (tp=pp=sp=1) and every batch arg sharded over dp; silently
        off at dp=1.
    """

    # mx.insight: attribution label (trials set their own), capture ran
    _insight_label = "parallel.train_step"
    _insight_done = False

    def __init__(self, block, loss_fn, optimizer, mesh, batch_specs,
                 n_labels=1, param_specs=None, donate=True,
                 steps_per_call=1, zero=0, grad_accum=1, remat=None,
                 dp_axis="dp", precision="fp32", grad_compress=None):
        # construction is one host span, ``mx/train.init``, holding
        # ``mx/train.plan``, ``mx/train.place`` and ``mx/train.states``;
        # mx.trace.startup() keeps it with their counts
        with _trace.span("train.init", category="train"):
            self._init(block, loss_fn, optimizer, mesh, batch_specs,
                       n_labels, param_specs, donate, steps_per_call, zero,
                       grad_accum, remat, dp_axis, precision, grad_compress)

    def _init(self, block, loss_fn, optimizer, mesh, batch_specs, n_labels,
              param_specs, donate, steps_per_call, zero, grad_accum, remat,
              dp_axis, precision, grad_compress):
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
        # lead axes are folded in below) — mx.autotune.tune_step rebuilds
        # steps with different lead-axis geometry from these
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
        t_shapes = {n: v.shape for n, v in trainable.items()}
        a_shapes = {n: v.shape for n, v in aux.items()}
        if param_specs is None:   # a name without a spec is replicated
            param_specs = (megatron_specs({**t_shapes, **a_shapes})
                           if "tp" in mesh.shape else {})

        # -- plan (parallel/layout.py) --
        bucket_elems = 0
        if self._compress != "none":
            bucket_elems = max(1, int(
                float(_config.get("comm.bucket_mb")) * (1 << 20) / 4))
        with _trace.span("train.plan", category="train") as plan:
            self.layout = lay = StateLayout(
                t_shapes, a_shapes, param_specs, dict(mesh.shape),
                zero=self.zero, dp_axis=dp_axis, fp8=self._fp8,
                bucket_elems=bucket_elems)
            plan.set(**lay.census(
                {n: v.dtype.itemsize for n, v in trainable.items()}))
        self.param_specs = lay.param_specs
        self.fopt = FunctionalOptimizer(optimizer)
        if self.zero and not type(self.fopt.opt)._zero_partitionable:
            raise MXNetError(
                f"{type(self.fopt.opt).__name__} is not elementwise "
                "(layer-wise norms / per-tensor RNG); it cannot run on "
                "ZeRO shards — use zero=0")

        # -- place: parameters, optimizer state, extra state --
        # (both spans count from shapes: nothing here waits for the device)
        sh = self._sh
        with _trace.span("train.place", category="train") as place:
            self.trainable = {
                n: jax.device_put(v, sh(lay.param_spec(n)))
                for n, v in lay.stack(trainable).items()}
            self.aux = {
                n: jax.device_put(v, sh(lay.param_spec(n)))
                for n, v in lay.stack(aux).items()}
            place.set(**_leaves_and_bytes((self.trainable, self.aux)))
        with _trace.span("train.states", category="train") as placed:
            self.states = {}
            for n, v in self.trainable.items():
                leaf = lay.leaves[n]
                s = self.fopt.init(n, lay.to_state_form(n, v))
                bad = [l.shape for l in jax.tree_util.tree_leaves(s)
                       if l.shape != leaf.state_shape]
                if bad and leaf.form != PARAM:
                    raise MXNetError(
                        f"{type(self.fopt.opt).__name__} state for '{n}' is "
                        f"not elementwise (leaf shapes {bad}); zero>0 "
                        "unsupported")
                self.states[n] = jax.device_put(s, sh(leaf.state_spec))
            self._fp8_margin = 1.0
            fp8_state = jax.device_put(
                _fp8.init_state(lay.fp8_sites), sh(P()))
            if self._fp8:
                self._fp8_margin = float(_config.get("amp.fp8_margin"))
                # serve-side engines key quantization guards off this tag
                # (it also rides save_states metadata for cold loads)
                block._fp8_trained = True
            resid_state = jax.device_put(
                {b: jnp.zeros(shape, jnp.float32)
                 for b, shape in lay.resid_shapes.items()}, sh(P(dp_axis)))
            self.extra = {"fp8": fp8_state, "resid": resid_state}
            if lay.names(FLAT):
                self._build_zero_update()
            placed.set(**_leaves_and_bytes((self.states, self.extra)))

        # -- build: the step, then its accumulate / steps_per_call wrappers --
        def base_step(trainable, aux, states, extra, rng, lr, t, *batch):
            scales = (_fp8.scales_from_state(extra["fp8"], self._fp8_margin)
                      if self._fp8 else {})
            loss, mutated, grads, fwd_amax, g_amax, resid = self._fwd_bwd(
                trainable, aux, rng, batch, scales, extra["resid"])
            new_fp8 = (_fp8.roll_state(extra["fp8"], fwd_amax, g_amax)
                       if self._fp8 else extra["fp8"])
            new_tr, new_states = self._apply_updates(
                trainable, grads, states, lr, t)
            return (new_tr, {**aux, **mutated}, new_states,
                    {"fp8": new_fp8, "resid": resid}, loss)

        spec_list = list(batch_specs)
        step = base_step

        if self.grad_accum > 1:
            K = self.grad_accum
            # At zero>=2 the accumulator holds gradients in the form of
            # their optimizer state (flat dp shards, or the dp-inserted
            # spec of a tensor-sharded leaf) — the long-lived gradient
            # memory is 1/dp per device and each microbatch grad
            # reduce-scatters straight into it.
            in_state_form = self.zero >= 2

            def step(trainable, aux, states, extra, rng, lr, t, *batches):
                # microbatches carry a leading K axis; ONE update at the end
                def g_init(n, v):
                    if not in_state_form:
                        return jnp.zeros(v.shape, v.dtype)
                    return lay.pin_state(n, jnp.zeros(
                        lay.leaves[n].state_shape, v.dtype), self.mesh)

                acc0 = {n: g_init(n, v) for n, v in trainable.items()}
                # scales come from the PRE-update histories once for all
                # microbatches; the history rolls ONCE per update with the
                # max amax over the scan (delayed scaling's contract)
                scales = (_fp8.scales_from_state(
                    extra["fp8"], self._fp8_margin) if self._fp8 else {})
                zf32 = jnp.zeros((), jnp.float32)
                fwd0 = {s: (zf32, zf32) for s in lay.fp8_sites}
                g0 = {s: zf32 for s in lay.fp8_sites}

                def body(carry, xs):
                    aux_c, acc, resid, fa, ga, i = carry
                    loss, mutated, grads, fwd_amax, g_amax, resid = (
                        self._fwd_bwd(
                            trainable, aux_c, jax.random.fold_in(rng, i),
                            xs, scales, resid))

                    def add(n):
                        g = grads[n]
                        if in_state_form:
                            g = lay.pin_state(
                                n, lay.to_state_form(n, g), self.mesh)
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
                new_fp8 = (_fp8.roll_state(extra["fp8"], fa, ga)
                           if self._fp8 else extra["fp8"])
                new_tr, new_states = self._apply_updates(
                    trainable, grads, states, lr, t,
                    grads_in_state_form=in_state_form)
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
        state_sh = self._state_shardings()
        self._step = jax.jit(
            step,
            in_shardings=state_sh + (sh(P()), sh(P()), sh(P()))
            + self.batch_shardings,
            out_shardings=state_sh + (sh(P()),),
            donate_argnums=(0, 1, 2, 3) if donate else ())
        self._n_step = 0

    # -- step internals -----------------------------------------------------
    def _sh(self, spec):
        return NamedSharding(self.mesh, spec)

    def _state_shardings(self):
        """The layout's specs bound to this step's mesh: a prefix of
        ``(trainable, aux, states, extra)`` (one sharding covers a leaf's
        whole optimizer state, the fp8 histories, the residuals)."""
        lay, sh = self.layout, self._sh
        return ({n: sh(lay.param_spec(n)) for n in self.trainable},
                {n: sh(lay.param_spec(n)) for n in self.aux},
                {n: sh(lay.leaves[n].state_spec) for n in self.states},
                {"fp8": sh(P()), "resid": sh(P(self.dp_axis))})

    def _loss_and_grad(self, trainable, aux, rng, inputs, labels):
        def lossf(tr):
            # the one scope of the forward; JAX names its backward
            # transpose(jvp(mx.fwd)) by itself
            with jax.named_scope("mx.fwd"):
                out, mutated = functional.functional_call(
                    self.block, self.layout.unstack({**tr, **aux}), *inputs,
                    train=True, rng_key=rng)
                return (self.loss_fn(out, *labels),
                        self.layout.stack(mutated))

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
                    self.block, self.layout.unstack({**tr, **aux}), *inputs,
                    train=True, rng_key=rng)
                loss = self.loss_fn(out, *labels)
                amax = dict(ctx.amax)
            return loss, (self.layout.stack(mutated), amax)

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

    def _fwd_bwd(self, trainable, aux, rng, batch, scales, resid):
        """One microbatch forward+backward; returns
        ``(loss, mutated, grads, fwd_amax, g_amax, new_resid)`` with the
        amax dicts empty unless fp8 and ``new_resid`` passed through
        unchanged unless compression is on."""
        if self._compress != "none":
            return self._compressed_fwd_bwd(
                trainable, aux, rng, batch, scales, resid)
        return (*self._local_fwd_bwd(trainable, aux, rng, batch, scales),
                resid)

    def _local_fwd_bwd(self, trainable, aux, rng, batch, scales):
        """``(loss, mutated, grads, fwd_amax, g_amax)`` of one (micro)batch —
        inputs then ``n_labels`` labels — at the step's precision, before
        any explicit reduction."""
        inputs = batch[:len(batch) - self.n_labels]
        labels = batch[len(batch) - self.n_labels:]
        if self._fp8:
            return self._fp8_loss_and_grad(
                trainable, aux, rng, inputs, labels, scales)
        (loss, mutated), grads = self._loss_and_grad(
            trainable, aux, rng, inputs, labels)
        return loss, mutated, grads, {}, {}

    def _compressed_fwd_bwd(self, trainable, aux, rng, batch, scales, resid):
        """Error-feedback compressed dp gradient reduction.

        A shard_map over the dp axis makes the per-rank gradient explicit
        (outside shard_map the dp reduction is implicit in XLA's psum of
        the batch-sharded backward): each rank runs loss+grad on its
        local microbatch shard, flattens grads into the configured
        buckets, adds its carried residual, quantizes against a SHARED
        scale (pmax over ranks — so dequantization is exact w.r.t. what
        was sent) and psums the int8-valued / bf16-snapped payload as f32
        operands.  The residual
        ``c - dequant(sent)`` carries to the next microbatch (EF-SGD),
        so the quantization error telescopes instead of biasing the
        trajectory.  Each bucket's psum is an independent collective —
        exactly the granularity XLA's latency-hiding scheduler overlaps
        with the remaining backward compute.
        """
        dpx = self.dp_axis
        dp_n = int(self.mesh.shape[dpx])
        buckets = self.layout.buckets

        def local(tr, ax, rngv, res, sc, *batch):
            # decorrelate dropout across ranks: outside shard_map the
            # same key spans the global batch, so fold in the rank
            rngl = jax.random.fold_in(rngv, jax.lax.axis_index(dpx))
            loss, mutated, grads, fwd_amax, g_amax = self._local_fwd_bwd(
                tr, ax, rngl, batch, sc)
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
                red, sent = quantized_mean(c, dpx, dp_n, self._compress)
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
        return fn(trainable, aux, rng, resid, scales, *batch)

    def _build_zero_update(self):
        dpx = self.dp_axis
        fopt = self.fopt
        names = self.layout.names(FLAT)

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
            new_w, new_s = fopt.update(w_flat, g_flat, zstates, lr, t)
            gathered = {n: jax.lax.all_gather(new_w[n], dpx, tiled=True)
                        for n in names}
            return gathered, new_s

        self._zero_update = _zupd

    @jax.named_scope("mx.optimizer")
    def _apply_updates(self, trainable, grads, states, lr, t,
                       grads_in_state_form=False):
        """Optimizer update dispatch by the form of a leaf's state: FLAT
        leaves go through the shard_map path, DP leaves through a
        sharding-constrained elementwise update (reduce-scatter over dp,
        update the chunk, gather back to the tensor-sharded layout),
        everything else through the plain fused update."""
        lay, mesh = self.layout, self.mesh
        new_tr, new_st = {}, {}
        rest = {n: v for n, v in trainable.items()
                if lay.leaves[n].form == PARAM}
        if rest:
            p, s = self.fopt.update(
                rest, grads, {n: states[n] for n in rest}, lr, t)
            new_tr.update(p)
            new_st.update(s)
        names = lay.names(DP)
        if names:
            tpw = {n: lay.pin_state(n, trainable[n], mesh) for n in names}
            tpg = {n: lay.pin_state(n, grads[n], mesh) for n in names}
            p, s = self.fopt.update(
                tpw, tpg, {n: states[n] for n in names}, lr, t)
            # the state keeps the dp-inserted spec (jit out_shardings pin it)
            new_tr.update({n: lay.pin_param(n, p[n], mesh) for n in names})
            new_st.update(s)
        names = lay.names(FLAT)
        if not names:
            return new_tr, new_st
        g_flat = {n: grads[n] for n in names}
        if not grads_in_state_form:
            g_flat = {n: lay.to_state_form(n, g) for n, g in g_flat.items()}
            if self.zero >= 2:
                # ZeRO-2: pin the flat grads to the dp shards so the full
                # gradient never materializes replicated
                g_flat = {n: lay.pin_state(n, g, mesh)
                          for n, g in g_flat.items()}
        w_flat = {n: lay.to_state_form(n, trainable[n]) for n in names}
        gathered, new_zs = self._zero_update(
            w_flat, g_flat, {n: states[n] for n in names}, lr, t)
        for n in names:
            w = lay.from_state_form(n, gathered[n])
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
        args = (self.trainable, self.aux, self.states, self.extra, rng, lr,
                t, *raws)
        if _insight._active and not self._insight_done:
            # one-time attribution capture BEFORE dispatch (donation
            # deletes the input buffers): trace-only .lower(), no
            # backend compile and no note_compile, so the recompile
            # detector and compile counters stay untouched
            self._insight_done = True
            with self._trace_scope():
                _insight.capture_jit(
                    self._insight_label, self._step, args, kind="train")
        # the scope surrounds the call so the layers' constrain() hooks,
        # the ring-attention routing and the flash kernel's shard_map see
        # the mesh while jit traces (first call) — no-op afterwards
        with self._trace_scope(), \
                _trace.span("train.dispatch", category="train"):
            out = self._step(*args)
        self.trainable, self.aux, self.states, self.extra, loss = out
        self._n_step += self.steps_per_call
        if _insight._active:
            # steady-state loop time from call inter-arrival: measured
            # on wall clocks the caller already pays, no device sync
            _insight.note_step(self._insight_label)
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
        unstacked = self.layout.unstack({**self.trainable, **self.aux})
        for n, v in unstacked.items():
            params[n]._data._rebind(v)

    # -- checkpoint / resume ------------------------------------------------
    def state_dict(self):
        """Gather weights + optimizer state to host numpy in the layout's
        CANONICAL topology-independent form (``StateLayout.to_canonical``):
        a bundle saved at one (dp, tp, pp) layout restores bitwise at any
        other."""
        arrays = self.layout.to_canonical(
            self.trainable, self.aux, self.states, self.extra)
        return {"arrays": arrays, "n_step": int(self._n_step)}

    def load_state_dict(self, bundle):
        """Restore from ``state_dict()``: values re-shard per THIS step's
        layout (which may differ from the saving run's — resume on a
        different (dp, tp, pp) re-stacks, re-pads and re-partitions the
        canonical arrays)."""
        host = self.layout.from_canonical(
            bundle["arrays"],
            (self.trainable, self.aux, self.states, self.extra))
        self.trainable, self.aux, self.states, self.extra = jax.device_put(
            host, self._state_shardings())
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
