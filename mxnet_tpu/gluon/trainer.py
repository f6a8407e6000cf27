"""gluon.Trainer.

Reference parity: python/mxnet/gluon/trainer.py:31-520 (optimizer + kvstore
orchestration: _allreduce_grads pushes/pullpulls per-param with priority
-param_index so first-needed params reduce first; _update applies fused
optimizer ops per device).

TPU-native design: gradients are jax Arrays; allreduce is the KVStore's
device/mesh psum; compute/comm overlap comes from PJRT async dispatch — the
python thread never blocks, matching the reference's engine overlap.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from .. import optimizer as opt
from .. import pipeline as _pipeline
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..base import MXNetError
from ..kvstore import create as create_kvstore, KVStoreBase
from .parameter import Parameter


class _FusedUpdate:
    """All parameter updates as ONE jitted multi-tensor XLA program.

    Reference analog: aggregate_num batching into multi_sgd_update /
    multi_mp_sgd_update / multi_lamb (src/operator/optimizer_op.cc:352-1130)
    — one kernel for many tensors instead of one dispatch per parameter.
    Here lr/wd/t arrive as traced arrays, so lr schedules and Adam's
    per-step bias correction do NOT retrace; the program recompiles only
    when shapes or static hyperparameters (momentum/betas/clip) change.
    """

    def __init__(self, optimizer):
        self.opt = optimizer
        self._jit = None
        self._static = None

    def applicable(self):
        o = self.opt
        return (getattr(o, "_FUSED_FAMILY", None) in ("sgd", "adam")
                and not o.multi_precision)

    def _build(self, family, static):
        rule = type(self.opt)._rule

        if family == "sgd":
            momentum, rescale_ignored, clip = static

            def run(ws, gs, ss, lrs, wds, ts, rescale):
                outs = [rule(w, g, s[0] if s else None, lrs[j], wds[j],
                             momentum, rescale, clip)
                        for j, (w, g, s) in enumerate(zip(ws, gs, ss))]
                return ([o[0] for o in outs],
                        [(o[1],) if o[1] is not None else () for o in outs])
        else:  # adam family
            beta1, beta2, eps, clip = static

            def run(ws, gs, ss, lrs, wds, ts, rescale):
                outs = [rule(w, g, s[0], s[1], lrs[j], wds[j], ts[j],
                             beta1, beta2, eps, rescale, clip)
                        for j, (w, g, s) in enumerate(zip(ws, gs, ss))]
                return ([o[0] for o in outs],
                        [(o[1], o[2]) for o in outs])

        return jax.jit(run, donate_argnums=(0, 2))

    def __call__(self, work, states):
        """work: list of (index, Parameter); states: Updater.states dict."""
        o = self.opt
        family = o._FUSED_FAMILY
        clip = o.clip_gradient or -1.0
        static = ((o.momentum, None, clip) if family == "sgd"
                  else (o.beta1, o.beta2, o.epsilon, clip))
        if self._jit is None or self._static != (family, static):
            self._jit = self._build(family, static)
            self._static = (family, static)

        lrs, wds, ts = [], [], []
        ws, gs, ss, state_nds = [], [], [], []
        for i, p in work:
            o._update_count(i)
            lrs.append(o._get_lr(i))
            wds.append(o._get_wd(i))
            ts.append(float(max(o._index_update_count[i], 1)))
            ws.append(p.data()._data)
            gs.append(p.grad()._data)
            s = states[i]
            nds = (() if s is None
                   else tuple(s) if isinstance(s, tuple) else (s,))
            state_nds.append(nds)
            ss.append(tuple(nd._data for nd in nds))

        new_ws, new_ss = self._jit(
            ws, gs, ss, jnp.asarray(lrs, jnp.float32),
            jnp.asarray(wds, jnp.float32), jnp.asarray(ts, jnp.float32),
            jnp.asarray(o.rescale_grad, jnp.float32))

        for (i, p), nw, nss, nds in zip(work, new_ws, new_ss, state_nds):
            p.data()._rebind(nw.astype(p.data().dtype))
            for nd, raw in zip(nds, nss):
                nd._rebind(raw)


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, dict):
            self._param_names = list(params.keys())
            params = list(params.values())
        else:
            self._param_names = [p.name for p in params]
        if not params:
            raise MXNetError("no parameters to optimize")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError(f"expected Parameter, got {type(p)}")
            self._param2idx[id(p)] = i
            self._params.append(p)
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._params_to_init = []
        self._contains_sparse_grad = False
        self._fused_update = None
        self._finite_check = None
        self._grad_norm_fn = None
        self._norm_window = None  # mx.pipeline.DeferredWindow, built lazily
        #: steps skipped by the non-finite grad guard (see step())
        self.nonfinite_steps = 0

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise MXNetError(
                    "optimizer_params must be None when optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _init_kvstore(self):
        if self._kvstore_type is None or self._kvstore_type == "":
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            kv = (self._kvstore_type
                  if isinstance(self._kvstore_type, KVStoreBase)
                  else create_kvstore(self._kvstore_type))
            self._kvstore = kv
            if self._compression_params and hasattr(kv, "set_gradient_compression"):
                kv.set_gradient_compression(self._compression_params)
            has_sparse = any(getattr(p, "_grad_stype", "default") ==
                             "row_sparse" for p in self._params)
            if self._update_on_kvstore is None:
                # env/config override first (reference: MXNET_UPDATE_ON_KVSTORE,
                # trainer.py:36); default False — fused local update is faster.
                # Row-sparse gradients force optimizer-on-store, like the
                # reference (trainer.py: contains_sparse check).
                from .. import config
                forced = config.get("update_on_kvstore")
                self._update_on_kvstore = (bool(forced)
                                           if forced is not None
                                           else has_sparse)
            elif has_sparse and not self._update_on_kvstore:
                raise MXNetError(
                    "update_on_kvstore=False is not supported with "
                    "row_sparse gradients (reference trainer.py raises too)")
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, p in enumerate(self._params):
                if p._data is not None:
                    self._kvstore.init(i, p.data())
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def allreduce_grads(self):
        """Reduce gradients across devices/workers (reference:
        trainer.py:363 _allreduce_grads)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        for i, p in enumerate(self._params):
            if p.grad_req != "null" and p._data is not None:
                grads = p.list_grad()
                if self._update_on_kvstore:
                    # optimizer runs in the store; weights pulled in _update
                    self._kvstore.push(i, grads, priority=-i)
                else:
                    self._kvstore.pushpull(i, grads, out=grads, priority=-i)

    # -- non-finite grad guard (resilience layer; see docs/FAULT_TOLERANCE) --
    def _guard_active(self):
        """The guard runs when opted in (mx.config trainer.skip_nonfinite)
        or automatically once an AMP loss scaler is attached (reference:
        amp's skip-on-overflow contract, python/mxnet/amp/loss_scaler.py)."""
        from .. import config
        return (getattr(self, "_amp_loss_scaler", None) is not None
                or bool(config.get("trainer.skip_nonfinite")))

    def _grad_raws(self):
        """Every gradient's raw array; of a row-sparse one the rows it
        holds (they carry its norm, and whether it is finite)."""
        grads = [p.grad() for p in self._params
                 if p.grad_req != "null" and p._data is not None]
        return [(g.data if hasattr(g, "indices") else g)._data
                for g in grads]

    def _grads_finite(self):
        """One fused XLA reduction over every gradient -> scalar bool."""
        raws = self._grad_raws()
        if not raws:
            return True
        if self._finite_check is None:
            self._finite_check = jax.jit(
                lambda gs: jnp.all(jnp.asarray(
                    [jnp.isfinite(g).all() for g in gs])))
        if _pipeline._guard_depth:
            _pipeline.note_host_sync("trainer.finite_check")
        return bool(self._finite_check(raws))

    def _grad_norm_device(self):
        """Global gradient L2 norm as ONE fused XLA reduction, returned as
        an UNFETCHED device scalar so callers choose when (if ever) to pay
        the host sync."""
        raws = self._grad_raws()
        if not raws:
            return None
        if self._grad_norm_fn is None:
            self._grad_norm_fn = jax.jit(
                lambda gs: jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in gs)))
        return self._grad_norm_fn(raws)

    def _grad_norm(self):
        """Global gradient L2 norm as a host float (telemetry: the
        per-step health signal operators watch for divergence).  This is
        a host sync — the step loop uses ``_note_grad_norm`` instead,
        which defers the fetch through a bounded window."""
        dev = self._grad_norm_device()
        if dev is None:
            return 0.0
        if _pipeline._guard_depth:
            _pipeline.note_host_sync("trainer.grad_norm")
        return float(dev)

    @staticmethod
    def _observe_grad_norm(norm):
        if math.isfinite(norm):
            _telemetry.observe("trainer.grad_norm", norm)

    def _note_grad_norm(self):
        """Record the step's grad norm without syncing: the device scalar
        is pushed into a bounded DeferredWindow and fetched only when the
        window overflows or ``drain_telemetry()`` runs (epoch boundaries,
        snapshots)."""
        dev = self._grad_norm_device()
        if dev is None:
            return
        if self._norm_window is None:
            self._norm_window = _pipeline.DeferredWindow()
        self._norm_window.push(dev, self._observe_grad_norm)

    def drain_telemetry(self):
        """Fetch every deferred grad-norm into the telemetry histogram and
        refresh the per-device ``memory.*`` gauges.  Call at epoch
        boundaries / before ``mx.telemetry.snapshot()`` for up-to-the-step
        numbers; the estimator's TelemetryHandler does."""
        if _trace._active:
            with _trace.span("train.drain", category="train",
                             pending=(len(self._norm_window)
                                      if self._norm_window is not None
                                      else 0)):
                if self._norm_window is not None:
                    self._norm_window.drain()
                if _telemetry._active:
                    _telemetry.record_memory()
            return
        if self._norm_window is not None:
            self._norm_window.drain()
        if _telemetry._active:
            _telemetry.record_memory()

    def _skip_step(self):
        """Count and absorb a non-finite step: weights untouched, the AMP
        scale backs off, accumulated ('add') grads are cleared so the
        poison cannot leak into the next step."""
        from .. import fault
        self.nonfinite_steps += 1
        fault.record("trainer.nonfinite_skip")
        if _telemetry._active:
            _telemetry.inc("trainer.nonfinite_total")
        from .. import blackbox as _blackbox
        if _blackbox._active:
            # non-finite escalation is a terminal-class anomaly: freeze
            # the evidence window while the poisoned state is still live
            _blackbox.dump(trigger="nonfinite",
                           reason=f"non-finite gradients skipped "
                                  f"(count={self.nonfinite_steps})")
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None:
            scaler.update_scale(True)
        for p in self._params:
            if p.grad_req == "add" and p._data is not None:
                p.zero_grad()

    def step(self, batch_size, ignore_stale_grad=False):
        """Reference: trainer.py:334.

        With the non-finite guard active, a step whose gradients contain
        inf/NaN is skipped (counted in ``nonfinite_steps`` and
        ``mx.fault.stats()``) instead of poisoning the weights.  The check
        runs *after* the cross-worker reduce where possible so every rank
        takes the same decision; with ``update_on_kvstore`` the optimizer
        runs inside the push, so there the local gradient is checked
        before pushing."""
        if not _telemetry._active:
            return self._step_impl(batch_size, ignore_stale_grad)
        # metrics wrapper: wall time, step count, and the global grad norm
        # (observed pre-update so a skipped step still reports what blew up)
        t0 = time.perf_counter()
        self._note_grad_norm()
        try:
            return self._step_impl(batch_size, ignore_stale_grad)
        finally:
            _telemetry.inc("trainer.steps_total")
            _telemetry.observe("trainer.step_seconds",
                               time.perf_counter() - t0)

    def _step_impl(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        guard = self._guard_active()
        if guard and self._update_on_kvstore and not self._grads_finite():
            self._skip_step()
            return
        self._allreduce_grads()
        if guard and not self._update_on_kvstore and not self._grads_finite():
            self._skip_step()
            return
        if guard and getattr(self, "_amp_loss_scaler", None) is not None:
            self._amp_loss_scaler.update_scale(False)
        self._update(ignore_stale_grad)

    def update(self, batch_size, ignore_stale_grad=False):
        """Apply optimizer without allreduce (assumes grads already reduced;
        reference: trainer.py update)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        updater = self._updaters[0]
        if self._update_on_kvstore:
            for i, p in enumerate(self._params):
                if p.grad_req != "null" and p._data is not None:
                    # weights were updated inside the store: pull them back
                    self._kvstore.pull(i, out=p.data(), priority=-i)
            return
        from ..ndarray.sparse import BaseSparseNDArray
        work, sparse_work = [], []
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or p._data is None:
                continue
            if isinstance(p.grad(), BaseSparseNDArray):
                sparse_work.append((i, p))  # row-wise lazy/densified update
            else:
                work.append((i, p))
        for i, p in sparse_work:
            updater(i, p.grad(), p.data())
        if not work:
            return
        if self._fused_update is None:
            fu = _FusedUpdate(self._optimizer)
            self._fused_update = fu if fu.applicable() else False
        if self._fused_update:
            for i, p in work:
                if i not in updater.states:
                    updater.states[i] = \
                        self._optimizer.create_state_multi_precision(i, p.data())
            self._fused_update(work, updater.states)
        else:
            for i, p in work:
                updater(i, p.grad(), p.data())

    # -- elastic resume (docs/FAULT_TOLERANCE.md "Preemption & elastic
    # resume"): everything save_states misses — the AMP loss scale and its
    # backoff window, the non-finite skip counter — plus the optimizer/
    # updater states as bytes, so a TrainState bundle restores the trainer
    # to the exact step it was preempted at -------------------------------
    def state_dict(self):
        scaler = getattr(self, "_amp_loss_scaler", None)
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            opt_blob = self._kvstore._updater.get_states(dump_optimizer=True)
        else:
            opt_blob = self._updaters[0].get_states(dump_optimizer=True)
        return {"optimizer": opt_blob,
                "nonfinite_steps": self.nonfinite_steps,
                "loss_scaler": None if scaler is None
                else scaler.state_dict()}

    def load_state_dict(self, state):
        self.nonfinite_steps = int(state.get("nonfinite_steps", 0))
        scaler_state = state.get("loss_scaler")
        if scaler_state is not None:
            if getattr(self, "_amp_loss_scaler", None) is None:
                from ..amp.loss_scaler import LossScaler
                self._amp_loss_scaler = LossScaler()
            self._amp_loss_scaler.load_state_dict(scaler_state)
        blob = state.get("optimizer")
        if blob is None:
            return
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore._updater.set_states(blob)
            self._optimizer = (self._kvstore._updater.optimizer
                               or self._optimizer)
        else:
            self._updaters[0].set_states(blob)
            self._optimizer = self._updaters[0].optimizer
        self._optimizer.param_dict = {i: p
                                      for i, p in enumerate(self._params)}
        self._fused_update = None  # rebuilt against the restored optimizer

    def save_states(self, fname):
        """Reference: trainer.py:482.  Crash-atomic like
        Block.save_parameters (temp + fsync + os.replace)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            from .. import serialization
            serialization.atomic_write_bytes(
                fname, self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        """Reference: trainer.py:511.  Validates a ``.sha256`` sidecar
        when present (CheckpointHandler writes one)."""
        from .. import serialization
        serialization.verify_checksum(fname)
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._optimizer or self._optimizer
        else:
            with open(fname, "rb") as f:
                self._updaters[0].set_states(f.read())
            self._optimizer = self._updaters[0].optimizer
        self._optimizer.param_dict = {i: p for i, p in enumerate(self._params)}
        self._fused_update = None  # rebuilt against the (possibly new) optimizer
