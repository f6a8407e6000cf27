"""Continuous-batching serve engine: ONE resident compiled decode step.

Design (PAPERS.md "Portable O(1) Autoregressive Caching for Inference"
is the blueprint; "A Learned Performance Model for TPUs" motivates the
static-shape discipline):

- **Fixed footprint.** The KV cache — per layer one
  (max_slots, max_seq, heads * head_dim) K and V array, a row as the
  projections give it — is allocated once at construction and *donated*
  through every compiled call, so the decode working set never grows,
  shrinks, or reallocates no matter how requests arrive: the decode step
  writes its row in place and, on a TPU, reads only the rows the live
  slots hold (``ops/attention.py::decode_attention``). Every device shape
  in the engine is static.
- **One decode executable.** All live requests advance together through
  a single AOT-compiled step (batch dim = max_slots); idle slots ride
  along masked. Prefill gets one executable per prompt-length *bucket*
  (``serve.buckets``), prompts pad up to the smallest fitting bucket,
  and ``warmup()`` compiles the whole grid up front — after that the
  PR 2 recompile detector (``telemetry.note_compile``) must stay silent,
  and the engine counts any post-warmup compile as a bug signal.
- **Continuous batching.** A slot is freed the moment its request
  finishes (EOS or token budget) and the next queued request is admitted
  into it mid-flight — no waiting for the batch to drain, the property
  that buys the ≥2x over sequential decode in
  benchmark/serve_throughput.py.
- **Sync-free step loop.** The mx.pipeline deferred-window pattern:
  each step's sampled (token, done) vectors stay on device and are
  pushed into a bounded :class:`_EmitWindow`; the host fetches them at
  most ``serve.drain_window`` steps later (or when it needs a slot).
  Dispatching a step never blocks on device results, so the device
  pipeline stays full. The price: completions are observed up to
  ``drain_window`` steps late — bounded staleness, never lost tokens.
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as onp

from .. import config as _config
from .. import fault as _fault
from .. import functional as _functional
from .. import goodput as _goodput
from .. import insight as _insight
from .. import pipeline as _pipeline
from .. import profiler as _profiler
from .. import servefleet as _servefleet
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..base import MXNetError
from . import quantize as _quantize
from .prefix import RadixIndex

__all__ = ["Request", "ServeEngine", "EngineBusy", "load"]

_telemetry.declare_metric(
    "serve.requests_total", "counter",
    "requests submitted to serve engines")
_telemetry.declare_metric(
    "serve.admitted_total", "counter",
    "requests admitted into a decode slot (prefill dispatched)")
_telemetry.declare_metric(
    "serve.completed_total", "counter",
    "requests finished (EOS or token budget)")
_telemetry.declare_metric(
    "serve.tokens_total", "counter",
    "generated tokens delivered to requests")
_telemetry.declare_metric(
    "serve.prefill_tokens_total", "counter",
    "prompt tokens processed by prefill (bucket-padded length)")
_telemetry.declare_metric(
    "serve.steps_total", "counter",
    "continuous-batching decode steps dispatched")
_telemetry.declare_metric(
    "serve.decode_kernel_calls_total", "counter",
    "layers of a traced decode step whose cached attention read took the "
    "Pallas kernel mx_decode_attn (a TPU, shapes "
    "ops/pallas/decode_attention.py::fits takes) and not the XLA "
    "composition, once a traced call; 0 on a CPU")
_telemetry.declare_metric(
    "serve.step_seconds", "histogram",
    "host wall time to dispatch one decode step (sync-free: excludes "
    "device completion)", buckets=_telemetry.TIME_BUCKETS)
_telemetry.declare_metric(
    "serve.ttft_seconds", "histogram",
    "time to first token: submit -> first token drained to the host",
    buckets=_telemetry.TIME_BUCKETS)
_telemetry.declare_metric(
    "serve.tpot_seconds", "histogram",
    "time per output token after the first (decode cadence per request)",
    buckets=_telemetry.TIME_BUCKETS)
_telemetry.declare_metric(
    "serve.queue_depth", "gauge",
    "requests waiting for a free slot")
_telemetry.declare_metric(
    "serve.rejected_total", "counter",
    "requests rejected by submit() (engine stopping, or the bounded "
    "serve.max_queue backpressure) or discarded queued by "
    "stop(drain=False)")
_telemetry.declare_metric(
    "serve.slot_occupancy", "gauge",
    "slots holding a live request")
_telemetry.declare_metric(
    "serve.post_warmup_compiles_total", "counter",
    "XLA compiles after warmup() — should stay 0; any hit means a "
    "request shape escaped the bucket grid")
_telemetry.declare_metric(
    "serve.quantized_params", "gauge",
    "parameters stored low-bit by the engine's weight quantization "
    "(serve.quantize_min_elems / serve.quantize_ndim govern eligibility)")
_telemetry.declare_metric(
    "serve.passthrough_params", "gauge",
    "parameters kept in float by the engine's weight quantization "
    "(ineligible rank/size, or quantization off)")
_telemetry.declare_metric(
    "serve.slo_violations_total", "counter",
    "requests finishing past a declared serving SLO objective, by kind "
    "(ttft: serve.slo_ttft_ms at first token; tpot: serve.slo_tpot_ms "
    "per output token at finish)")
_telemetry.declare_metric(
    "serve.slo_burn_rate", "gauge",
    "per-engine error-budget burn rate against serve.slo_target over "
    "the trailing window, by kind — 1.0 spends the budget exactly; "
    "past goodput.burn_threshold the engine's /healthz goes red (the "
    "autoscaler admission signal)")

_telemetry.declare_metric(
    "serve.prefix_hits_total", "counter",
    "admissions that reused a cached KV prefix (radix prefix cache): "
    "matched blocks were row-copied and only the suffix prefilled")
_telemetry.declare_metric(
    "serve.prefix_misses_total", "counter",
    "admissions that prefilled the whole prompt (no cached prefix, a "
    "suffix that would overrun max_seq, or a serve.prefix_evict "
    "injection between match and copy)")
_telemetry.declare_metric(
    "serve.prefix_tokens_reused_total", "counter",
    "prompt tokens whose KV was row-copied from the prefix cache "
    "instead of recomputed by prefill")
_telemetry.declare_metric(
    "serve.prefix_evictions_total", "counter",
    "KV blocks dropped from the radix index (slot reuse, LRU capacity "
    "pressure, or the serve.prefix_evict chaos injection)")
_telemetry.declare_metric(
    "serve.prefix_blocks", "gauge",
    "KV blocks currently indexed by the engine's radix prefix cache")
_telemetry.declare_metric(
    "serve.spec_rounds_total", "counter",
    "speculative-decoding rounds dispatched (one draft propose + one "
    "batched big-model verify per round)")
_telemetry.declare_metric(
    "serve.spec_proposed_total", "counter",
    "draft tokens proposed by speculative decoding (k per live slot "
    "per round)")
_telemetry.declare_metric(
    "serve.spec_accepted_total", "counter",
    "draft proposals the big-model verify accepted (the emitted "
    "correction token is not counted)")
_telemetry.declare_metric(
    "serve.spec_acceptance_rate", "gauge",
    "trailing draft-acceptance ratio (accepted / proposed) — the "
    "knob that decides whether speculation pays for its draft")
_telemetry.declare_metric(
    "serve.class_ttft_seconds", "histogram",
    "per-SLO-class time to first token (labelled slo_class; the "
    "unlabelled serve.ttft_seconds carries the aggregate)",
    buckets=_telemetry.TIME_BUCKETS)
_telemetry.declare_metric(
    "serve.class_tpot_seconds", "histogram",
    "per-SLO-class time per output token (labelled slo_class)",
    buckets=_telemetry.TIME_BUCKETS)
_telemetry.declare_metric(
    "serve.class_queue_depth", "gauge",
    "queued requests per SLO class (labelled slo_class)")
_telemetry.declare_metric(
    "serve.aged_admissions_total", "counter",
    "admissions where starvation aging (serve.class_aging_ms) "
    "promoted a request ahead of strict class priority")

#: weight-storage modes ServeEngine(quantize=...) understands; combine
#: with "," (e.g. "int4_weights,int8_kv")
QUANTIZE_MODES = ("int8_weights", "int4_weights", "int8_kv")


def _parse_quantize(quantize):
    """-> (normalized spec or None, weight mode or None, kv_int8 flag)."""
    if not quantize:
        return None, None, False
    modes = [m.strip() for m in str(quantize).split(",") if m.strip()]
    unknown = [m for m in modes if m not in QUANTIZE_MODES]
    if unknown or not modes:
        raise MXNetError(
            f"unknown quantize mode {quantize!r}; modes: "
            f"{', '.join(QUANTIZE_MODES)} (comma-combinable)")
    weight = [m for m in modes if m.endswith("_weights")]
    if len(weight) > 1:
        raise MXNetError(f"conflicting weight modes in {quantize!r}")
    return ",".join(dict.fromkeys(modes)), \
        (weight[0] if weight else None), "int8_kv" in modes


class EngineBusy(MXNetError):
    """:meth:`ServeEngine.submit` rejected the request — the engine is
    stopping, or the bounded queue (``serve.max_queue``) is full.
    Structured so callers can backpressure instead of string-matching:
    ``reason`` ("stopping" / "queue_full"), ``queued`` (depth at
    rejection), ``max_queue`` (the bound; 0 = unbounded), and
    ``retry_after_hint`` — the machine-readable backoff in seconds
    (queue depth x the engine's observed TPOT p50), so a router retries
    when a slot is plausibly free instead of hammering a saturated
    replica."""

    def __init__(self, reason, queued, max_queue, retry_after_hint=0.0):
        self.reason = reason
        self.queued = queued
        self.max_queue = max_queue
        self.retry_after_hint = float(retry_after_hint)
        bound = f", bound {max_queue} (serve.max_queue)" if max_queue else ""
        hint = (f", retry after ~{self.retry_after_hint:.3f}s"
                if self.retry_after_hint else "")
        super().__init__(
            f"serve engine busy ({reason}): {queued} queued{bound}{hint}")


class Request:
    """One generation request and its latency record.

    ``generated`` holds every sampled token id (EOS included when hit);
    ``output_ids`` strips a trailing EOS. TTFT/TPOT are measured at
    *drain* time — when the token was actually available to the caller,
    not when the device produced it — so the deferred window's bounded
    staleness is charged to the engine, keeping the SLO numbers honest.
    """

    __slots__ = ("id", "prompt", "max_new_tokens", "eos_id", "generated",
                 "slot", "finished", "rejected", "reject_reason",
                 "t_submit", "t_admitted", "t_first",
                 "t_done", "phases", "_span", "_enq",
                 "slo_class", "prefix_tokens", "_nodes")

    def __init__(self, rid, prompt, max_new_tokens, eos_id=None,
                 slo_class="default"):
        self.id = rid
        self.prompt = list(prompt)
        self.max_new_tokens = max(1, int(max_new_tokens))
        self.eos_id = eos_id
        #: admission-priority class (serve.slo_classes; "default" when
        #: the engine runs classless)
        self.slo_class = slo_class
        #: prompt tokens served from the radix prefix cache (KV rows
        #: copied instead of recomputed); 0 = full prefill
        self.prefix_tokens = 0
        self._nodes = ()   # pinned radix path, released at _finish
        self.generated = []
        self.slot = None
        self.finished = False
        #: structured rejection marker: a queued request discarded by
        #: stop(drain=False) flips this True (with reject_reason set)
        #: so a waiting caller observes the outcome instead of hanging
        self.rejected = False
        self.reject_reason = None
        self.t_submit = time.perf_counter()
        self.t_admitted = None
        self.t_first = None
        self.t_done = None
        #: per-phase wall-time samples (seconds) — the source of
        #: stats()["phases"]: unbounded while mx.trace records this
        #: request, else capped by serve.phase_sampling
        self.phases = {}
        self._span = None   # serve.request root (trace.SpanHandle)
        self._enq = None    # serve.enqueue child, open until admission

    @property
    def output_ids(self):
        out = list(self.generated)
        if out and self.eos_id is not None and out[-1] == self.eos_id:
            out.pop()
        return out

    @property
    def ttft(self):
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot(self):
        if self.t_done is None or self.t_first is None:
            return None
        return (self.t_done - self.t_first) / max(1, len(self.generated) - 1)

    def __repr__(self):
        state = "done" if self.finished else (
            "slot%d" % self.slot if self.slot is not None else "queued")
        return (f"Request(id={self.id}, prompt={len(self.prompt)} tok, "
                f"out={len(self.generated)} tok, {state})")


class _EmitWindow(_pipeline.DeferredWindow):
    """DeferredWindow whose entries are device *vectors* (per-slot token
    ids + done flags), not scalars: the drain fetches with device_get and
    hands host numpy arrays to the sink. Overflow keeps the base-class
    behavior — oldest entry drained in place, counted as a host sync and
    a ``pipeline.deferred_evictions_total`` tick."""

    def _drain_one(self):
        value, sink = self._pending.pop(0)
        sink(jax.device_get(value))

    def drain_oldest(self, n=1):
        for _ in range(min(n, len(self._pending))):
            if _pipeline._guard_depth:
                _pipeline.note_host_sync("serve.drain")
            self._drain_one()


def _parse_buckets(spec):
    try:
        vals = sorted({int(v) for v in str(spec).split(",") if v.strip()})
    except ValueError as e:
        raise MXNetError(f"bad serve.buckets spec {spec!r}") from e
    if not vals or any(v <= 0 for v in vals):
        raise MXNetError(f"bad serve.buckets spec {spec!r}")
    return vals


@functools.partial(jax.jit, static_argnames="size")
def _cache_rows(cache, slot, size):
    def one(leaf):
        return jax.lax.dynamic_index_in_dim(leaf, slot, 0,
                                            keepdims=False)[:size]
    return (jnp.stack([one(k) for k, _ in cache]),
            jnp.stack([one(v) for _, v in cache]))


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


class ServeEngine:
    """Online inference over a block exposing the KV-cache surface
    (``init_cache`` / ``prefill`` / ``decode_step`` — gluon's GPT family
    and any HybridBlock following the same contract).

    Usage::

        eng = mx.serve.load(model, max_slots=8, eos_id=50256)
        eng.warmup()                      # compile the whole grid
        reqs = [eng.submit(ids, max_new_tokens=64) for ids in prompts]
        eng.run()                         # continuous batching
        reqs[0].output_ids, reqs[0].ttft, eng.stats()

    ``temperature=0`` is greedy; >0 samples from softmax(logits/T).
    ``quantize`` picks low-bit storage (serve/quantize.py, comma-
    combinable): ``"int8_weights"`` = per-channel int8 weights,
    ``"int4_weights"`` = group-wise int4 packed two nibbles per byte,
    ``"int8_kv"`` = int8 KV cache with per-(slot, row, head) scales.
    Dequant always fuses into the consuming matmuls, so HBM reads stay
    low-bit.
    """

    def __init__(self, model, max_slots=None, max_seq=None, buckets=None,
                 eos_id=None, temperature=0.0, seed=0, quantize=None,
                 drain_window=None, cache_dtype="float32", draft=None,
                 prefix_cache=None):
        for attr in ("init_cache", "prefill", "decode_step"):
            if not callable(getattr(model, attr, None)):
                raise MXNetError(
                    f"model {type(model).__name__} has no {attr}(); the "
                    "serve engine needs the KV-cache block surface "
                    "(gluon.model_zoo.gpt, docs/SERVING.md)")
        self.model = model
        self.max_slots = int(max_slots if max_slots is not None
                             else _config.get("serve.max_slots"))
        if self.max_slots <= 0:
            raise MXNetError("max_slots must be positive")
        if max_seq is None:
            max_seq = getattr(model, "max_length", None)
            if max_seq is None:
                raise MXNetError("max_seq not given and model has no "
                                 "max_length")
        self.max_seq = int(max_seq)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self._ensure_initialized()
        params = _functional.param_arrays(model)
        self.quantize, weight_mode, kv_int8 = _parse_quantize(quantize)
        self._weight_mode = weight_mode
        if (weight_mode == "int4_weights"
                and getattr(model, "_fp8_trained", False)
                and not _config.get("serve.allow_fp8_requant")):
            # fp8-trained weights already carry ~2 mantissa bits of
            # quantization noise at every matmul site; stacking group-wise
            # int4 on top compounds it past the accuracy contract int4
            # was validated under.  int8_weights / int8_kv compose fine
            # (int8's grid is strictly finer than e4m3's).
            raise MXNetError(
                "quantize='int4_weights' on an fp8-trained checkpoint "
                "(model._fp8_trained is set): compounding int4 weight "
                "quantization on fp8 training noise is refused by "
                "default. Serve with 'int8_weights'/'int8_kv' (which "
                "compose with fp8 training), or set "
                "mx.config.set('serve.allow_fp8_requant', True) to "
                "override after validating accuracy.")
        if kv_int8:
            cache_dtype = "int8"
        pt, qt, qdt = self._quantize_weights(params)
        self._params = (pt, qt)
        self._qdtypes = qdt
        if _telemetry._active and weight_mode:
            _telemetry.set_gauge("serve.quantized_params", len(qt))
            _telemetry.set_gauge("serve.passthrough_params", len(pt))
        buckets = _parse_buckets(buckets if buckets is not None
                                 else _config.get("serve.buckets"))
        self.buckets = [b for b in buckets if b <= self.max_seq] \
            or [self.max_seq]
        self.cache_dtype = cache_dtype
        cache = model.init_cache(self.max_slots, self.max_seq,
                                 dtype=cache_dtype)
        self._cache = jax.tree_util.tree_map(
            _functional._raw, cache,
            is_leaf=lambda x: hasattr(x, "_data"))
        n = self.max_slots
        self._state = {
            "tokens": jnp.zeros((n,), jnp.int32),
            "positions": jnp.zeros((n,), jnp.int32),
            "done": jnp.ones((n,), bool),
            "limits": jnp.zeros((n,), jnp.int32),
            "key": jax.random.PRNGKey(seed),
        }
        self._queue = collections.deque()
        self._slots = [None] * n
        self._free = list(range(n - 1, -1, -1))  # pop() -> lowest first
        self._window = _EmitWindow(
            drain_window if drain_window is not None
            else _config.get("serve.drain_window"))
        self._exe = {}
        import types
        self._aux_exe_owner = types.SimpleNamespace()
        self._warmed = False
        self.compiles = 0
        self.post_warmup_compiles = 0
        self._next_id = 0
        self._steps = 0
        # cache rows a decode step's read covers: whole blocks up to each
        # live slot's position where the kernel reads, else every row
        from ..ops.attention import decode_read_block
        leaves = jax.tree_util.tree_leaves(self._cache)
        self._read_block = decode_read_block(leaves[0]) \
            if leaves and draft is None else None
        self._rows_read = 0
        self._completed = []
        self._stopping = False
        self._max_queue = int(_config.get("serve.max_queue"))
        self._last_step_time = None
        self._created = time.monotonic()
        # serving SLO objectives (0 = disarmed) + the always-on bounded
        # phase reservoir (stats()["phases"] without the tracer)
        self._slo_ttft = float(_config.get("serve.slo_ttft_ms")) / 1e3
        self._slo_tpot = float(_config.get("serve.slo_tpot_ms")) / 1e3
        self._slo_events = collections.deque(maxlen=2048)
        self._phase_cap = int(_config.get("serve.phase_sampling"))
        # -- SLO classes: strict-priority admission over one queue ------
        spec = str(_config.get("serve.slo_classes") or "")
        self._classes = [c.strip() for c in spec.split(",") if c.strip()] \
            or ["default"]
        if len(set(self._classes)) != len(self._classes):
            raise MXNetError(
                f"duplicate class in serve.slo_classes {spec!r}")
        self._class_rank = {c: i for i, c in enumerate(self._classes)}
        self._class_bounds = {}
        bspec = str(_config.get("serve.class_max_queue") or "")
        for part in (p.strip() for p in bspec.split(",") if p.strip()):
            cls, _, bound = part.partition("=")
            cls = cls.strip()
            if cls not in self._class_rank or not bound.strip().isdigit():
                raise MXNetError(
                    f"bad serve.class_max_queue entry {part!r} (classes: "
                    f"{', '.join(self._classes)})")
            self._class_bounds[cls] = int(bound)
        self._aging = float(_config.get("serve.class_aging_ms")) / 1e3
        self._aged_admissions = 0
        # -- radix prefix cache -----------------------------------------
        if prefix_cache is None:
            prefix_cache = bool(_config.get("serve.prefix_cache"))
        self._prefix = None
        self._prefix_block = int(_config.get("serve.prefix_block"))
        if prefix_cache:
            if self._prefix_block <= 0:
                raise MXNetError("serve.prefix_block must be positive")
            for attr in ("prefill_suffix", "copy_cache_rows"):
                if not callable(getattr(model, attr, None)):
                    raise MXNetError(
                        f"model {type(model).__name__} has no {attr}(); "
                        "the prefix cache needs the suffix-prefill block "
                        "surface (docs/SERVING.md 'Prefix caching')")
            self._prefix = RadixIndex(
                self._prefix_block,
                int(_config.get("serve.prefix_capacity")))
        # -- speculative decoding (draft model) -------------------------
        self.draft = draft
        self._spec_k = 0
        self._draft_params = None
        self._draft_cache = None
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        if draft is not None:
            if self.temperature != 0.0:
                raise MXNetError(
                    "speculative decoding needs temperature=0: the "
                    "verify keeps greedy output token-for-token "
                    "identical, which has no sampled analogue here")
            if not callable(getattr(model, "decode_multi", None)):
                raise MXNetError(
                    f"model {type(model).__name__} has no decode_multi();"
                    " the speculative verify needs the multi-token "
                    "decode surface (docs/SERVING.md)")
            for attr in ("init_cache", "prefill", "decode_step"):
                if not callable(getattr(draft, attr, None)):
                    raise MXNetError(
                        f"draft {type(draft).__name__} has no {attr}(); "
                        "the draft must expose the same KV-cache "
                        "surface as the served model")
            if self._prefix is not None:
                for attr in ("prefill_suffix", "copy_cache_rows"):
                    if not callable(getattr(draft, attr, None)):
                        raise MXNetError(
                            f"draft {type(draft).__name__} has no "
                            f"{attr}(); combining the prefix cache with "
                            "speculative decoding needs it on the draft "
                            "too (its KV rows are copied alongside)")
            self._spec_k = max(2, int(_config.get("serve.spec_tokens")))
            self._ensure_initialized(draft)
            # draft weights stay float: the draft is small by design and
            # the verify keeps output quality pinned to the big model
            self._draft_params = _functional.param_arrays(draft)
            dcache = draft.init_cache(self.max_slots, self.max_seq,
                                      dtype=cache_dtype)
            self._draft_cache = jax.tree_util.tree_map(
                _functional._raw, dcache,
                is_leaf=lambda x: hasattr(x, "_data"))
        self._register_health()

    def _register_health(self):
        """Register this engine's /healthz provider. The ops endpoint's
        /healthz reflects THIS engine's step-loop liveness (a process
        hosts one serving engine; the newest wins).  Bound weakly: a
        collected engine must not pin a stale check.  Re-invoked by
        :meth:`resume` after a rolling weight update's drain/stop cycle
        unregistered the provider."""
        import weakref
        ref = weakref.ref(self)

        def _check():
            eng = ref()
            if eng is None:
                _telemetry.unregister_health("serve")
                return True
            return eng._health()

        self._health_name = _telemetry.register_health("serve", _check)

    # -- model/param plumbing -------------------------------------------

    def _quantize_weights(self, params):
        """Run the engine's configured weight-storage mode over a flat
        ``{name: array}`` tree -> ``(passthrough, quantized, qdtypes)``.
        Shared by __init__ and :meth:`update_weights` so a weight swap
        reproduces the storage layout the AOT executables were compiled
        against."""
        if self._weight_mode == "int8_weights":
            return _quantize.quantize_params_int8(params)
        if self._weight_mode == "int4_weights":
            return _quantize.quantize_params_int4(params)
        return params, {}, {}

    def _ensure_initialized(self, model=None):
        """Materialize deferred params with one tiny eager forward —
        shape inference must not happen inside an AOT trace."""
        model = self.model if model is None else model
        needs = any(p._data is None
                    for p in model.collect_params().values())
        if needs:
            from .. import numpy as np
            model(np.zeros((1, min(2, self.max_seq)), dtype="int32"))

    def _full_params(self):
        pt, qt = self._params
        if not qt:
            return pt
        return _quantize.dequantize_params(pt, qt, self._qdtypes)

    def _sample(self, logits, key):
        if self.temperature > 0:
            return jax.random.categorical(
                key, logits / self.temperature, axis=-1).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # -- compiled step functions ----------------------------------------

    def _compile(self, kind, build_args):
        """AOT lower+compile one step executable, accounted through the
        PR 2 recompile detector (telemetry.note_compile) so a post-warmup
        compile trips RecompileWarning exactly like a re-tracing block.
        The base grid (decode + prefill buckets) counts against the
        engine; the prefix/spec surface (copy + suffix buckets + spec)
        is a second planned grid and counts against its own owner, so a
        fully-featured warmup does not trip the per-block signature
        heuristic while real post-warmup escapes still do."""
        t0 = time.perf_counter()
        jitted, args = build_args()
        exe = jitted.lower(*args).compile()
        dt = time.perf_counter() - t0
        self.compiles += 1
        if self._warmed:
            self.post_warmup_compiles += 1
            if _telemetry._active:
                _telemetry.inc("serve.post_warmup_compiles_total")
        owner = self if (kind == "decode" or kind.startswith("prefill")) \
            else self._aux_exe_owner
        _telemetry.note_compile(owner, f"serve.{kind}", dt,
                                signatures=len(self._exe) + 1)
        if _insight._active:
            # attribution capture from the AOT executable we already
            # paid for (args are the abstract ShapeDtypeStructs)
            _insight.register_executable(f"serve.{kind}", compiled=exe,
                                         args=args, kind="serve")
        return exe

    def _decode_fn(self, params, cache, state):
        pt, qt = params
        full = (_quantize.dequantize_params(pt, qt, self._qdtypes)
                if qt else pt)
        key, kf, ks = jax.random.split(state["key"], 3)
        (logits, cache), _ = _functional.functional_call(
            self.model, full, state["tokens"][:, None], cache,
            state["positions"], ~state["done"], rng_key=kf,
            method="decode_step")
        tok = self._sample(logits, ks)
        done0 = state["done"]
        positions = jnp.where(done0, state["positions"],
                              state["positions"] + 1)
        hit_eos = (tok == self.eos_id) if self.eos_id is not None \
            else jnp.zeros_like(done0)
        done = done0 | hit_eos | (positions >= state["limits"])
        new_state = {
            "tokens": jnp.where(done0, state["tokens"], tok),
            "positions": positions,
            "done": done,
            "limits": state["limits"],
            "key": key,
        }
        emit = (jnp.where(done0, -1, tok), done)
        return cache, new_state, emit

    def _prefill_fn(self, params, cache, state, prompt, slot, length,
                    limit):
        pt, qt = params
        full = (_quantize.dequantize_params(pt, qt, self._qdtypes)
                if qt else pt)
        key, kf, ks = jax.random.split(state["key"], 3)
        (logits, cache), _ = _functional.functional_call(
            self.model, full, prompt[None, :], cache, slot,
            rng_key=kf, method="prefill")
        tok = self._sample(logits[0, length - 1][None, :], ks)[0]
        hit_eos = (tok == self.eos_id) if self.eos_id is not None \
            else jnp.array(False)
        done = hit_eos | (length >= limit)
        new_state = {
            "tokens": state["tokens"].at[slot].set(tok),
            "positions": state["positions"].at[slot].set(length),
            "done": state["done"].at[slot].set(done),
            "limits": state["limits"].at[slot].set(limit),
            "key": key,
        }
        return cache, new_state, (tok, done)

    # cache trees ride the copy / suffix / spec executables as ONE
    # donated pytree so a spec engine's draft cache moves with the big
    # model's — one dispatch, one donation story
    def _cache_tree(self):
        if self.draft is not None:
            return (self._cache, self._draft_cache)
        return self._cache

    def _set_cache_tree(self, tree):
        if self.draft is not None:
            self._cache, self._draft_cache = tree
        else:
            self._cache = tree

    def _copy_blocks(self, caches, src_slots, src_rows, dst_slot):
        """Traced matched-path copy: row r of ``dst_slot`` becomes row
        src_rows[r] of slot src_slots[r] (shape (max_seq,), so the
        executable never depends on the match length).  Rows past the
        matched prefix are encoded by the caller as identity
        coordinates.  ONE gather per leaf, inlined into the
        suffix-prefill executables — a prefix-hit admission is ONE
        dispatch, same as a miss, or the copy overhead eats the reuse
        win."""
        from ..ops import attention as _att
        return _att.gather_cache_rows(caches, src_slots, src_rows,
                                      dst_slot)

    def _suffix_fn(self, params, cache, state, suffix, src_slots,
                   src_rows, slot, start, length, limit):
        """Prefix-cache admission, fused: copy the matched KV block
        path into rows [0, start) of ``slot``, then run only the
        ``length``-token suffix (padded to its bucket) and sample from
        its last real row."""
        pt, qt = params
        full = (_quantize.dequantize_params(pt, qt, self._qdtypes)
                if qt else pt)
        cache = self._copy_blocks(cache, src_slots, src_rows, slot)
        key, kf, ks = jax.random.split(state["key"], 3)
        (logits, cache), _ = _functional.functional_call(
            self.model, full, suffix[None, :], cache, slot, start,
            rng_key=kf, method="prefill_suffix")
        tok = self._sample(logits[0, length - 1][None, :], ks)[0]
        end = start + length
        hit_eos = (tok == self.eos_id) if self.eos_id is not None \
            else jnp.array(False)
        done = hit_eos | (end >= limit)
        new_state = {
            "tokens": state["tokens"].at[slot].set(tok),
            "positions": state["positions"].at[slot].set(end),
            "done": state["done"].at[slot].set(done),
            "limits": state["limits"].at[slot].set(limit),
            "key": key,
        }
        return cache, new_state, (tok, done)

    def _prefill_spec_fn(self, params, dparams, caches, state, prompt,
                         slot, length, limit):
        """Spec-mode prefill: the prompt also runs through the draft so
        its cache holds the same context the big model's does."""
        cache, dcache = caches
        pt, qt = params
        full = (_quantize.dequantize_params(pt, qt, self._qdtypes)
                if qt else pt)
        key, kf, ks = jax.random.split(state["key"], 3)
        (logits, cache), _ = _functional.functional_call(
            self.model, full, prompt[None, :], cache, slot,
            rng_key=kf, method="prefill")
        (_, dcache), _ = _functional.functional_call(
            self.draft, dparams, prompt[None, :], dcache, slot,
            rng_key=kf, method="prefill")
        tok = self._sample(logits[0, length - 1][None, :], ks)[0]
        hit_eos = (tok == self.eos_id) if self.eos_id is not None \
            else jnp.array(False)
        done = hit_eos | (length >= limit)
        new_state = {
            "tokens": state["tokens"].at[slot].set(tok),
            "positions": state["positions"].at[slot].set(length),
            "done": state["done"].at[slot].set(done),
            "limits": state["limits"].at[slot].set(limit),
            "key": key,
        }
        return (cache, dcache), new_state, (tok, done)

    def _suffix_spec_fn(self, params, dparams, caches, state, suffix,
                        src_slots, src_rows, slot, start, length,
                        limit):
        caches = self._copy_blocks(caches, src_slots, src_rows, slot)
        cache, dcache = caches
        pt, qt = params
        full = (_quantize.dequantize_params(pt, qt, self._qdtypes)
                if qt else pt)
        key, kf, ks = jax.random.split(state["key"], 3)
        (logits, cache), _ = _functional.functional_call(
            self.model, full, suffix[None, :], cache, slot, start,
            rng_key=kf, method="prefill_suffix")
        (_, dcache), _ = _functional.functional_call(
            self.draft, dparams, suffix[None, :], dcache, slot, start,
            rng_key=kf, method="prefill_suffix")
        tok = self._sample(logits[0, length - 1][None, :], ks)[0]
        end = start + length
        hit_eos = (tok == self.eos_id) if self.eos_id is not None \
            else jnp.array(False)
        done = hit_eos | (end >= limit)
        new_state = {
            "tokens": state["tokens"].at[slot].set(tok),
            "positions": state["positions"].at[slot].set(end),
            "done": state["done"].at[slot].set(done),
            "limits": state["limits"].at[slot].set(limit),
            "key": key,
        }
        return (cache, dcache), new_state, (tok, done)

    def _spec_fn(self, params, dparams, caches, state):
        """One speculative round, ONE dispatch: the draft proposes k
        tokens greedily against its own cache, then the big model
        verifies all k in a single batched ``decode_multi`` call.

        Acceptance is the standard greedy rule — proposal i stands iff
        every earlier proposal matched the big model's argmax — and the
        first disagreement is replaced by the big model's own token, so
        the emitted stream is token-for-token the non-speculative greedy
        output.  A slot emits between 1 and k tokens per round (0 when
        already done); rows written past the accepted point are garbage
        the next round overwrites before anything attends to them."""
        cache, dcache = caches
        pt, qt = params
        full = (_quantize.dequantize_params(pt, qt, self._qdtypes)
                if qt else pt)
        n, k = self.max_slots, self._spec_k
        key, kf = jax.random.split(state["key"], 2)
        pos0 = state["positions"]
        cur = state["tokens"]
        drafts = []
        for i in range(k):
            (dlogits, dcache), _ = _functional.functional_call(
                self.draft, dparams, cur[:, None], dcache, pos0 + i,
                rng_key=kf, method="decode_step")
            cur = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            drafts.append(cur)
        d = jnp.stack(drafts, axis=1)                      # (n, k)
        seq = jnp.concatenate([state["tokens"][:, None], d[:, :k - 1]],
                              axis=1)
        (logits, cache), _ = _functional.functional_call(
            self.model, full, seq, cache, pos0,
            rng_key=kf, method="decode_multi")
        b = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (n, k)
        ones = jnp.ones((n, 1), bool)
        ok = jnp.concatenate(
            [ones, jnp.cumprod((d[:, :k - 1] == b[:, :k - 1])
                               .astype(jnp.int32), axis=1).astype(bool)],
            axis=1)
        pos_i = pos0[:, None] + 1 + jnp.arange(k)[None, :]
        hit_eos = (b == self.eos_id) if self.eos_id is not None \
            else jnp.zeros(b.shape, bool)
        stop = hit_eos | (pos_i >= state["limits"][:, None])
        before_stop = jnp.concatenate(
            [ones, jnp.cumprod((~stop[:, :k - 1]).astype(jnp.int32),
                               axis=1).astype(bool)], axis=1)
        live = ~state["done"]
        valid = ok & before_stop & live[:, None]
        toks = jnp.where(valid, b, -1)
        nvalid = valid.sum(axis=1)          # >= 1 for every live slot
        last = jnp.maximum(nvalid - 1, 0)[:, None]
        last_tok = jnp.take_along_axis(b, last, axis=1)[:, 0]
        last_stop = jnp.take_along_axis(stop, last, axis=1)[:, 0]
        new_done = state["done"] | (live & last_stop)
        new_state = {
            "tokens": jnp.where(live, last_tok, state["tokens"]),
            "positions": jnp.where(live, pos0 + nvalid, pos0),
            "done": new_done,
            "limits": state["limits"],
            "key": key,
        }
        return (cache, dcache), new_state, (toks, new_done)

    def _decode_exe(self):
        exe = self._exe.get("decode")
        if exe is None:
            def build():
                jitted = jax.jit(self._decode_fn, donate_argnums=(1, 2))
                return jitted, (_sds(self._params), _sds(self._cache),
                                _sds(self._state))
            exe = self._exe["decode"] = self._compile("decode", build)
        return exe

    def _prefill_exe(self, bucket):
        key = ("prefill", bucket)
        exe = self._exe.get(key)
        if exe is None:
            def build():
                scalar = jax.ShapeDtypeStruct((), jnp.int32)
                prompt = jax.ShapeDtypeStruct((bucket,), jnp.int32)
                if self.draft is not None:
                    jitted = jax.jit(self._prefill_spec_fn,
                                     donate_argnums=(2, 3))
                    return jitted, (_sds(self._params),
                                    _sds(self._draft_params),
                                    _sds(self._cache_tree()),
                                    _sds(self._state), prompt,
                                    scalar, scalar, scalar)
                jitted = jax.jit(self._prefill_fn, donate_argnums=(1, 2))
                return jitted, (_sds(self._params), _sds(self._cache),
                                _sds(self._state), prompt,
                                scalar, scalar, scalar)
            exe = self._exe[key] = self._compile(f"prefill_{bucket}", build)
        return exe

    def _suffix_exe(self, bucket):
        key = ("suffix", bucket)
        exe = self._exe.get(key)
        if exe is None:
            def build():
                scalar = jax.ShapeDtypeStruct((), jnp.int32)
                suffix = jax.ShapeDtypeStruct((bucket,), jnp.int32)
                vec = jax.ShapeDtypeStruct((self.max_seq,), jnp.int32)
                if self.draft is not None:
                    jitted = jax.jit(self._suffix_spec_fn,
                                     donate_argnums=(2, 3))
                    return jitted, (_sds(self._params),
                                    _sds(self._draft_params),
                                    _sds(self._cache_tree()),
                                    _sds(self._state), suffix,
                                    vec, vec,
                                    scalar, scalar, scalar, scalar)
                jitted = jax.jit(self._suffix_fn, donate_argnums=(1, 2))
                return jitted, (_sds(self._params), _sds(self._cache),
                                _sds(self._state), suffix,
                                vec, vec,
                                scalar, scalar, scalar, scalar)
            exe = self._exe[key] = self._compile(f"suffix_{bucket}", build)
        return exe

    def _spec_exe(self):
        exe = self._exe.get("spec")
        if exe is None:
            def build():
                jitted = jax.jit(self._spec_fn, donate_argnums=(2, 3))
                return jitted, (_sds(self._params),
                                _sds(self._draft_params),
                                _sds(self._cache_tree()),
                                _sds(self._state))
            exe = self._exe["spec"] = self._compile("spec", build)
        return exe

    def warmup(self):
        """Compile the full executable grid: decode (or the speculative
        propose+verify round when a draft is attached) + one prefill per
        bucket, plus one fused block-copy + suffix-prefill per bucket
        when the prefix cache is on. After this the engine never
        compiles again for any request mix whose prompts fit the
        buckets — the recompile-guard regression test pins that down."""
        if self.draft is not None:
            self._spec_exe()
        else:
            self._decode_exe()
        for b in self.buckets:
            self._prefill_exe(b)
        if self._prefix is not None:
            for b in self.buckets:
                self._suffix_exe(b)
        self._warmed = True
        return self

    # -- scheduling ------------------------------------------------------

    def bucket_for(self, length):
        for b in self.buckets:
            if length <= b:
                return b
        raise MXNetError(
            f"prompt length {length} exceeds the largest bucket "
            f"{self.buckets[-1]} (serve.buckets, max_seq={self.max_seq})")

    def submit(self, prompt, max_new_tokens=32, eos_id="engine",
               slo_class=None):
        """Enqueue one request; returns its :class:`Request` handle.
        Admission happens inside :meth:`step` when a slot frees up.
        ``slo_class`` names one of ``serve.slo_classes`` (priority
        admission); ``None`` takes the lowest-priority (last) class."""
        prompt = [int(t) for t in onp.asarray(prompt).reshape(-1)]
        if not prompt:
            raise MXNetError("empty prompt")
        self.bucket_for(len(prompt))  # validate now, not at admission
        cls = self._classes[-1] if slo_class is None else str(slo_class)
        if cls not in self._class_rank:
            raise MXNetError(
                f"unknown slo_class {cls!r} (serve.slo_classes: "
                f"{', '.join(self._classes)})")
        if self._stopping:
            if _telemetry._active:
                _telemetry.inc("serve.rejected_total", reason="stopping")
            raise EngineBusy("stopping", len(self._queue), self._max_queue,
                             retry_after_hint=self._retry_after_hint())
        if self._max_queue and len(self._queue) >= self._max_queue:
            if _telemetry._active:
                _telemetry.inc("serve.rejected_total", reason="queue_full")
            raise EngineBusy("queue_full", len(self._queue), self._max_queue,
                             retry_after_hint=self._retry_after_hint())
        bound = self._class_bounds.get(cls, 0)
        if bound and sum(1 for r in self._queue
                         if r.slo_class == cls) >= bound:
            if _telemetry._active:
                _telemetry.inc("serve.rejected_total",
                               reason="class_queue_full")
            raise EngineBusy("class_queue_full", len(self._queue), bound,
                             retry_after_hint=self._retry_after_hint())
        req = Request(self._next_id, prompt, max_new_tokens,
                      self.eos_id if eos_id == "engine" else eos_id,
                      slo_class=cls)
        self._next_id += 1
        self._queue.append(req)
        if _trace._active:
            req._span = _trace.begin("serve.request", category="serve",
                                     request=req.id,
                                     prompt_tokens=len(prompt))
            req._enq = _trace.begin("serve.enqueue", category="serve",
                                    parent=req._span.context,
                                    request=req.id)
        if _telemetry._active:
            _telemetry.inc("serve.requests_total")
            _telemetry.set_gauge("serve.queue_depth", len(self._queue))
        return req

    def _finish(self, req):
        req.finished = True
        req.t_done = time.perf_counter()
        if req.slot is not None:
            self._slots[req.slot] = None
            self._free.append(req.slot)
            self._free.sort(reverse=True)
            req.slot = None
        if req._nodes:
            # unpin the request's radix path — its blocks become
            # LRU-evictable again
            self._prefix.release(list(req._nodes))
            req._nodes = ()
        self._completed.append(req)
        if req._enq is not None:  # finished without ever being admitted
            req._enq.end()
            req._enq = None
        if req._span is not None:
            req._span.end(tokens=len(req.generated))
            req._span = None
        if _telemetry._active:
            _telemetry.inc("serve.completed_total")
            _telemetry.inc("serve.tokens_total", len(req.generated))
            if req.tpot is not None:
                _telemetry.observe("serve.tpot_seconds", req.tpot)
                _telemetry.observe("serve.class_tpot_seconds", req.tpot,
                                   slo_class=req.slo_class)
        if self._slo_tpot and req.tpot is not None:
            self._slo_observe("tpot", req.tpot > self._slo_tpot,
                              req.slo_class)

    def _prefill_sink(self, req):
        def sink(fetched):
            t0u = _profiler.now_us() if _trace._active else 0
            span_ctx = req._span.context if req._span is not None else None
            tok, done = int(fetched[0]), bool(fetched[1])
            req.t_first = time.perf_counter()
            req.generated.append(tok)
            if _telemetry._active and req.ttft is not None:
                _telemetry.observe("serve.ttft_seconds", req.ttft)
                _telemetry.observe("serve.class_ttft_seconds", req.ttft,
                                   slo_class=req.slo_class)
            if self._slo_ttft and req.ttft is not None:
                self._slo_observe("ttft", req.ttft > self._slo_ttft,
                                  req.slo_class)
            if done:
                self._finish(req)
            if _trace._active and span_ctx is not None:
                _trace.emit("serve.drain", t0u, _profiler.now_us() - t0u,
                            parent=span_ctx, category="serve",
                            request=req.id, first_token=True)
        return sink

    def _decode_sink(self, slot_map):
        def sink(fetched):
            t0u = _profiler.now_us() if _trace._active else 0
            toks, done = fetched
            for slot, req in slot_map.items():
                if req.finished:
                    continue  # finished in an older entry of this window
                span_ctx = (req._span.context
                            if req._span is not None else None)
                tok = int(toks[slot])
                if tok >= 0:
                    req.generated.append(tok)
                if bool(done[slot]):
                    self._finish(req)
                if _trace._active and span_ctx is not None and tok >= 0:
                    _trace.emit("serve.drain", t0u,
                                _profiler.now_us() - t0u,
                                parent=span_ctx, category="serve",
                                request=req.id)
        return sink

    def _next_request(self):
        """Dequeue under strict class priority (``serve.slo_classes``
        order, FIFO within a class), with the starvation-aging escape
        hatch: once a request waits past ``serve.class_aging_ms`` it
        competes on age alone, so a saturated high class cannot starve
        the low classes forever."""
        q = self._queue
        if len(self._classes) == 1 or len(q) == 1:
            return q.popleft()
        best, best_rank = None, len(self._classes)
        for r in q:
            rank = self._class_rank[r.slo_class]
            if rank < best_rank:
                best, best_rank = r, rank
                if rank == 0:
                    break
        req = best
        if self._aging:
            now = time.perf_counter()
            aged = [r for r in q if (now - r.t_submit) >= self._aging]
            if aged:
                oldest = min(aged, key=lambda r: r.t_submit)
                if oldest is not best:
                    req = oldest
                    self._aged_admissions += 1
                    if _telemetry._active:
                        _telemetry.inc("serve.aged_admissions_total")
        q.remove(req)
        return req

    def _pick_slot(self):
        """Free-slot choice.  Without the prefix cache: lowest slot
        (the original behaviour).  With it: the *coldest* free slot —
        the one whose newest indexed block is oldest, never-indexed
        first — so admissions overwrite the least-reusable KV rows."""
        if self._prefix is None or len(self._free) == 1:
            return self._free.pop()
        slot = min(self._free,
                   key=lambda s: (self._prefix.slot_heat(s), s))
        self._free.remove(slot)
        return slot

    def _spec_sink(self, slot_map):
        """Drain sink for a speculative round: each live slot carries up
        to k token ids (-1 padded past the accepted point).  Acceptance
        accounting happens here, host-side — a live slot always emits at
        least one token (the big model's own), so ``emitted - 1`` is the
        number of draft proposals that survived the verify."""
        def sink(fetched):
            t0u = _profiler.now_us() if _trace._active else 0
            toks, done = fetched
            k, proposed, accepted = self._spec_k, 0, 0
            for slot, req in slot_map.items():
                if req.finished:
                    continue  # finished in an older entry of this window
                span_ctx = (req._span.context
                            if req._span is not None else None)
                emitted = [int(t) for t in toks[slot] if int(t) >= 0]
                req.generated.extend(emitted)
                if emitted:
                    # rows with no emit were already done on device —
                    # the draft proposed nothing real for them
                    proposed += k
                    accepted += len(emitted) - 1
                if bool(done[slot]):
                    self._finish(req)
                if _trace._active and span_ctx is not None and emitted:
                    _trace.emit("serve.drain", t0u,
                                _profiler.now_us() - t0u,
                                parent=span_ctx, category="serve",
                                request=req.id, tokens=len(emitted))
            self._spec_proposed += proposed
            self._spec_accepted += accepted
            if _telemetry._active and proposed:
                _telemetry.inc("serve.spec_proposed_total", proposed)
                _telemetry.inc("serve.spec_accepted_total", accepted)
                _telemetry.set_gauge(
                    "serve.spec_acceptance_rate",
                    round(self._spec_accepted
                          / max(1, self._spec_proposed), 4))
        return sink

    def _admit(self):
        admitted = 0
        while self._queue and self._free:
            self._dispatch_prefill(self._next_request(), self._pick_slot())
            admitted += 1
        return admitted

    def _dispatch_prefill(self, req, slot):
        """Admit ``req`` into ``slot``.  With the prefix cache on, the
        longest indexed prompt prefix is row-copied from its donor slot
        (block granular) and only the suffix runs through prefill; the
        whole prompt is then (re)indexed under this slot and pinned
        until the request finishes."""
        length = len(req.prompt)
        limit = min(length + req.max_new_tokens - 1, self.max_seq - 1)
        t0u = _profiler.now_us() if _trace._active else 0
        t0p = time.perf_counter()
        nodes, start, sbucket = (), 0, None
        if self._prefix is not None:
            nodes = tuple(self._prefix.match(req.prompt))
            if nodes and _fault._active \
                    and _fault.fire("serve.prefix_evict"):
                # chaos: the matched prefix vanishes between match and
                # copy — the engine must fall back to a full prefill
                dropped = self._prefix.evict_path(list(nodes))
                if dropped and _telemetry._active:
                    _telemetry.inc("serve.prefix_evictions_total", dropped)
                nodes = ()
            if nodes:
                start = len(nodes) * self._prefix_block
                sbucket = self.bucket_for(length - start)
                if start + sbucket > self.max_seq:
                    # the padded suffix would overrun the cache rows
                    nodes, start, sbucket = (), 0, None
        if nodes:
            # the destination slot's stale rows leave the index first
            evicted = self._prefix.evict_slot(slot)
            if evicted and _telemetry._active:
                _telemetry.inc("serve.prefix_evictions_total", evicted)
            # per-row source coordinates for the matched prefix; rows
            # past it are identity (dest slot, own row) — untouched
            blk = self._prefix_block
            src_slots = onp.full((self.max_seq,), slot, dtype=onp.int32)
            src_rows = onp.arange(self.max_seq, dtype=onp.int32)
            for i, node in enumerate(nodes):
                src_slots[i * blk:(i + 1) * blk] = node.slot
                src_rows[i * blk:(i + 1) * blk] = onp.arange(
                    node.row, node.row + blk, dtype=onp.int32)
            suffix = req.prompt[start:]
            padded = onp.zeros((sbucket,), dtype=onp.int32)
            padded[:len(suffix)] = suffix
            exe = self._suffix_exe(sbucket)
            if self.draft is not None:
                tree, self._state, emit = exe(
                    self._params, self._draft_params, self._cache_tree(),
                    self._state, jnp.asarray(padded),
                    jnp.asarray(src_slots), jnp.asarray(src_rows),
                    jnp.int32(slot), jnp.int32(start),
                    jnp.int32(len(suffix)), jnp.int32(limit))
                self._set_cache_tree(tree)
            else:
                self._cache, self._state, emit = exe(
                    self._params, self._cache, self._state,
                    jnp.asarray(padded),
                    jnp.asarray(src_slots), jnp.asarray(src_rows),
                    jnp.int32(slot), jnp.int32(start),
                    jnp.int32(len(suffix)), jnp.int32(limit))
            req.prefix_tokens = start
            self._prefix.hits += 1
            self._prefix.tokens_reused += start
            bucket = sbucket
            if _telemetry._active:
                _telemetry.inc("serve.prefix_hits_total")
                _telemetry.inc("serve.prefix_tokens_reused_total", start)
        else:
            if self._prefix is not None:
                evicted = self._prefix.evict_slot(slot)
                if evicted and _telemetry._active:
                    _telemetry.inc("serve.prefix_evictions_total",
                                   evicted)
                self._prefix.misses += 1
                if _telemetry._active:
                    _telemetry.inc("serve.prefix_misses_total")
            bucket = self.bucket_for(length)
            padded = onp.zeros((bucket,), dtype=onp.int32)
            padded[:length] = req.prompt
            exe = self._prefill_exe(bucket)
            if self.draft is not None:
                tree, self._state, emit = exe(
                    self._params, self._draft_params, self._cache_tree(),
                    self._state, jnp.asarray(padded), jnp.int32(slot),
                    jnp.int32(length), jnp.int32(limit))
                self._set_cache_tree(tree)
            else:
                self._cache, self._state, emit = exe(
                    self._params, self._cache, self._state,
                    jnp.asarray(padded), jnp.int32(slot),
                    jnp.int32(length), jnp.int32(limit))
        if self._prefix is not None:
            path = self._prefix.insert(req.prompt, slot)
            self._prefix.acquire(path)
            req._nodes = tuple(path)
            if _telemetry._active:
                _telemetry.set_gauge("serve.prefix_blocks",
                                     len(self._prefix))
        req.slot = slot
        req.t_admitted = time.perf_counter()
        if req._enq is not None:
            req._enq.end()
            req._enq = None
        if _trace._active and req._span is not None:
            duru = _profiler.now_us() - t0u
            _trace.emit("serve.prefill", t0u, duru,
                        parent=req._span.context, category="serve",
                        request=req.id, slot=slot, bucket=bucket,
                        prefix_tokens=req.prefix_tokens)
        if _trace._active or self._phase_cap:
            self._phase_note(req, "queue_wait",
                             req.t_admitted - req.t_submit)
            self._phase_note(req, "prefill",
                             req.t_admitted - t0p)
        self._slots[slot] = req
        self._window.push(emit, self._prefill_sink(req))
        if _telemetry._active:
            _telemetry.inc("serve.admitted_total")
            _telemetry.inc("serve.prefill_tokens_total", bucket)

    # -- the serve loop --------------------------------------------------

    def step(self):
        """One continuous-batching iteration: free slots via bounded
        drain when the queue is starved, admit, dispatch ONE decode step
        for every live slot, defer the result. Returns False when fully
        idle (nothing queued, running, or pending drain)."""
        self._last_step_time = time.monotonic()
        if self._queue and not self._free and len(self._window):
            # starved for slots: reclaim just enough, oldest first —
            # one per queued request, so a deep queue refills the whole
            # slot grid in one step instead of trickling one admission
            # per decode dispatch
            self._window.drain_oldest(min(len(self._queue),
                                          len(self._window)))
        admitted = self._admit()
        live = {i: r for i, r in enumerate(self._slots) if r is not None}
        if _telemetry._active:
            _telemetry.set_gauge("serve.queue_depth", len(self._queue))
            _telemetry.set_gauge("serve.slot_occupancy", len(live))
            if len(self._classes) > 1:
                depth = {c: 0 for c in self._classes}
                for r in self._queue:
                    depth[r.slo_class] += 1
                for c, v in depth.items():
                    _telemetry.set_gauge("serve.class_queue_depth", v,
                                         slo_class=c)
        if not live:
            if len(self._window):
                self._window.drain()
                return True
            return admitted > 0
        if self.draft is not None:
            exe = self._spec_exe()
            t0 = time.perf_counter()
            tree, self._state, emit = exe(
                self._params, self._draft_params, self._cache_tree(),
                self._state)
            self._set_cache_tree(tree)
            self._spec_rounds += 1
        else:
            exe = self._decode_exe()
            t0 = time.perf_counter()
            self._cache, self._state, emit = exe(
                self._params, self._cache, self._state)
        dt = time.perf_counter() - t0
        self._steps += 1
        self._rows_read += self._rows_covered(live)
        if _servefleet._active:
            _servefleet.note_step(self)
        if _telemetry._active:
            _telemetry.inc("serve.steps_total")
            _telemetry.observe("serve.step_seconds", dt)
            if self.draft is not None:
                _telemetry.inc("serve.spec_rounds_total")
        if _trace._active:
            # one span per live request per step: the dispatch wall time
            # was measured anyway, so re-stamp it on the shared clock
            duru = int(dt * 1e6)
            t0u = _profiler.now_us() - duru
            for slot, req in live.items():
                if req._span is not None:
                    _trace.emit("serve.decode_step", t0u, duru,
                                parent=req._span.context,
                                category="serve", request=req.id,
                                slot=slot, step=self._steps)
                self._phase_note(req, "decode_step", dt)
        elif self._phase_cap:
            for req in live.values():
                self._phase_note(req, "decode_step", dt)
        sink = self._spec_sink(live) if self.draft is not None \
            else self._decode_sink(live)
        self._window.push(emit, sink)
        return True

    def _rows_covered(self, live):
        """Cache rows one decode step's read covers, from the host's own
        slot table (no device sync; a slot's position as the drained
        tokens give it, at most ``drain_window`` rows behind): whole
        blocks up to each live slot's position where the kernel reads,
        ``max_slots x max_seq`` where the composition does."""
        if self._read_block is None:
            return self.max_slots * self.max_seq
        block = self._read_block
        return sum(
            -(-min(len(r.prompt) + max(1, len(r.generated)), self.max_seq)
              // block) * block for r in live.values())

    def cache_rows(self, slot, size):
        """Rows 0 .. ``size`` - 1 of cache slot ``slot`` in every layer,
        as ``(k, v)``, each ``(layers, size, n_embd)`` in the cache's type:
        what the engine's programs wrote, for a caller that holds them
        against a reference (one jitted read a ``size``).  An int8 cache
        has no such rows."""
        if self.cache_dtype == "int8":
            raise MXNetError("an int8 KV cache holds (values, scales) "
                             "pairs: cache_rows() reads float caches")
        return _cache_rows(self._cache, jnp.int32(slot), int(size))

    def _phase_note(self, req, key, val):
        """Per-request phase sample: unbounded while the tracer runs
        (the PR 9 behaviour), else capped at ``serve.phase_sampling``
        samples per phase so stats()["phases"] stays populated in
        production at a bounded cost."""
        lst = req.phases.setdefault(key, [])
        if _trace._active or len(lst) < self._phase_cap:
            lst.append(val)

    def drain(self):
        """Fetch every deferred emit (host sync); completions land."""
        self._window.drain()

    @property
    def pending(self):
        return bool(self._queue or len(self._window)
                    or any(s is not None for s in self._slots))

    def run(self, max_steps=None):
        """Drive :meth:`step` until every submitted request finished (or
        ``max_steps`` decode steps elapsed), then drain. The continuous-
        batching main loop for offline/batch use; online callers own the
        loop and call ``step()`` themselves."""
        steps = 0
        try:
            while self.pending:
                self.step()
                steps += 1
                if max_steps is not None and steps >= max_steps:
                    break
            self.drain()
        except Exception as e:
            # the serving loop is the long-running production surface:
            # freeze the evidence window with engine state attached
            # before the exception unwinds (the excepthook dedupes on
            # the same exception object, so this is the one bundle)
            from .. import blackbox as _blackbox
            if _blackbox._active:
                _blackbox.set_context(serve={
                    "decode_steps": steps,
                    "queued": len(self._queue),
                    "live_slots": sum(1 for s in self._slots
                                      if s is not None),
                    "completed": len(self._completed)})
                _blackbox.dump(trigger="manual",
                               reason=f"serve.run fatal: "
                                      f"{type(e).__name__}: {e}", exc=e)
            raise
        return self

    # -- shutdown / liveness ---------------------------------------------

    def stop(self, drain=True):
        """Graceful shutdown.  From the moment this is called,
        :meth:`submit` raises :class:`EngineBusy` ("stopping").

        ``drain=True`` finishes every in-flight AND queued request (runs
        the step loop to completion) before returning; ``drain=False``
        discards still-queued requests (each counted in
        ``serve.rejected_total``) and only fetches the already-dispatched
        deferred emits, leaving in-flight slots unfinished.  Either way
        the engine's /healthz provider is unregistered.  Idempotent."""
        if self._stopping:
            return self
        self._stopping = True
        tok = _goodput.begin("drain") if _goodput._active else None
        try:
            if drain:
                self.run()
            else:
                while self._queue:
                    self._reject(self._queue.popleft(), "stopping")
                self.drain()
        finally:
            _goodput.end(tok)
            _telemetry.unregister_health(self._health_name)
        return self

    # -- rolling weight updates (mx.servefleet) --------------------------

    def update_weights(self, params):
        """Swap the engine's weights in place with a new flat
        ``{name: jax.Array}`` tree (the :func:`mxnet_tpu.functional.
        param_arrays` layout) and return the previous ``(passthrough,
        quantized)`` tuple for :meth:`restore_weights` rollback.

        The new tree is pushed through the SAME quantization mode the
        engine was built with and validated structurally — names, shapes
        and dtypes must match what the AOT executables were compiled
        against, so the swap never invalidates the compiled grid and a
        subsequent :meth:`warmup` is a cache hit (zero compiles).  The
        KV cache is untouched: callers drain in-flight requests first
        (``stop(drain=True)``) because tokens decoded under the old
        weights must not continue under the new ones."""
        pt, qt, qdt = self._quantize_weights(dict(params))
        old_pt, old_qt = self._params

        def _sig(tree):
            return {k: (tuple(v.shape), str(v.dtype))
                    for k, v in tree.items()}
        for label, new, old in (("passthrough", pt, old_pt),
                                ("quantized", qt, old_qt)):
            if _sig(new) != _sig(old):
                missing = sorted(set(old) - set(new))
                extra = sorted(set(new) - set(old))
                changed = sorted(
                    k for k in set(new) & set(old)
                    if (tuple(new[k].shape), str(new[k].dtype))
                    != (tuple(old[k].shape), str(old[k].dtype)))
                raise MXNetError(
                    f"update_weights: incoming {label} params do not "
                    f"match the tree the engine compiled against "
                    f"(missing={missing[:4]}, extra={extra[:4]}, "
                    f"changed={changed[:4]}) — the compiled grid would "
                    "be invalid; build a fresh engine for a different "
                    "architecture")
        self._params = (pt, qt)
        self._qdtypes = qdt
        return (old_pt, old_qt)

    def restore_weights(self, old):
        """Roll back to a ``(passthrough, quantized)`` tuple previously
        returned by :meth:`update_weights` — the canary auto-rollback
        path.  No validation: the tuple came from this engine."""
        self._params = old
        return self

    def resume(self):
        """Re-open a drained engine after a rolling weight update:
        clears the stopping latch (submit() admits again) and
        re-registers the /healthz provider that :meth:`stop`
        unregistered.  The compiled grid, KV cache and slot machinery
        are untouched."""
        self._stopping = False
        self._register_health()
        return self

    def _slo_observe(self, kind, violated, slo_class="default"):
        """Account one request against the declared SLO objective of
        ``kind`` — the drain-time observation point the burn gauge and
        autoscaler admission signal ride."""
        self._slo_events.append(
            (time.monotonic(), kind, bool(violated), slo_class))
        if violated and _telemetry._active:
            _telemetry.inc("serve.slo_violations_total", kind=kind,
                           slo_class=slo_class)

    def slo_burn(self, window=300.0):
        """Per-kind error-budget burn rate over the trailing ``window``
        seconds: violation rate over the budget ``1 - serve.slo_target``
        (1.0 spends the budget exactly).  {} until an objective is
        armed and a request has been observed."""
        budget = 1.0 - float(_config.get("serve.slo_target"))
        if budget <= 0:
            return {}
        cut = time.monotonic() - window
        out = {}
        for kind, armed in (("ttft", self._slo_ttft),
                            ("tpot", self._slo_tpot)):
            if not armed:
                continue
            hits = [v for (t, k, v, _c) in self._slo_events
                    if k == kind and t >= cut]
            if not hits:
                continue
            burn = (sum(hits) / len(hits)) / budget
            out[kind] = round(burn, 4)
            if _telemetry._active:
                _telemetry.set_gauge("serve.slo_burn_rate",
                                     round(burn, 4), kind=kind)
        return out

    def _tpot_p50(self):
        """Observed TPOT p50 over the most recent completions — the unit
        of the EngineBusy ``retry_after_hint``. Falls back to the armed
        SLO objective (the declared cadence) before any request has
        finished, then to a conservative 20ms guess."""
        tpots = sorted(r.tpot for r in self._completed[-256:]
                       if r.tpot is not None)
        if tpots:
            return tpots[len(tpots) // 2]
        return self._slo_tpot if self._slo_tpot else 0.02

    def _retry_after_hint(self):
        return self._tpot_p50() * max(1, len(self._queue))

    def _reject(self, req, reason):
        """Account a queued request discarded by stop(drain=False): its
        spans close (rejected=True), ``req.rejected``/``req.reject_reason``
        flip so a waiting caller observes a structured outcome, and it
        never reaches a slot."""
        req.rejected = True
        req.reject_reason = reason
        if req._enq is not None:
            req._enq.end()
            req._enq = None
        if req._span is not None:
            req._span.end(rejected=True)
            req._span = None
        if _telemetry._active:
            _telemetry.inc("serve.rejected_total", reason=reason)

    def _health(self):
        """/healthz provider: red while stopping, and red when the engine
        has pending work but the step loop has not dispatched within
        ``serve.health_window`` seconds (a wedged or abandoned loop — the
        condition a static-OK healthz could never see); red as well when
        a declared serving SLO's error budget burns past
        ``goodput.burn_threshold`` — the 503 the autoscaler consumes."""
        if self._stopping:
            return {"ok": False, "state": "stopping"}
        if self._slo_ttft or self._slo_tpot:
            burn = self.slo_burn()
            thresh = float(_config.get("goodput.burn_threshold"))
            if burn and max(burn.values()) > thresh:
                return {"ok": False, "state": "slo_burn", "burn": burn,
                        "threshold": thresh}
        if not self.pending:
            return {"ok": True, "state": "idle", "steps": self._steps}
        last = (self._last_step_time if self._last_step_time is not None
                else self._created)
        age = time.monotonic() - last
        window = _config.get("serve.health_window")
        return {"ok": age < window, "state": "serving",
                "steps": self._steps, "last_step_age_s": round(age, 3)}

    # -- reporting -------------------------------------------------------

    def stats(self):
        """Host-side aggregate: counts, tokens, latency percentiles (from
        per-request records — telemetry histograms carry the bucketed
        view when enabled)."""
        done = self._completed
        ttfts = sorted(r.ttft for r in done if r.ttft is not None)
        tpots = sorted(r.tpot for r in done if r.tpot is not None)

        def pct(vals, q):
            if not vals:
                return None
            return float(onp.percentile(vals, q))

        out = {
            "completed": len(done),
            "queued": len(self._queue),
            "live": sum(1 for s in self._slots if s is not None),
            "steps": self._steps,
            # 1.0: the composition read every row of every slot
            "decode_rows_read_share": self._rows_read / (
                self._steps * self.max_slots * self.max_seq)
            if self._steps else None,
            "tokens_out": sum(len(r.generated) for r in done),
            "compiles": self.compiles,
            "post_warmup_compiles": self.post_warmup_compiles,
            "max_slots": self.max_slots,
            "max_seq": self.max_seq,
            "buckets": list(self.buckets),
            "quantize": self.quantize,
            "cache_dtype": self.cache_dtype,
        }
        for name, vals in (("ttft", ttfts), ("tpot", tpots)):
            out[name] = {"p50": pct(vals, 50), "p95": pct(vals, 95),
                         "p99": pct(vals, 99)}
        # per-request phase breakdown: unbounded trace instrumentation
        # while mx.trace records, else the bounded always-on reservoir
        # (serve.phase_sampling; None per phase only when both are off)
        phases = {}
        for key, label in (("queue_wait", "queue_wait"),
                           ("prefill", "prefill"),
                           ("decode_step", "decode_per_token")):
            vals = sorted(v for r in done for v in r.phases.get(key, ()))
            phases[label] = None if not vals else {
                "p50": pct(vals, 50), "p95": pct(vals, 95),
                "p99": pct(vals, 99)}
        out["phases"] = phases
        if self._slo_ttft or self._slo_tpot:
            viol = {}
            for (_t, kind, v, _c) in self._slo_events:
                if v:
                    viol[kind] = viol.get(kind, 0) + 1
            out["slo"] = {
                "ttft_ms": self._slo_ttft * 1e3 if self._slo_ttft else None,
                "tpot_ms": self._slo_tpot * 1e3 if self._slo_tpot else None,
                "target": float(_config.get("serve.slo_target")),
                "burn": self.slo_burn(),
                "violations": viol,
            }
        if self.quantize:
            pt, qt = self._params
            now, was = _quantize.quantized_bytes(pt, qt, self._qdtypes)
            out["weight_bytes"] = now
            out["weight_bytes_fp"] = was
            out["quantized_params"] = len(qt)
            out["passthrough_params"] = len(pt)
        if self._prefix is not None:
            out["prefix"] = self._prefix.stats()
        if self.draft is not None:
            rate = (self._spec_accepted / self._spec_proposed
                    if self._spec_proposed else None)
            out["spec"] = {
                "k": self._spec_k,
                "rounds": self._spec_rounds,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance_rate": None if rate is None
                else round(rate, 4),
            }
        if len(self._classes) > 1 or self._aging:
            per = {}
            for cls in self._classes:
                rs = [r for r in done if r.slo_class == cls]
                ct = sorted(r.ttft for r in rs if r.ttft is not None)
                cp = sorted(r.tpot for r in rs if r.tpot is not None)
                per[cls] = {
                    "completed": len(rs),
                    "queued": sum(1 for r in self._queue
                                  if r.slo_class == cls),
                    "ttft": {"p50": pct(ct, 50), "p99": pct(ct, 99)},
                    "tpot": {"p50": pct(cp, 50), "p99": pct(cp, 99)},
                }
            out["classes"] = per
            out["aged_admissions"] = self._aged_admissions
        return out

    @property
    def prefix_hits(self):
        """Host counter of prefix-cache admission hits — the per-replica
        number mx.servefleet snapshots into /servefleet and report()."""
        return self._prefix.hits if self._prefix is not None else 0

    @property
    def spec_acceptance(self):
        """Trailing draft-acceptance ratio, None without a draft or
        before the first speculative round drained."""
        if self.draft is None or not self._spec_proposed:
            return None
        return self._spec_accepted / self._spec_proposed


def load(model, max_slots=None, quantize=None, warmup=False, **kwargs):
    """Build a :class:`ServeEngine` over ``model``.

    ``quantize`` enables low-bit decode storage — "int8_weights",
    "int4_weights", "int8_kv", comma-combinable (docs/SERVING.md);
    ``warmup=True`` compiles the full bucket grid before returning so
    the first request never pays a compile.  ``prefix_cache=True`` (or
    ``serve.prefix_cache=1``) turns on radix prefix-cache KV reuse;
    ``draft=small_model`` turns on speculative decoding (greedy-exact,
    ``serve.spec_tokens`` proposals per round).
    """
    eng = ServeEngine(model, max_slots=max_slots, quantize=quantize,
                      **kwargs)
    if warmup:
        eng.warmup()
    return eng
