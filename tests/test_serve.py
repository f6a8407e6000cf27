"""mx.serve continuous-batching engine (docs/SERVING.md).

Oracles: the KV-cache decode surface against the full forward (bitwise
class of numerics — same matmul precision, different reduction extent),
continuous batching against sequential generation, the PR 2 recompile
detector as the zero-post-warmup-compile assertion, and the pipeline
sync_guard proving the decode loop never touches the host.
"""
import warnings

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
from mxnet_tpu.serve import quantize as squant
from mxnet_tpu.serve.engine import EngineBusy, _parse_buckets


def _tiny(**kw):
    cfg = dict(vocab_size=97, units=32, hidden_size=64, num_layers=2,
               num_heads=2, max_length=32, dropout=0.0, embed_dropout=0.0)
    cfg.update(kw)
    net = GPTForCausalLM(**cfg)
    net.initialize()
    return net


def _engine(net=None, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("buckets", "4,8")
    return mx.serve.load(net if net is not None else _tiny(), **kw)


def _ref_greedy(net, prompt, n):
    """Greedy continuation via the full forward — the no-cache oracle."""
    seq = list(prompt)
    for _ in range(n):
        lg = net(mx.np.array(onp.array([seq], dtype="int32"))).asnumpy()
        seq.append(int(lg[0, -1].argmax()))
    return seq[len(prompt):]


@pytest.fixture
def metrics():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.disable()


# -- block-level KV-cache surface -------------------------------------------

def test_prefill_matches_full_forward():
    mx.random.seed(0)
    net = _tiny()
    prompt = onp.random.RandomState(0).randint(1, 97, (1, 6)).astype("int32")
    full = net(mx.np.array(prompt)).asnumpy()
    caches = net.init_cache(max_slots=3, max_seq=16)
    logits, _ = net.prefill(mx.np.array(prompt), caches, 1)
    assert onp.allclose(logits.asnumpy(), full, atol=1e-5)


def test_decode_step_matches_full_forward():
    """Cached single-token decode must reproduce the full forward's last
    position, step after step, in an arbitrary slot."""
    mx.random.seed(1)
    net = _tiny()
    prompt = [3, 14, 15, 9, 2]
    caches = net.init_cache(max_slots=4, max_seq=16)
    slot = 2
    logits, caches = net.prefill(
        mx.np.array(onp.array([prompt], dtype="int32")), caches, slot)
    seq = list(prompt) + [int(logits.asnumpy()[0, -1].argmax())]
    for _ in range(5):
        tokens = onp.zeros((4, 1), dtype="int32")
        tokens[slot, 0] = seq[-1]
        positions = onp.zeros((4,), dtype="int32")
        positions[slot] = len(seq) - 1
        lg, caches = net.decode_step(mx.np.array(tokens), caches,
                                     mx.np.array(positions))
        ref = net(mx.np.array(onp.array([seq], dtype="int32"))).asnumpy()
        assert onp.allclose(lg.asnumpy()[slot], ref[0, -1], atol=1e-4)
        seq.append(int(lg.asnumpy()[slot].argmax()))


def test_init_cache_rejects_beyond_position_table():
    net = _tiny(max_length=16)
    with pytest.raises(ValueError):
        net.init_cache(max_slots=2, max_seq=64)


# -- engine correctness -----------------------------------------------------

def test_engine_greedy_matches_reference():
    mx.random.seed(2)
    net = _tiny()
    eng = _engine(net)
    rng = onp.random.RandomState(2)
    reqs = [eng.submit(rng.randint(1, 97, size=rng.randint(2, 8)).tolist(),
                       max_new_tokens=6) for _ in range(7)]
    eng.run()
    for r in reqs:
        assert r.finished
        assert r.generated == _ref_greedy(net, r.prompt, 6), r.id


def test_slot_reuse_waves():
    """More requests than slots: completions must free slots mid-flight
    and later requests must decode correctly in the reused slots."""
    mx.random.seed(3)
    net = _tiny()
    eng = _engine(net, max_slots=2, drain_window=2)
    rng = onp.random.RandomState(3)
    reqs = [eng.submit(rng.randint(1, 97, size=3 + (i % 4)).tolist(),
                       max_new_tokens=3 + (i % 3)) for i in range(9)]
    eng.run()
    assert all(r.finished for r in reqs)
    for r in reqs:
        assert r.generated == _ref_greedy(net, r.prompt, r.max_new_tokens)
    assert eng.stats()["completed"] == 9


def test_max_new_tokens_and_eos():
    mx.random.seed(4)
    net = _tiny()
    eng = _engine(net)
    r1 = eng.submit([5, 9, 3], max_new_tokens=4)
    eng.run()
    assert len(r1.generated) == 4
    eos = r1.generated[1]
    eng2 = _engine(net, eos_id=eos)
    r2 = eng2.submit([5, 9, 3], max_new_tokens=50)
    eng2.run()
    assert r2.generated == r1.generated[:2]  # stopped at the eos token
    assert r2.output_ids == r1.generated[:1]  # eos stripped


def test_generation_capped_by_max_seq():
    net = _tiny(max_length=16)
    eng = mx.serve.load(net, max_slots=2, max_seq=12, buckets="4,8")
    r = eng.submit([1, 2, 3, 4], max_new_tokens=500)
    eng.run()
    # positions stop at max_seq-1: 4 prompt rows + 8 generated contents
    assert len(r.generated) == 12 - 4
    assert r.finished


def test_prompt_longer_than_buckets_rejected():
    eng = _engine()
    with pytest.raises(mx.MXNetError):
        eng.submit(list(range(1, 20)), max_new_tokens=2)
    with pytest.raises(mx.MXNetError):
        eng.submit([], max_new_tokens=2)


def test_parse_buckets_validation():
    assert _parse_buckets("8,4,8") == [4, 8]
    with pytest.raises(mx.MXNetError):
        _parse_buckets("a,b")
    with pytest.raises(mx.MXNetError):
        _parse_buckets("-4")


def test_temperature_sampling_seeded():
    mx.random.seed(5)
    net = _tiny()
    outs = []
    for _ in range(2):
        eng = _engine(net, temperature=1.0, seed=11)
        r = eng.submit([5, 9, 3], max_new_tokens=8)
        eng.run()
        outs.append(r.generated)
    assert outs[0] == outs[1]  # same engine seed -> same stream
    eng = _engine(net, temperature=1.0, seed=12)
    r = eng.submit([5, 9, 3], max_new_tokens=8)
    eng.run()
    assert r.generated != outs[0]


def test_engine_requires_cache_surface():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4)
    net.initialize()
    with pytest.raises(mx.MXNetError):
        mx.serve.ServeEngine(net, max_seq=8)


def test_engine_stays_usable_after_run():
    """The engine is a persistent server: a second batch of requests
    reuses the same executables and cache."""
    mx.random.seed(6)
    net = _tiny()
    eng = _engine(net)
    eng.submit([4, 4, 4], max_new_tokens=3)
    eng.run()
    compiles = eng.compiles
    r = eng.submit([7, 7, 7], max_new_tokens=3)
    eng.run()
    assert r.finished
    assert eng.compiles == compiles
    assert r.generated == _ref_greedy(net, [7, 7, 7], 3)


# -- recompile guard (satellite: PR 2 detector as the assertion) ------------

def test_zero_recompiles_after_warmup(metrics):
    """After warmup over the bucket grid, a mixed request stream must
    trigger zero RecompileWarnings — the detector limit is pinned to the
    warmup compile count, so ANY further compile would fire it."""
    mx.random.seed(7)
    net = _tiny()
    eng = _engine(net, max_slots=3, buckets="4,8,16", drain_window=2)
    eng.warmup()
    assert eng.compiles == 4  # decode + 3 prefill buckets
    mx.config.set("telemetry.recompile_limit", eng.compiles)
    try:
        rng = onp.random.RandomState(7)
        with warnings.catch_warnings():
            warnings.simplefilter("error", telemetry.RecompileWarning)
            for i in range(12):
                eng.submit(rng.randint(1, 97,
                                       size=rng.randint(2, 16)).tolist(),
                           max_new_tokens=1 + (i % 5))
            eng.run()
    finally:
        mx.config.reset("telemetry.recompile_limit")
    assert eng.post_warmup_compiles == 0
    assert telemetry.counters().get(
        "serve.post_warmup_compiles_total") is None


def test_unwarmed_bucket_trips_detector(metrics):
    """Sanity check the guard has teeth: a compile past the limit DOES
    warn when a prompt shape escapes the warmed grid."""
    mx.random.seed(8)
    net = _tiny()
    eng = _engine(net, buckets="4")
    eng.warmup()
    eng.buckets = [4, 8]  # simulate an unwarmed bucket joining the grid
    mx.config.set("telemetry.recompile_limit", eng.compiles)
    try:
        with pytest.warns(telemetry.RecompileWarning):
            eng.submit([1] * 7, max_new_tokens=2)
            eng.run()
    finally:
        mx.config.reset("telemetry.recompile_limit")
    assert eng.post_warmup_compiles == 1


# -- sync-free loop ---------------------------------------------------------

def test_decode_loop_is_sync_free():
    """With a roomy drain window, dispatching admissions + decode steps
    must not touch the host; the drain at the end is the only sync."""
    mx.random.seed(9)
    net = _tiny()
    eng = _engine(net, drain_window=64)
    eng.warmup()
    for i in range(3):
        eng.submit([2 + i, 5, 9], max_new_tokens=8)
    # 1 admission step + enough decode steps to finish all 8 tokens:
    # completion is only OBSERVED at drain, so the guarded phase is
    # step-bounded — exactly the production cadence
    with mx.pipeline.sync_guard() as g:
        for _ in range(10):
            eng.step()
    assert g.count == 0, g.sites
    eng.drain()
    assert eng.stats()["completed"] == 3
    assert all(len(r.generated) == 8 for r in eng._completed)


def test_starved_queue_drains_bounded():
    """When the queue is starved for slots the engine reclaims oldest
    window entries, bounded by the queue depth — not a full drain."""
    mx.random.seed(10)
    net = _tiny()
    eng = _engine(net, max_slots=1, drain_window=8)
    rng = onp.random.RandomState(10)
    for _ in range(4):
        eng.submit(rng.randint(1, 97, size=3).tolist(), max_new_tokens=2)
    eng.run()
    assert eng.stats()["completed"] == 4


# -- weight-only int8 (satellite) -------------------------------------------

def test_quantize_roundtrip_error_bound():
    rng = onp.random.RandomState(0)
    w = rng.randn(64, 128).astype("float32")
    pt, qt, qdt = squant.quantize_params_int8({"w": w}, min_elements=1)
    assert not pt and list(qt) == ["w"]
    deq = squant.dequantize_params(pt, qt, qdt)["w"]
    # symmetric per-row int8: error <= scale/2 per row
    scale = onp.abs(w).max(axis=1, keepdims=True) / 127.0
    assert (onp.abs(onp.asarray(deq) - w) <= scale / 2 + 1e-7).all()


def test_quantize_skips_small_and_non2d():
    rng = onp.random.RandomState(1)
    params = {"big": rng.randn(128, 64).astype("float32"),
              "small": rng.randn(4, 4).astype("float32"),
              "vec": rng.randn(8192).astype("float32")}
    pt, qt, _ = squant.quantize_params_int8(params, min_elements=1024)
    assert set(qt) == {"big"} and set(pt) == {"small", "vec"}


def test_int8_engine_generates_and_shrinks_weights():
    mx.random.seed(11)
    net = _tiny(units=64, hidden_size=128)
    e8 = _engine(net, quantize="int8_weights")
    r8 = e8.submit([5, 9, 3], max_new_tokens=5)
    e8.run()
    st = e8.stats()
    assert st["weight_bytes"] < 0.5 * st["weight_bytes_fp"]
    assert len(r8.generated) == 5
    # tiny-model sanity: weight-only int8 shouldn't derail greedy decode
    efp = _engine(net)
    rfp = efp.submit([5, 9, 3], max_new_tokens=5)
    efp.run()
    agree = sum(a == b for a, b in zip(r8.generated, rfp.generated))
    assert agree >= 3, (r8.generated, rfp.generated)


def test_engine_rejects_unknown_quantize():
    with pytest.raises(mx.MXNetError):
        _engine(quantize="int4")


# -- int4 weights + int8 KV cache (tentpole) ---------------------------------

def test_int4_pack_roundtrip_and_bytes():
    rng = onp.random.RandomState(0)
    w = rng.randn(64, 256).astype("float32")
    pt, qt, qdt = squant.quantize_params_int4({"w": w}, min_elements=1)
    assert not pt and list(qt) == ["w"]
    packed, scales = qt["w"]
    assert onp.asarray(packed).dtype == onp.uint8
    assert onp.asarray(packed).shape == (64, 128)     # two nibbles/byte
    assert qdt["w"]["mode"] == "int4"
    deq = onp.asarray(squant.dequantize_params(pt, qt, qdt)["w"])
    # group-wise symmetric int4: error <= half a step per group
    g = qdt["w"]["group"]
    gmax = onp.abs(w.reshape(64, -1, g)).max(axis=2, keepdims=True)
    step = onp.broadcast_to(gmax / 7.0, w.reshape(64, -1, g).shape)
    assert (onp.abs(deq - w) <= step.reshape(64, 256) / 2 + 1e-7).all()
    now, was = squant.quantized_bytes(pt, qt, qdt)
    assert now / was <= 0.15, now / was                # the CI gate's bound


def test_int4_skips_odd_cols_and_non2d():
    rng = onp.random.RandomState(1)
    params = {"odd": rng.randn(64, 129).astype("float32"),
              "vec": rng.randn(8192).astype("float32"),
              "ok": rng.randn(64, 128).astype("float32")}
    pt, qt, _ = squant.quantize_params_int4(params, min_elements=1)
    assert set(qt) == {"ok"} and set(pt) == {"odd", "vec"}


def test_int4_engine_generates_and_shrinks_weights():
    # greedy on an untrained net is argmax over near-uniform logits —
    # seed chosen so fp32 decode has enough margin to survive 4-bit
    # weights (a trained model's logit margins are far larger)
    mx.random.seed(29)
    net = _tiny(units=64, hidden_size=128)
    e4 = _engine(net, quantize="int4_weights")
    r4 = e4.submit([5, 9, 3], max_new_tokens=5)
    e4.run()
    st = e4.stats()
    assert st["weight_bytes"] < 0.25 * st["weight_bytes_fp"]
    assert st["quantized_params"] > 0
    assert st["quantized_params"] + st["passthrough_params"] == \
        st["quantized_params"] + len(e4._params[0])
    assert len(r4.generated) == 5
    efp = _engine(net)
    rfp = efp.submit([5, 9, 3], max_new_tokens=5)
    efp.run()
    # 4-bit weights on a tiny random net: most greedy tokens still agree
    agree = sum(a == b for a, b in zip(r4.generated, rfp.generated))
    assert agree >= 3, (r4.generated, rfp.generated)


def test_int8_kv_cache_greedy_parity():
    """int8 KV storage quantizes each written row against its own absmax:
    on a well-scaled tiny model greedy decode must match fp32 KV."""
    mx.random.seed(14)
    net = _tiny()
    rng = onp.random.RandomState(14)
    prompts = [rng.randint(1, 97, size=rng.randint(2, 8)).tolist()
               for _ in range(5)]
    ekv = _engine(net, quantize="int8_kv")
    assert ekv.cache_dtype == "int8"
    assert ekv.stats()["cache_dtype"] == "int8"
    rkv = [ekv.submit(p, max_new_tokens=6) for p in prompts]
    ekv.run()
    efp = _engine(net)
    rfp = [efp.submit(p, max_new_tokens=6) for p in prompts]
    efp.run()
    match = sum(a.generated == b.generated for a, b in zip(rkv, rfp))
    assert match >= 4, [(a.generated, b.generated)
                        for a, b in zip(rkv, rfp)]


def test_int8_kv_cache_arrays_are_int8():
    net = _tiny()
    eng = _engine(net, quantize="int8_kv")
    (kq, ks), (vq, vs) = eng._cache[0]
    assert onp.asarray(kq).dtype == onp.int8
    assert onp.asarray(vq).dtype == onp.int8
    assert onp.asarray(ks).dtype == onp.float32
    heads = 2                                # _tiny()'s
    assert ks.shape == kq.shape[:2] + (heads,)   # per-(slot, row, head)


def test_combined_int4_weights_int8_kv():
    mx.random.seed(15)
    net = _tiny(units=64, hidden_size=128)
    eng = _engine(net, quantize="int4_weights,int8_kv")
    assert eng.quantize == "int4_weights,int8_kv"
    assert eng.cache_dtype == "int8"
    r = eng.submit([7, 2, 9], max_new_tokens=5)
    eng.run()
    assert len(r.generated) == 5
    st = eng.stats()
    assert st["weight_bytes"] < 0.25 * st["weight_bytes_fp"]


def test_conflicting_weight_modes_rejected():
    with pytest.raises(mx.MXNetError):
        _engine(quantize="int8_weights,int4_weights")


def test_zero_recompiles_with_quantization(metrics):
    """The low-bit cache pytree and dequant-on-read must not change the
    traced signature per step: PR 2's detector stays at zero after
    warmup in every quantize mode."""
    mx.random.seed(16)
    for spec in ("int8_weights", "int4_weights,int8_kv"):
        telemetry.reset()
        eng = _engine(_tiny(), quantize=spec)
        eng.warmup()
        for p in ([3, 1, 4], [1, 5], [9, 2, 6, 5]):
            eng.submit(p, max_new_tokens=4)
        eng.run()
        assert eng.stats()["post_warmup_compiles"] == 0, spec


def test_quantize_eligibility_knobs():
    rng = onp.random.RandomState(2)
    params = {"mid": rng.randn(32, 32).astype("float32")}   # 1024 elems
    pt, qt, _ = squant.quantize_params_int8(params)         # default 4096
    assert set(pt) == {"mid"} and not qt
    prev = mx.config.set("serve.quantize_min_elems", 512)
    try:
        pt, qt, _ = squant.quantize_params_int8(params)
        assert set(qt) == {"mid"}
    finally:
        mx.config.set("serve.quantize_min_elems", prev)
    prev = mx.config.set("serve.quantize_ndim", 1)
    try:
        pt, qt, _ = squant.quantize_params_int8(
            {"vec": rng.randn(8192).astype("float32")})
        assert set(qt) == {"vec"}                            # 1-D now eligible
    finally:
        mx.config.set("serve.quantize_ndim", prev)


def test_int4_group_size_knob():
    rng = onp.random.RandomState(3)
    w = rng.randn(8, 256).astype("float32")
    prev = mx.config.set("serve.quantize_group_size", 64)
    try:
        _, qt, qdt = squant.quantize_params_int4({"w": w}, min_elements=1)
    finally:
        mx.config.set("serve.quantize_group_size", prev)
    assert qdt["w"]["group"] == 64
    assert qt["w"][1].shape == (8, 4)                        # 256/64 groups


def test_quantized_param_counts_in_telemetry(metrics):
    mx.random.seed(17)
    eng = _engine(_tiny(units=64, hidden_size=128),
                  quantize="int8_weights")
    g = telemetry.snapshot()["gauges"]
    st = eng.stats()
    assert g["serve.quantized_params"] == st["quantized_params"] > 0
    assert g["serve.passthrough_params"] == st["passthrough_params"]


# -- serve.* telemetry ------------------------------------------------------

def test_serve_metrics_recorded(metrics):
    mx.random.seed(12)
    eng = _engine(drain_window=2)
    for _ in range(3):
        eng.submit([3, 1, 4], max_new_tokens=4)
    eng.run()
    c = telemetry.counters()
    assert c["serve.requests_total"] == 3
    assert c["serve.admitted_total"] == 3
    assert c["serve.completed_total"] == 3
    assert c["serve.tokens_total"] == 12
    assert c["serve.steps_total"] >= 3
    snap = telemetry.snapshot()
    assert snap["histograms"]["serve.ttft_seconds"]["count"] == 3
    assert snap["histograms"]["serve.tpot_seconds"]["count"] == 3
    assert "serve.step_seconds" in snap["histograms"]
    q = telemetry.quantiles("serve.ttft_seconds")
    assert set(q) == {"p50", "p95", "p99"}
    assert 0 <= q["p50"] <= q["p95"] <= q["p99"]
    st = eng.stats()
    assert st["ttft"]["p50"] is not None
    assert st["tpot"]["p99"] >= st["tpot"]["p50"]


# -- histogram quantiles (satellite) ----------------------------------------

def test_hist_quantile_estimation(metrics):
    for v in [0.001] * 50 + [0.008] * 40 + [0.3] * 10:
        telemetry.observe("q.lat", v)
    q = telemetry.quantiles("q.lat")
    assert q["p50"] == pytest.approx(0.001, abs=1e-6)
    assert 0.25 <= q["p95"] <= 0.5   # interpolated inside the 0.3 bucket
    assert 0.25 <= q["p99"] <= 0.5
    assert telemetry.quantiles("q.lat", qs=(0.999,))["p99_9"] <= 0.5
    assert telemetry.quantiles("nope") is None


def test_quantiles_in_snapshot_and_exposition(metrics):
    telemetry.observe("q.x", 0.004)
    telemetry.observe("q.x", 0.07)
    snap = telemetry.snapshot()
    assert set(snap["histograms"]["q.x"]["quantiles"]) == {"50", "95", "99"}
    import json
    json.dumps(snap)  # stays JSON-safe
    text = telemetry.exposition()
    assert 'mxnet_q_x{quantile="0.5"}' in text
    assert 'mxnet_q_x{quantile="0.99"}' in text
    # quantile estimates stay within the recorded value range's bucket
    line = [l for l in text.splitlines() if 'quantile="0.99"' in l][0]
    assert float(line.split()[-1]) <= 0.1


def test_quantiles_ride_jsonl_reports(metrics, tmp_path):
    rep = telemetry.TrainingTelemetry(path=str(tmp_path / "run.jsonl"),
                                      interval=100)
    telemetry.observe("q.y", 0.01)
    rep.close()
    records = telemetry.TrainingTelemetry.read(str(tmp_path / "run.jsonl"))
    final = [r for r in records if r.get("type") == "run_report"][-1]
    hists = final["metrics"]["histograms"]
    assert "quantiles" in hists["q.y"]


# -- graceful drain, backpressure, /healthz ---------------------------------

def test_submit_backpressure_bounded_queue(metrics):
    prev = mx.config.set("serve.max_queue", 2)
    try:
        eng = _engine()
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.submit([4, 5], max_new_tokens=2)
        with pytest.raises(EngineBusy) as ei:
            eng.submit([6], max_new_tokens=2)
        assert ei.value.reason == "queue_full"
        assert ei.value.queued == 2 and ei.value.max_queue == 2
        assert telemetry.counters(aggregate=True).get(
            "serve.rejected_total") == 1
        eng.run()                        # queue drains: admission reopens
        assert eng.submit([7], max_new_tokens=1) is not None
        eng.stop()
    finally:
        mx.config.set("serve.max_queue", prev)


def test_stop_drain_finishes_in_flight_and_rejects_new(metrics):
    eng = _engine()
    reqs = [eng.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
    eng.stop(drain=True)
    assert all(r.finished for r in reqs)
    with pytest.raises(EngineBusy) as ei:
        eng.submit([4], max_new_tokens=1)
    assert ei.value.reason == "stopping"
    eng.stop()                           # idempotent


def test_stop_no_drain_discards_queued(metrics):
    eng = _engine(max_slots=1)
    a = eng.submit([1, 2], max_new_tokens=2)
    b = eng.submit([3, 4], max_new_tokens=2)
    eng.stop(drain=False)
    assert not a.finished and not b.finished and not eng.pending
    assert telemetry.counters(aggregate=True).get(
        "serve.rejected_total") == 2


def test_stop_no_drain_every_queued_request_observes_rejection(metrics):
    """stop(drain=False) must leave NO queued request ambiguous: each
    one flips rejected=True with a machine-readable reason, so a caller
    holding the handle distinguishes 'discarded' from 'still running'
    without string-matching logs."""
    eng = _engine(max_slots=1)
    reqs = [eng.submit([1, 2, 3], max_new_tokens=2) for _ in range(5)]
    eng.stop(drain=False)
    queued = [r for r in reqs if not r.finished and r.slot is None]
    assert queued, "expected still-queued requests at stop time"
    for r in queued:
        assert r.rejected is True
        assert r.reject_reason == "stopping"
    # requests that reached a slot are unfinished but NOT rejected:
    # their state is 'abandoned in flight', a different contract
    for r in reqs:
        if r not in queued:
            assert not r.rejected
    by_reason = {k: v for k, v in telemetry.counters().items()
                 if k.startswith("serve.rejected_total")}
    assert any('reason="stopping"' in k for k in by_reason), by_reason
    assert sum(by_reason.values()) == len(queued)


def test_engine_busy_carries_retry_after_hint(metrics):
    """EngineBusy.retry_after_hint = queue depth x observed TPOT p50 —
    the machine-readable backoff the fleet router consumes instead of
    hammering a saturated replica."""
    prev = mx.config.set("serve.max_queue", 2)
    try:
        eng = _engine(max_slots=1)
        # one completed request seeds the TPOT p50 observation
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run()
        p50 = eng._tpot_p50()
        assert p50 > 0
        eng.submit([1, 2], max_new_tokens=2)
        eng.submit([3, 4], max_new_tokens=2)
        with pytest.raises(EngineBusy) as ei:
            eng.submit([5], max_new_tokens=1)
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after_hint == pytest.approx(2 * p50)
        assert f"{ei.value.retry_after_hint:.3f}" in str(ei.value)
        eng.run()
        eng.stop()
        with pytest.raises(EngineBusy) as ei:
            eng.submit([6], max_new_tokens=1)
        assert ei.value.reason == "stopping"
        assert ei.value.retry_after_hint > 0  # floor: one p50 interval
    finally:
        mx.config.set("serve.max_queue", prev)


def test_engine_healthz_tracks_step_loop(metrics):
    eng = _engine()
    _, checks = telemetry.health()
    assert checks["serve"]["state"] == "idle" and checks["serve"]["ok"]
    eng.submit([1, 2], max_new_tokens=2)
    prev = mx.config.set("serve.health_window", 0.0)
    try:
        ok, checks = telemetry.health()
        assert ok is False and checks["serve"]["state"] == "serving"
    finally:
        mx.config.set("serve.health_window", prev)
    eng.run()
    assert telemetry.health()[1]["serve"]["ok"] is True
    eng.stop()
    assert "serve" not in telemetry.health()[1]


# -- SLO budgets + always-on phase reservoir (docs/OBSERVABILITY.md) --------

def test_slo_violations_counted_and_burn_gauge(metrics):
    prev = [mx.config.set("serve.slo_ttft_ms", 0.0001),
            mx.config.set("serve.slo_tpot_ms", 0.0001),
            mx.config.set("serve.slo_target", 0.9)]
    try:
        eng = _engine()
        eng.submit([5, 9, 3], max_new_tokens=4)
        eng.run()
        counters = telemetry.counters()
        viol = {k: v for k, v in counters.items()
                if k.startswith("serve.slo_violations_total")}
        assert sum(viol.values()) >= 1, counters
        assert any('kind="ttft"' in k for k in viol), viol
        burn = eng.slo_burn()
        assert burn and max(burn.values()) > 2.0
        slo = eng.stats()["slo"]
        assert slo["violations"]["ttft"] >= 1
        assert slo["burn"] == burn
        # a hot burn rate flips the engine health check red
        ok, checks = telemetry.health()
        assert ok is False and checks["serve"]["state"] == "slo_burn"
        eng.stop()
    finally:
        mx.config.set("serve.slo_ttft_ms", prev[0])
        mx.config.set("serve.slo_tpot_ms", prev[1])
        mx.config.set("serve.slo_target", prev[2])


def test_slo_disarmed_by_default(metrics):
    eng = _engine()
    eng.submit([5, 9], max_new_tokens=2)
    eng.run()
    assert eng.slo_burn() == {}
    assert "slo" not in eng.stats()
    assert not any(k.startswith("serve.slo_violations_total")
                   for k in telemetry.counters())
    eng.stop()


def test_phase_reservoir_without_tracer(metrics):
    # stats()["phases"] populates from the bounded reservoir even when
    # the request tracer is off
    eng = _engine()
    for _ in range(2):
        eng.submit([5, 9, 3], max_new_tokens=3)
    eng.run()
    phases = eng.stats()["phases"]
    for label in ("queue_wait", "prefill", "decode_per_token"):
        assert phases[label] is not None, phases
        assert phases[label]["p50"] >= 0.0
    eng.stop()


def test_phase_reservoir_disabled_and_bounded(metrics):
    prev = mx.config.set("serve.phase_sampling", 0)
    try:
        eng = _engine()
        eng.submit([5, 9], max_new_tokens=2)
        eng.run()
        assert all(v is None                   # off and no tracer
                   for v in eng.stats()["phases"].values())
        eng.stop()
    finally:
        mx.config.set("serve.phase_sampling", prev)
    prev = mx.config.set("serve.phase_sampling", 2)
    try:
        eng = _engine()
        req = eng.submit([5, 9, 3], max_new_tokens=6)
        eng.run()
        assert len(req.phases["decode_step"]) <= 2   # reservoir cap
        eng.stop()
    finally:
        mx.config.set("serve.phase_sampling", prev)
