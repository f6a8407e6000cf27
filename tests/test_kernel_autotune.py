"""Kernel-level autotuning: searched Pallas block shapes, the learned
cost model, and drift-triggered online re-tuning.

Strategy mirrors test_autotune.py: the search loop runs against a
deterministic fake measurer (convergence, fraction cap, persistence and
the retune drill are exact assertions); a parity oracle then proves
every candidate block shape computes the same function in interpret
mode (outputs allclose, grads for flash attention), so ANY winner the
search picks is numerically safe.
"""
import json
import math

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autotune, config, fault, insight, telemetry
from mxnet_tpu.autotune import kernels as K
from mxnet_tpu.autotune.learned import (LearnedCostModel, rank_gate,
                                        spearman)
from mxnet_tpu.autotune.persist import append_trials, kernel_key

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path):
    """Every test gets its own winners file, a clean tuned table and
    clean counters."""
    prior = config.get("autotune.cache_dir")
    config.set("autotune.cache_dir", str(tmp_path / "autotune"))
    K.reset()
    telemetry.reset()
    telemetry.enable()
    try:
        yield
    finally:
        config.set("autotune.cache_dir", prior)
        config.set("autotune.retune_on_drift", False)
        K.reset()
        insight.reset()
        insight.disable()
        telemetry.reset()
        telemetry.disable()
        fault.configure(None)


def _planted(best, weight=1.0):
    """Deterministic fake measurer: seconds grow with the log-distance
    of every block axis from the planted optimum."""
    def measure(kernel, bucket, blocks):
        d = sum(abs(math.log2(v) - math.log2(best.get(k, v)))
                for k, v in blocks.items())
        return 1e-3 * (1.0 + weight * d)
    return measure


# ---------------------------------------------------------------------------
# routing: static defaults, buckets, tuned table
# ---------------------------------------------------------------------------

def test_static_defaults_cover_every_kernel_and_family():
    for fam in ("v4", "v5e", "v6", "cpu"):
        for kern in K.KERNELS:
            blocks = K._STATIC_DEFAULTS[fam][kern]
            assert set(blocks) == set(K._SPACE[kern])
    # the CPU row IS the historical one-size constants (interpret-mode
    # CI behavior must be bit-identical untuned)
    assert K._STATIC_DEFAULTS["cpu"]["flash_attention"] == {
        "block_q": 1024, "block_k": 512}
    assert K._STATIC_DEFAULTS["cpu"]["quantized_matmul"] == {
        "block_m": 256, "block_n": 256}
    assert K._STATIC_DEFAULTS["cpu"]["ln_residual"] == {"block_rows": 256}


def test_device_family_mapping():
    assert K._device_family("TPU v4") == "v4"
    assert K._device_family("TPU v3") == "v4"
    assert K._device_family("TPU v5e") == "v5e"
    assert K._device_family("TPU v5 lite") == "v5e"
    assert K._device_family("TPU v5p") == "v6"
    assert K._device_family("TPU v6e") == "v6"
    assert K._device_family("cpu") == "cpu"
    assert K._device_family() == "cpu"    # this CI host


def test_shape_bucket_rounds_to_powers_of_two():
    assert K.shape_bucket("flash_attention", (100, 120, 64)) == (128, 128, 64)
    assert K.shape_bucket("quantized_matmul", (1000, 512, 3000)) == (
        1024, 512, 4096)
    assert K.shape_bucket("ln_residual", (5000, 1024)) == (8192, 1024)
    with pytest.raises(mx.MXNetError):
        K.shape_bucket("nope", (1, 2))


def test_resolve_blocks_untuned_is_static_and_tuned_wins_per_bucket():
    assert K.resolve_blocks("flash_attention") == {
        "block_q": 1024, "block_k": 512}
    assert K.resolve_blocks("flash_attention", (300, 300, 64)) == {
        "block_q": 1024, "block_k": 512}
    K._TUNED[("flash_attention", (512, 512, 64))] = {
        "block_q": 256, "block_k": 128}
    # matching bucket -> tuned; other buckets stay static
    assert K.resolve_blocks("flash_attention", (300, 300, 64)) == {
        "block_q": 256, "block_k": 128}
    assert K.resolve_blocks("flash_attention", (2000, 2000, 64)) == {
        "block_q": 1024, "block_k": 512}
    K.reset()
    assert K.resolve_blocks("flash_attention", (300, 300, 64)) == {
        "block_q": 1024, "block_k": 512}


def test_kernel_candidates_dedup_by_clamped_blocks():
    full = K.kernel_candidates("flash_attention")
    assert len(full) == 16 and full == K.kernel_candidates("flash_attention")
    # a tiny bucket collapses the grid to ONE effective candidate
    assert len(K.kernel_candidates("flash_attention", (128, 128, 64))) == 1
    some = K.kernel_candidates("flash_attention", (512, 512, 64))
    assert 1 < len(some) < len(full)
    with pytest.raises(mx.MXNetError):
        K.kernel_candidates("flash_attention", axes={"block_z": (1,)})


# ---------------------------------------------------------------------------
# parity oracle: every candidate computes the same function
# ---------------------------------------------------------------------------

def test_flash_attention_parity_across_all_candidate_blocks():
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    rs = onp.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 2, 200, 64), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 200, 64), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 200, 64), jnp.float32)
    bucket = K.shape_bucket("flash_attention", (200, 200, 64))

    def run(blocks, bwd_blocks):
        def loss(q_, k_, v_):
            return flash_attention(
                q_, k_, v_, causal=True, interpret=True,
                block_q=blocks["block_q"], block_k=blocks["block_k"],
                bwd_block_q=bwd_blocks["block_q"],
                bwd_block_k=bwd_blocks["block_k"]).sum()
        out = flash_attention(q, k, v, causal=True, interpret=True,
                              **blocks)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, g

    fwd_cands = K.kernel_candidates("flash_attention", bucket)
    bwd_cands = K.kernel_candidates("flash_attention_bwd", bucket)
    assert len(fwd_cands) > 1 and len(bwd_cands) > 1
    ref_out, ref_g = run(fwd_cands[0], bwd_cands[0])
    for fb in fwd_cands[1:]:
        out, g = run(fb, bwd_cands[0])
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref_out),
                                    atol=2e-5)
        for a, b in zip(g, ref_g):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        atol=2e-4)
    for bb in bwd_cands[1:]:     # bwd tiles vary independently of the fwd
        _, g = run(fwd_cands[0], bb)
        for a, b in zip(g, ref_g):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        atol=2e-4)


@pytest.mark.parametrize("kernel", ["quantized_matmul", "fp8_matmul"])
def test_matmul_parity_across_all_candidate_blocks(kernel):
    from mxnet_tpu.ops.pallas.quant_matmul import (FP8_FORMATS, fp8_matmul,
                                                   quantized_matmul)
    rs = onp.random.RandomState(1)
    m = n = kk = 200
    x = jnp.asarray(rs.randn(m, kk), jnp.float32)
    ws = jnp.asarray(onp.abs(rs.randn(n)) / 127.0 + 1e-4, jnp.float32)
    xs = jnp.float32(0.05)
    if kernel == "quantized_matmul":
        w = jnp.asarray(rs.randint(-127, 128, (n, kk)), jnp.int8)
        mm = lambda **kw: quantized_matmul(x, w, ws, xs, interpret=True,
                                           **kw)
    else:
        w = jnp.asarray(rs.randn(n, kk), FP8_FORMATS["e4m3"][0])
        mm = lambda **kw: fp8_matmul(x, w, ws, xs, interpret=True, **kw)
    bucket = K.shape_bucket(kernel, (m, n, kk))
    cands = K.kernel_candidates(kernel, bucket)
    assert len(cands) > 1
    ref = mm(**cands[0])
    for blocks in cands[1:]:
        onp.testing.assert_allclose(onp.asarray(mm(**blocks)),
                                    onp.asarray(ref), rtol=1e-5, atol=1e-4)


def test_ln_residual_parity_across_all_candidate_blocks():
    from mxnet_tpu.ops.pallas.ln_residual import ln_residual_dropout
    rs = onp.random.RandomState(2)
    x = jnp.asarray(rs.randn(300, 128), jnp.float32)
    h = jnp.asarray(rs.randn(300, 128), jnp.float32)
    g = jnp.asarray(rs.randn(128), jnp.float32)
    b = jnp.asarray(rs.randn(128), jnp.float32)
    bucket = K.shape_bucket("ln_residual", (300, 128))
    cands = K.kernel_candidates("ln_residual", bucket)
    assert len(cands) > 1
    ref = ln_residual_dropout(x, h, g, b, interpret=True, **cands[0])
    for blocks in cands[1:]:
        out = ln_residual_dropout(x, h, g, b, interpret=True, **blocks)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                    atol=2e-5)


# ---------------------------------------------------------------------------
# search: convergence, fraction cap, persistence
# ---------------------------------------------------------------------------

def _vmem_kept(kernel, bucket):
    from mxnet_tpu.autotune.cost import (VMEM_BYTES, VMEM_FRACTION,
                                         kernel_tile_bytes)
    budget = int(VMEM_BYTES * VMEM_FRACTION)
    return [b for b in K.kernel_candidates(kernel, bucket)
            if kernel_tile_bytes(kernel, bucket, b) <= budget]


def test_flash_cost_model_follows_the_kernels_tiles():
    """The analytic entries rank by what the kernels now do: a causal
    tile above the diagonal costs the backward a launch and the forward
    nothing, the forward holds K and V whole, the backward's fp32
    scratch and four score tiles count against VMEM."""
    from mxnet_tpu.autotune.cost import kernel_cost, kernel_tile_bytes
    from mxnet_tpu.ops.pallas.flash_attention import tile_counts
    bucket = (1024, 1024, 64)

    def blocks(bq, bk):
        return {"block_q": bq, "block_k": bk}
    # backward at 512/512: 4 grid steps a kernel (1 skipped), 3 tiles of
    # 7 passes, a pass of 512 x 512 x 128 weighing 1.0
    assert tile_counts(1024, 1024, 512, 512, True)["skipped"] == 1
    assert kernel_cost("flash_attention_bwd", bucket,
                       blocks(512, 512)) == pytest.approx(2 * 4 + 7 * 3)
    # forward: 2 grid steps + 3 loop trips, 3 tiles of 2 passes
    assert kernel_cost("flash_attention", bucket,
                       blocks(512, 512)) == pytest.approx(5 + 2 * 3)
    # the order the v5e read (PERF.md, PR 26): larger tiles first
    order = [blocks(512, 512), blocks(512, 256), blocks(256, 256),
             blocks(256, 128)]
    costs = [kernel_cost("flash_attention_bwd", bucket, b) for b in order]
    assert costs == sorted(costs) and len(set(costs)) == len(costs)
    # VMEM: the forward's K and V grow with the sequence at fixed blocks
    assert (kernel_tile_bytes("flash_attention", (4096, 4096, 64),
                              blocks(512, 512))
            - kernel_tile_bytes("flash_attention", bucket, blocks(512, 512))
            == 4 * 2 * (4096 - 1024) * 128)
    assert kernel_tile_bytes("flash_attention_bwd", bucket,
                             blocks(512, 512)) == 4 * (
        4 * 512 * 128 + 6 * 512 * 128 + 4 * 512 * 512 + 2 * 512)
    # the v5e's row is the chip's sweep, inside the searched space
    v5e = K._STATIC_DEFAULTS["v5e"]
    assert v5e["flash_attention_bwd"] == blocks(512, 512)
    assert v5e["flash_attention"] == blocks(512, 512)
    for kern in ("flash_attention", "flash_attention_bwd"):
        for axis, val in v5e[kern].items():
            assert val in K._SPACE[kern][axis]


def test_search_converges_to_planted_optimum():
    best = {"block_q": 512, "block_k": 256}
    bucket = (2048, 2048, 128)
    shapes = {"flash_attention": [bucket]}
    kept = _vmem_kept("flash_attention", bucket)
    assert len(kept) > 8        # a rich grid survives the VMEM budget
    res = K.search_kernels(kernels=("flash_attention",), shapes=shapes,
                           measure=_planted(best), fraction=1.0)
    assert res.n_trials == len(kept) and not res.cache_hits
    assert res.tuned[("flash_attention", bucket)] == best
    # published into the process-global table: call-site routing sees it
    assert K.resolve_blocks("flash_attention", (2000, 1500, 128)) == best
    assert telemetry.counters()[
        "autotune.kernel_trials_total"] == len(kept)
    assert telemetry.counters()[
        'autotune.pruned_total{reason="vmem"}'] == 16 - len(kept)


def test_second_search_is_answered_from_cache_with_zero_trials():
    best = {"block_q": 512, "block_k": 256}
    shapes = {"flash_attention": [(2048, 2048, 128)]}
    K.search_kernels(kernels=("flash_attention",), shapes=shapes,
                     measure=_planted(best), fraction=1.0)
    K.reset()   # fresh process simulation: table empty, file warm
    calls = []

    def measure(kernel, bucket, blocks):
        calls.append(blocks)
        return 1.0

    res = K.search_kernels(kernels=("flash_attention",), shapes=shapes,
                           measure=measure)
    assert not calls and res.n_trials == 0 and res.cache_hits == 1
    assert res.tuned[("flash_attention", (2048, 2048, 128))] == best
    assert K.resolve_blocks("flash_attention", (2048, 2048, 128)) == best
    assert telemetry.counters()["autotune.kernel_cache_hits_total"] == 1


def test_measured_fraction_respects_the_knob_and_includes_default():
    bucket = (2048, 2048, 128)
    shapes = {"flash_attention": [bucket]}
    kept = len(_vmem_kept("flash_attention", bucket))
    res = K.search_kernels(kernels=("flash_attention",), shapes=shapes,
                           measure=_planted({"block_q": 256,
                                             "block_k": 128}),
                           fraction=0.25)
    assert res.n_trials == max(1, int(0.25 * kept)) == 3
    # the static default is always one of the measured baselines
    default = K.static_blocks("flash_attention")
    eff = {tuple(sorted(t["blocks"].items())) for t in res.trials}
    assert tuple(sorted(default.items())) in eff
    counters = telemetry.counters()
    assert counters['autotune.pruned_total{reason="ranked_out"}'] == kept - 3


def test_winner_persists_with_kind_kernel_and_schema_2(tmp_path):
    # at dim 1024 the VMEM budget prunes block_rows >= 512, so plant 256
    shapes = {"ln_residual": [(4096, 1024)]}
    res = K.search_kernels(kernels=("ln_residual",), shapes=shapes,
                           measure=_planted({"block_rows": 256}),
                           fraction=1.0)
    with open(autotune.winners_path()) as f:
        doc = json.load(f)
    assert doc["schema"] == 2
    key = kernel_key("ln_residual", (4096, 1024), "cpu")
    rec = doc["winners"][key]
    assert rec["kind"] == "kernel"
    assert rec["blocks"] == {"block_rows": 256}
    assert len(doc["trials"]) == res.n_trials > 0
    # load_tuned restores the table in a fresh process
    K.reset()
    assert K.load_tuned() == 1
    assert K.resolve_blocks("ln_residual", (4000, 1024)) == {
        "block_rows": 256}


def test_schema_1_file_migrates_in_place_and_step_winner_survives():
    path = autotune.winners_path()
    import os
    os.makedirs(os.path.dirname(path), exist_ok=True)
    step_rec = {"config": {"batch_size": 32, "steps_per_call": 2,
                           "grad_accum": 1, "zero": 0, "remat": False,
                           "prefetch_depth": 2},
                "fingerprint": "abcd1234", "items_per_s": 100.0}
    with open(path, "w") as f:
        json.dump({"version": 1,
                   "winners": {"abcd1234|cpu|dp1": step_rec}}, f)
    # a kernel search writes into the SAME file; the v1 step winner
    # must survive verbatim with zero re-trials needed
    K.search_kernels(kernels=("ln_residual",),
                     shapes={"ln_residual": [(4096, 1024)]},
                     measure=_planted({"block_rows": 512}), fraction=1.0)
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == 2 and doc["version"] == 2
    assert doc["winners"]["abcd1234|cpu|dp1"] == step_rec
    assert autotune.load_winner("abcd1234|cpu|dp1") == step_rec
    assert kernel_key("ln_residual", (4096, 1024), "cpu") in doc["winners"]


def test_oom_trial_is_recorded_and_search_survives():
    fault.configure("autotune.trial_oom:at=2,times=1")
    res = K.search_kernels(kernels=("flash_attention",),
                           shapes={"flash_attention": [(2048, 2048, 128)]},
                           measure=_planted({"block_q": 512,
                                             "block_k": 256}),
                           fraction=1.0)
    by_status = {}
    for t in res.trials:
        by_status[t["status"]] = by_status.get(t["status"], 0) + 1
    n_kept = len(_vmem_kept("flash_attention", (2048, 2048, 128)))
    assert by_status.get("oom") == 1 and by_status["ok"] == n_kept - 1
    assert res.tuned   # a winner still emerged
    assert telemetry.counters()["autotune.trials_oom_total"] == 1


# ---------------------------------------------------------------------------
# learned cost model
# ---------------------------------------------------------------------------

def test_spearman_ranks_with_ties():
    assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert spearman([1, 1, 1], [1, 2, 3]) == 0.0
    assert spearman([], []) == 0.0
    with pytest.raises(mx.MXNetError):
        spearman([1], [1, 2])


def _synthetic_records(bucket=(1024, 1024, 1024)):
    """Ground truth the analytic model ranks BADLY: runtime grows with
    tile size (the analytic cost prefers big tiles — fewer launches)."""
    records = []
    for blocks in K.kernel_candidates("quantized_matmul"):
        sec = 1e-3 * (math.log2(blocks["block_m"])
                      + 0.5 * math.log2(blocks["block_n"]))
        records.append({"kernel": "quantized_matmul",
                        "bucket": list(bucket), "blocks": blocks,
                        "seconds": sec})
    return records


def test_learned_model_outranks_analytic_on_synthetic_trials():
    records = _synthetic_records()
    model = LearnedCostModel()
    assert model.fit(records) == len(records) >= 8
    use, lc, ac = rank_gate(model, records)
    assert use is True
    assert lc > 0.9          # near-perfect fit of a log-linear truth
    assert lc >= ac          # the asserted beats-or-ties bar


def test_search_ranks_by_learned_model_once_records_accumulate():
    append_trials(_synthetic_records())
    res = K.search_kernels(kernels=("quantized_matmul",),
                           shapes={"quantized_matmul": [(1024, 1024,
                                                         1024)]},
                           measure=_planted({"block_m": 64,
                                             "block_n": 128}),
                           fraction=0.5)
    assert res.ranked_by == "learned"
    assert res.learned_corr >= res.analytic_corr
    assert telemetry.snapshot()["gauges"][
        "autotune.learned_rank_corr"] == pytest.approx(res.learned_corr,
                                                       abs=1e-3)
    # the learned ranking (small tiles first, matching the synthetic
    # truth) put the planted optimum inside the measured half
    assert res.tuned[("quantized_matmul", (1024, 1024, 1024))] == {
        "block_m": 64, "block_n": 128}


def test_run_report_carries_kernel_trials_and_learned_reads_them_back(
        tmp_path):
    from mxnet_tpu.autotune.learned import load_telemetry_records
    K.search_kernels(kernels=("ln_residual",),
                     shapes={"ln_residual": [(4096, 1024)]},
                     measure=_planted({"block_rows": 512}), fraction=1.0)
    report_path = tmp_path / "report.jsonl"
    tt = telemetry.TrainingTelemetry(path=str(report_path), interval=1)
    tt.step(loss=1.0)
    report = tt.close()
    assert report["autotune"]["kernels"]["trials"] > 0
    assert report["autotune"]["kernel_trials"]
    # the fleet loop: JSONL report -> training records for the model
    records = load_telemetry_records(str(report_path))
    assert records and all(r["kernel"] == "ln_residual" for r in records)
    model = LearnedCostModel()
    assert model.fit(records) == len(records)


# ---------------------------------------------------------------------------
# drift-triggered online re-tune (the chaos drill)
# ---------------------------------------------------------------------------

def _dense_step(cfg):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.train import ShardedTrainStep
    mx.random.seed(3)
    net = nn.Dense(8, in_units=4)
    net.initialize()

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    return ShardedTrainStep(net, loss_fn, "adam", cfg,
                            batch_specs=cfg.batch_specs(2, 1), n_labels=1)


def test_drift_event_triggers_background_retune_and_checkpoint_swap():
    from mxnet_tpu.parallel.mesh import MeshConfig
    cfg = MeshConfig(dp=8)
    step = _dense_step(cfg)
    rs = onp.random.RandomState(5)
    x = rs.randn(16, 4).astype("float32")
    y = rs.randint(0, 8, (16,)).astype("int32")
    losses = [float(step(x, y)) for _ in range(3)]

    retuner = autotune.Retuner(
        kernels=("flash_attention",),
        shapes={"flash_attention": [(2048, 2048, 128)]},
        measure=_planted({"block_q": 512, "block_k": 256}),
        fraction=1.0).arm()
    config.set("autotune.retune_on_drift", True)
    config.set("insight.drift_window", 8)
    insight.enable()
    for _ in range(8):
        telemetry.observe("trainer.step_seconds", 0.1)
    fault.configure("insight.drift:prob=1")     # stretch every sample 3x
    for _ in range(8):
        telemetry.observe("trainer.step_seconds", 0.1)
        if insight.drift_events():
            break
    assert insight.drift_events(), "chaos drift did not fire"
    fault.configure(None)

    retuner.join(timeout=30)
    assert retuner.pending and retuner.searches == 1
    # winners are STAGED, not live: the global table is untouched until
    # the checkpoint boundary
    assert K.resolve_blocks("flash_attention", (2048, 2048, 128)) == {
        "block_q": 1024, "block_k": 512}

    n_before = step._n_step
    swapped = retuner.checkpoint(step)
    assert swapped is not step and swapped._n_step == n_before
    assert not retuner.pending and retuner.applied == 1
    assert K.resolve_blocks("flash_attention", (2048, 2048, 128)) == {
        "block_q": 512, "block_k": 256}
    assert telemetry.counters()["autotune.retunes_total"] == 1
    # the loss trajectory continues uninterrupted on the same weights
    after = [float(swapped(x, y)) for _ in range(3)]
    assert all(onp.isfinite(after))
    assert after[-1] < losses[0]
    # idle checkpoint boundaries are free no-ops
    assert retuner.checkpoint(swapped) is swapped
    retuner.disarm()


def test_retune_hook_is_a_noop_while_the_knob_is_off():
    retuner = autotune.Retuner(measure=_planted({})).arm()
    config.set("autotune.retune_on_drift", False)
    retuner._on_drift("trainer.step", {"seconds": 0.3})
    assert retuner.searches == 0 and not retuner.pending
    assert retuner.checkpoint(None) is None
    retuner.disarm()


def test_insight_drift_hooks_fan_out_and_reset_clears():
    seen = []
    insight.on_drift(lambda s, e: seen.append(s))
    insight.on_drift(lambda s, e: 1 / 0)     # broken subscriber: swallowed
    insight._record_drift("trainer.step",
                          {"seconds": 0.3, "baseline": 0.1, "ewma": 0.3})
    assert seen == ["trainer.step"]
    insight.reset()
    insight._record_drift("trainer.step",
                          {"seconds": 0.3, "baseline": 0.1, "ewma": 0.3})
    assert seen == ["trainer.step"]          # hook gone after reset


def test_rebuild_defaults_to_own_mesh_config():
    from mxnet_tpu.parallel.mesh import MeshConfig
    step = _dense_step(MeshConfig(dp=8))
    rs = onp.random.RandomState(6)
    x = rs.randn(8, 4).astype("float32")
    y = rs.randint(0, 8, (8,)).astype("int32")
    float(step(x, y))
    rebuilt = step.rebuild()
    assert rebuilt.mesh_config == step.mesh_config
    assert rebuilt._n_step == step._n_step
    assert onp.isfinite(float(rebuilt(x, y)))
