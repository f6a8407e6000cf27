"""mx.autotune: measured config search for the compiled step.

Strategy: the search loop runs against a deterministic fake-measurement
backend (same injection style as the fake-device ``memory_stats`` tests
in test_zero.py) so convergence, pruning, OOM survival and persistence
are exact assertions; a small number of real-trial tests then prove the
measured path is hermetic against the caller's params/optimizer.
"""
import json

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autotune, config, fault, telemetry
from mxnet_tpu.autotune import (
    Candidate, CostModel, ModelStats, SearchSpace, TrialOOM,
    model_fingerprint, winner_key,
)
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.train import ShardedTrainStep

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path):
    """Every test gets its own winners file; counters start clean."""
    prior = config.get("autotune.cache_dir")
    config.set("autotune.cache_dir", str(tmp_path / "autotune"))
    telemetry.reset()
    telemetry.enable()
    try:
        yield
    finally:
        config.set("autotune.cache_dir", prior)
        telemetry.reset()
        telemetry.disable()
        fault.configure(None)


def _make_net(units=6, in_units=4, seed=7):
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    return net


def _loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def _sample(n=16, in_units=4, classes=6, seed=1):
    rs = onp.random.RandomState(seed)
    return (rs.randn(n, in_units).astype("float32"),
            rs.randint(0, classes, (n,)).astype("int32"))


def _search(measure, space=None, dp=1, net=None, **kw):
    """Fake-measured search over a tiny Dense model."""
    mesh = make_mesh({"dp": dp})
    return autotune.search(
        net or _make_net(), _loss_fn, "adam", mesh, (P("dp"), P("dp")),
        _sample(), space=space or SearchSpace(batch_size=16),
        hbm_budget=None, measure=measure, **kw)


def _stats(dp=1, param_count=1000, act=1000, sample=64):
    return ModelStats(param_count=param_count, param_bytes=4 * param_count,
                      state_bytes=8 * param_count, dp=dp,
                      act_bytes_per_item=act, sample_item_bytes=sample)


# ---------------------------------------------------------------------------
# space
# ---------------------------------------------------------------------------

def test_space_grid_is_deterministic_and_contains_default():
    space = SearchSpace(batch_size=16)
    grid = space.candidates()
    assert len(grid) == len(space) == 3 * 2 * 3 * 3  # spc x ga x zero x remat
    assert grid == space.candidates()
    assert space.default_candidate() in grid
    d = space.default_candidate()
    assert (d.steps_per_call, d.grad_accum, d.zero, d.remat) == (1, 1, 0,
                                                                 False)


def test_candidate_config_roundtrips_json():
    c = Candidate(32, steps_per_call=4, grad_accum=2, zero=1, remat="dots",
                  prefetch_depth=3)
    back = Candidate.from_config(json.loads(json.dumps(c.config())))
    assert back == c and hash(back) == hash(c)


def test_precision_axis_enumerates_and_defaults():
    space = SearchSpace(batch_size=16, precision=("fp32", "int8_weights"))
    assert len(space) == 2 * 3 * 2 * 3 * 3
    precs = {c.precision for c in space.candidates()}
    assert precs == {"fp32", "int8_weights"}
    # default candidate takes the first precision — the measured baseline
    assert space.default_candidate().precision == "fp32"
    # train searches are unchanged: single-value axis by default
    assert len(SearchSpace(batch_size=16)) == 3 * 2 * 3 * 3
    with pytest.raises(mx.MXNetError):
        SearchSpace(batch_size=16, precision=())


def test_precision_roundtrips_and_loads_legacy_configs():
    c = Candidate(32, precision="int4_weights")
    back = Candidate.from_config(json.loads(json.dumps(c.config())))
    assert back == c and back.precision == "int4_weights"
    # winners persisted before the precision axis have no such key
    legacy = Candidate(32, steps_per_call=2).config()
    del legacy["precision"]
    assert Candidate.from_config(legacy).precision == "fp32"
    assert Candidate.from_config(legacy) == Candidate(32, steps_per_call=2)


def test_precision_never_pruned_by_dominance():
    """Different numeric formats have different numerics: the cost model
    may rank them (int8 cheaper) but must never analytically prune one
    in favor of another — only measured trials compare formats."""
    from mxnet_tpu.autotune.cost import PRECISION_COMPUTE_FACTOR
    model = CostModel(_stats(dp=1), hbm_budget=None)
    a = Candidate(16, prefetch_depth=0, precision="fp32")
    b = Candidate(16, prefetch_depth=0, precision="int8")
    assert model.compute_cost(b) < model.compute_cost(a)
    keep, pruned = model.plan([a, b])
    assert a in keep and b in keep and not pruned
    # factor table covers every advertised axis value
    from mxnet_tpu.autotune.space import PRECISION_VALUES
    assert set(PRECISION_VALUES) <= set(PRECISION_COMPUTE_FACTOR)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_dominance_prunes_majority_without_budget():
    """>=50% of the grid must go analytically even when no HBM budget is
    known (CPU CI) — the acceptance bar for 'pruned without compiling'."""
    space = SearchSpace(batch_size=16)
    model = CostModel(_stats(dp=4), hbm_budget=None)
    keep, pruned = model.plan(space.candidates(), space.default_candidate())
    assert len(pruned) >= len(space) * 0.5
    assert space.default_candidate() in keep
    assert all(r in ("dominated", "invalid", "hbm") for _c, r in pruned)
    # nothing lost: keep + pruned partition the grid
    assert len(keep) + len(pruned) == len(space)


def test_memory_knobs_strictly_cost_compute():
    model = CostModel(_stats(dp=4), hbm_budget=None)
    base = Candidate(16, prefetch_depth=2)
    for knob in (dict(zero=1), dict(zero=2), dict(grad_accum=2),
                 dict(remat="dots"), dict(remat=True)):
        c = Candidate(16, prefetch_depth=2, **knob)
        assert model.compute_cost(c) > model.compute_cost(base), knob
        assert model.hbm_bytes(c) <= model.hbm_bytes(base), knob


def test_hbm_budget_rejects_fat_candidates():
    """With a budget only the memory-lean configs survive; the reasons
    say which rule fired."""
    model = CostModel(_stats(dp=4, act=10_000), hbm_budget=None)
    lean = Candidate(16, zero=2, grad_accum=2, remat=True, prefetch_depth=0)
    fat = Candidate(16, prefetch_depth=2)
    budget = (model.hbm_bytes(lean) + model.hbm_bytes(fat)) // 2
    tight = CostModel(_stats(dp=4, act=10_000), hbm_budget=budget)
    assert tight.fits(lean) and not tight.fits(fat)
    space = SearchSpace(batch_size=16)
    keep, pruned = tight.plan(space.candidates(), space.default_candidate())
    reasons = {r for _c, r in pruned}
    assert "hbm" in reasons
    assert all(tight.fits(c) or c == space.default_candidate()
               for c in keep)


def test_hbm_budget_auto_reads_fake_device_stats():
    """hbm_budget='auto' goes through the same PJRT memory_stats surface
    as the memory.* gauges (fake-device pattern from test_zero.py)."""
    class _Dev:
        def __init__(self, i, limit):
            self.id = i
            self._limit = limit

        def memory_stats(self):
            return {"bytes_in_use": 10, "peak_bytes_in_use": 20,
                    "bytes_limit": self._limit}

    budget = autotune.search.__globals__["_hbm_budget"](
        [_Dev(0, 1000), _Dev(1, 800)])
    # min over devices x autotune.hbm_fraction (0.9 default)
    assert budget == int(800 * config.get("autotune.hbm_fraction"))

    class _NoStats:
        id = 2

        def memory_stats(self):
            return None

    assert autotune.search.__globals__["_hbm_budget"]([_NoStats()]) is None


def test_invalid_geometry_is_pruned():
    model = CostModel(_stats(dp=4), hbm_budget=None)
    assert model.invalid_reason(Candidate(16, grad_accum=3)) == "invalid"
    assert model.invalid_reason(Candidate(6, grad_accum=2)) == "invalid"
    assert model.invalid_reason(Candidate(16, zero=1)) is None
    solo = CostModel(_stats(dp=1), hbm_budget=None)
    assert solo.invalid_reason(Candidate(16, zero=1)) == "dominated"
    no_zero = CostModel(_stats(dp=4), hbm_budget=None, zero_ok=False)
    assert no_zero.invalid_reason(Candidate(16, zero=1)) == "invalid"


def test_max_trials_caps_keep_but_spares_default():
    space = SearchSpace(batch_size=16)
    model = CostModel(_stats(dp=4), hbm_budget=None, max_trials=2)
    keep, pruned = model.plan(space.candidates(), space.default_candidate())
    assert len(keep) == 2
    assert space.default_candidate() in keep
    assert any(r == "ranked_out" for _c, r in pruned)


# ---------------------------------------------------------------------------
# search loop (deterministic fake measurements)
# ---------------------------------------------------------------------------

def _planted(best_spc=4):
    """Measurement backend with a planted optimum on the spc axis."""
    def measure(c):
        return 1000.0 + (500.0 if c.steps_per_call == best_spc else 0.0) \
            + c.steps_per_call
    return measure


def test_search_converges_to_planted_optimum():
    res = _search(_planted(best_spc=4))
    assert res.best.candidate.steps_per_call == 4
    assert res.best.items_per_s == pytest.approx(1504.0)
    assert res.speedup is not None and res.speedup > 1.0
    assert res.default is not None and res.default.status == "ok"
    assert res.pruned_fraction >= 0.5


def test_search_prunes_before_measuring():
    measured = []

    def measure(c):
        measured.append(c)
        return 100.0

    res = _search(measure)
    assert len(measured) == len(res.trials)
    assert len(measured) + len(res.pruned) == res.n_candidates
    assert len(res.pruned) >= res.n_candidates * 0.5


def test_oom_trial_recorded_not_fatal():
    """One exploding candidate must surface as status='oom' in telemetry
    and the result — and the search still produces a winner."""
    def measure(c):
        if c.steps_per_call == 2:
            raise TrialOOM("RESOURCE_EXHAUSTED: out of memory")
        return 100.0 + c.steps_per_call

    res = _search(measure)
    by_status = {t.status for t in res.trials}
    assert "oom" in by_status and "ok" in by_status
    assert res.best is not None
    assert res.best.candidate.steps_per_call != 2
    snap = telemetry.counters(aggregate=True)
    assert snap.get("autotune.trials_oom_total", 0) >= 1
    assert res.summary()["trials_oom"] >= 1


def test_injected_fault_point_ooms_one_trial():
    """The autotune.trial_oom chaos point (MXNET_FAULT_SPEC surface) fires
    inside the trial loop and is recorded as an OOM outcome."""
    fault.configure("autotune.trial_oom:at=1,times=1")
    res = _search(lambda c: 100.0)
    assert sum(1 for t in res.trials if t.status == "oom") == 1
    assert res.best is not None


def test_generic_trial_error_does_not_kill_search():
    def measure(c):
        if c.steps_per_call == 4:
            raise ValueError("trace blew up")
        return 100.0

    res = _search(measure)
    assert any(t.status == "error" for t in res.trials)
    assert res.best is not None


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def test_winner_persists_and_second_search_runs_zero_trials():
    calls = []

    def measure(c):
        calls.append(c)
        return 100.0 + c.steps_per_call

    net = _make_net(seed=3)
    first = _search(measure, net=net)
    assert not first.reused and calls
    n_first = len(calls)
    second = _search(measure, net=net)
    assert second.reused
    assert len(second.trials) == 0 and len(calls) == n_first
    assert second.config == first.config
    assert second.best.status == "cached"
    snap = telemetry.counters(aggregate=True)
    assert snap.get("autotune.cache_hits_total", 0) == 1


def test_fingerprint_invalidates_on_model_change():
    net_a, net_b = _make_net(units=6), _make_net(units=7)
    assert model_fingerprint(net_a) != model_fingerprint(net_b)
    first = _search(_planted(), net=net_a)
    second = _search(_planted(), net=net_b)
    assert not second.reused           # different fingerprint -> new search
    assert first.key != second.key
    # both live side by side in the same winners file
    winners = autotune.load_winner(first.key), autotune.load_winner(
        second.key)
    assert all(w is not None for w in winners)


def test_force_reruns_past_a_cached_winner():
    net = _make_net(seed=5)
    _search(_planted(), net=net)
    forced = _search(_planted(), net=net, force=True)
    assert not forced.reused and forced.trials


def test_winner_key_shape():
    key = winner_key("abcd", "TPU v4", 8)
    assert key == "abcd|TPU v4|dp8"


def test_winners_file_is_valid_json_with_version():
    net = _make_net(seed=9)
    res = _search(_planted(), net=net)
    with open(res.path) as f:
        data = json.load(f)
    # schema 2 (kernel winners + trials ring); "version" kept as an alias
    assert data["schema"] == 2 and data["version"] == 2
    rec = data["winners"][res.key]
    assert rec["config"] == res.config
    assert rec["fingerprint"] == res.key.split("|")[0]


# ---------------------------------------------------------------------------
# hermetic real trials
# ---------------------------------------------------------------------------

def test_real_trials_leak_no_state_into_caller():
    """Measured trials run the real ShardedTrainStep but must not move
    the block's parameters or the caller's optimizer clock."""
    net = _make_net()
    before = {n: onp.asarray(p.data()._data).copy()
              for n, p in net.collect_params().items()}
    opt = mx.optimizer.create("adam", learning_rate=0.05)
    space = SearchSpace(batch_size=16, steps_per_call=(1, 2),
                        grad_accum=(1,), zero=(0,), remat=(False,))
    mesh = make_mesh({"dp": 4})
    res = autotune.search(net, _loss_fn, opt, mesh, (P("dp"), P("dp")),
                          _sample(), space=space, hbm_budget=None,
                          trial_seconds=0.03, force=True)
    assert res.best is not None and res.best.status == "ok"
    assert opt.num_update == 0
    after = {n: onp.asarray(p.data()._data) for n, p in
             net.collect_params().items()}
    for n in before:
        onp.testing.assert_array_equal(before[n], after[n])


def test_step_autotune_returns_tuned_step_that_trains():
    net = _make_net()
    opt = mx.optimizer.create("adam", learning_rate=0.05)
    mesh = make_mesh({"dp": 4})
    step = ShardedTrainStep(net, _loss_fn, opt, mesh,
                            (P("dp"), P("dp")), n_labels=1)
    x, y = _sample()
    first = float(step(x, y))
    space = SearchSpace(batch_size=16, steps_per_call=(1, 2),
                        grad_accum=(1,), zero=(0,), remat=(False,))
    tuned, res = autotune.tune_step(step, sample_batch=(x, y), space=space,
                                    trial_seconds=0.03, force=True)
    assert res.best is not None
    cfg = res.config
    assert tuned.steps_per_call == cfg["steps_per_call"]
    # step counter carries over; the tuned step keeps training
    assert tuned._n_step == step._n_step
    batch = (onp.resize(x, (cfg["steps_per_call"] * 16, 4)),
             onp.resize(y, (cfg["steps_per_call"] * 16,)))
    if cfg["steps_per_call"] > 1:
        batch = tuple(b.reshape((cfg["steps_per_call"], 16) + b.shape[1:])
                      for b in batch)
    loss = float(tuned(*batch))
    assert onp.isfinite(first) and onp.isfinite(loss)


def test_search_survives_all_trials_failing():
    def measure(c):
        raise TrialOOM("out of memory")

    res = _search(measure)
    assert res.best is None and res.config is None
    assert all(t.status == "oom" for t in res.trials)


# ---------------------------------------------------------------------------
# recompile accounting
# ---------------------------------------------------------------------------

def test_trial_compile_scope_restores_detector_state():
    net = _make_net()
    prior_limit = config.get("telemetry.recompile_limit")
    telemetry.note_compile(net, "warmup", 0.01)
    baseline = net.__dict__["_telemetry_compiles"]
    with autotune.trial_compile_scope(net, limit=500):
        assert config.get("telemetry.recompile_limit") == 500
        for _ in range(5):
            telemetry.note_compile(net, "trial", 0.01)
        assert net.__dict__["_telemetry_compiles"] == baseline + 5
    assert net.__dict__["_telemetry_compiles"] == baseline
    assert not net.__dict__["_telemetry_recompile_warned"]
    assert config.get("telemetry.recompile_limit") == prior_limit


def test_search_emits_no_recompile_warnings(recwarn):
    """A full search's warmup compiles stay under the trial-scoped limit:
    zero RecompileWarning during or after."""
    net = _make_net()
    space = SearchSpace(batch_size=16, steps_per_call=(1, 2),
                        grad_accum=(1,), zero=(0,), remat=(False,))
    mesh = make_mesh({"dp": 4})
    autotune.search(net, _loss_fn, "adam", mesh, (P("dp"), P("dp")),
                    _sample(), space=space, hbm_budget=None,
                    trial_seconds=0.03, force=True, persist=False)
    assert not [w for w in recwarn.list
                if issubclass(w.category, telemetry.RecompileWarning)]


# ---------------------------------------------------------------------------
# surfaces: telemetry plane, estimator, bench
# ---------------------------------------------------------------------------

def test_run_report_carries_autotune_plane(tmp_path):
    _search(_planted())
    rep = telemetry.TrainingTelemetry(path=None)
    report = rep.close()
    assert "autotune" in report
    assert report["autotune"]["best"]["config"]["steps_per_call"] == 4
    counters = report["metrics"]["counters"]
    assert any(k.startswith("autotune.trials_total") for k in counters)


def test_estimator_fit_autotune_runs_search_before_loop():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.contrib import estimator as est
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset

    mx.random.seed(11)
    x, y = _sample(n=32, in_units=4, classes=2)
    loader = DataLoader(ArrayDataset(x, y.astype("f")), batch_size=8,
                        num_workers=0)
    net = nn.Sequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    e = est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                      trainer=gluon.Trainer(net.collect_params(), "adam",
                                            {"learning_rate": 0.05}))
    e.fit(loader, epochs=1,
          autotune=dict(measure=_planted(), persist=False))
    res = e.autotune_result
    assert res is not None and res.best is not None
    assert res.best.candidate.steps_per_call == 4


def test_bench_rows_carry_full_config_dict():
    import bench
    cfg = bench._config_dict(32, 4)
    assert cfg == {"batch": 32, "steps_per_call": 4, "zero": 0,
                   "grad_accum": 1, "remat": False, "prefetch_depth": None}


def test_bench_accepts_autotune_winners_file(tmp_path):
    """--config maps winners.json onto extra tuned train-family grid
    points (one per distinct winner config, per family)."""
    import bench
    winners = {"version": 1, "winners": {
        "fp|cpu|dp1": {"config": Candidate(16, steps_per_call=2).config(),
                       "items_per_s": 10.0},
        # duplicate config under another key must not double the grid
        "fp2|cpu|dp1": {"config": Candidate(16, steps_per_call=2).config()},
    }}
    path = tmp_path / "winners.json"
    path.write_text(json.dumps(winners))
    entries = bench._tuned_entries(str(path))
    assert len(entries) == len(bench._TRAIN_FAMILIES)
    for fn, kwargs in entries:
        assert kwargs["bs"] == 16 and kwargs["k_steps"] == 2
        assert kwargs["_tuned"]["steps_per_call"] == 2

    # plain {workload: config} mapping addresses one family directly
    plain = tmp_path / "plain.json"
    plain.write_text(json.dumps(
        {"gpt_train": Candidate(8, steps_per_call=4).config()}))
    entries = bench._tuned_entries(str(plain))
    assert len(entries) == 1
    assert entries[0][0] is bench.bench_gpt_train
