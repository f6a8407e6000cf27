"""The serve cells on the CPU (tiny stand-ins): the traffic generator, the
needed-work functions against hand counts, both cells end to end against
the plain reference, set-up cut with no rest, and — with the int8 control
switched on, or a token altered where it is produced — ``correct`` coming
out false; the serve readers on a small trace recorded on the chip."""
import json
import os

import pytest

import run as harness
from test_harness import DEVICE_KEYS, E2E_KEYS, check_rows, last_line

CELLS = ["gpt2m-serve-steady", "gpt2m-serve-flood"]
HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {"n_embd": 256, "n_inner": 1024, "n_layer": 4, "vocab_size": 2048}


def _module(kind, name):
    return harness.load_module(kind, name)


# ---- traffic -------------------------------------------------------------

def test_the_traffic_is_drawn_from_the_seed():
    driver = _module("drivers", "serve_requests")
    mix = harness.load_json(harness.HERE, "traffic",
                            "chat-poisson-steady.json")
    rate = mix["arrivals"]["rate_per_s"]
    a = driver.schedule(mix, 3, 50.0, 50257)
    b = driver.schedule(mix, 2 ** 31 + 77, 50.0, 50257)   # past 32 bits
    for plan in (a, b):
        due = [d for d, _, _ in plan]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 50.0
        # a Poisson count: within five standard deviations of its mean
        assert abs(len(plan) - rate * 50.0) < 5 * (rate * 50.0) ** 0.5
        for _, prompt, budget in plan:
            assert 32 <= len(prompt) <= 896 and 1 <= budget <= 256
            assert len(prompt) + budget <= 1024
    # another seed, other requests; the same seed, the same
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    assert a[0][0] != b[0][0] and a[0][1].tolist() != b[0][1].tolist()
    again = driver.schedule(mix, 3, 50.0, 50257)
    assert [(d, p.tolist(), g) for d, p, g in a] \
        == [(d, p.tolist(), g) for d, p, g in again]


def test_the_gaps_are_exponential_and_the_lengths_lognormal():
    """Nothing is dealt or stratified: over a long horizon the gaps have
    the exponential's mean and its spread (a coefficient of variation
    of 1, and neighbours that do not lean on each other), the lengths
    the lognormals' medians."""
    import numpy as onp
    driver = _module("drivers", "serve_requests")
    mix = harness.load_json(harness.HERE, "traffic",
                            "chat-poisson-steady.json")
    rate = mix["arrivals"]["rate_per_s"]
    plan = driver.schedule(mix, 11, 3000.0, 64)
    gaps = onp.diff([d for d, _, _ in plan])
    assert abs(gaps.mean() * rate - 1) < 0.03
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05
    # sums of 16 neighbours spread as independent draws do (1 / 4)
    sums = gaps[:len(gaps) // 16 * 16].reshape(-1, 16).sum(1)
    assert abs(sums.std() / sums.mean() - 0.25) < 0.03
    prompts = onp.array([len(p) for _, p, _ in plan])
    budgets = onp.array([g for _, _, g in plan])
    assert abs(onp.median(prompts) / mix["prompt_tokens"]["median"] - 1) < 0.04
    assert abs(onp.median(budgets) / mix["output_tokens"]["median"] - 1) < 0.06
    assert prompts.min() >= 32 and prompts.max() == 896
    assert abs(onp.corrcoef(prompts[:-1], prompts[1:])[0, 1]) < 0.03


# ---- needed work ---------------------------------------------------------

def test_serve_flops_against_hand_counts():
    fl = _module("flops", "gpt2.serve")
    e, f, layers, vocab = 256, 1024, 4, 2048
    block = 8 * e * e + 4 * e * f            # 4 projections, 2 MLP products
    head = 2 * e * vocab
    # a prompt of 3 tokens: keys seen 1 + 2 + 3, two products of 2*e each
    assert fl.prefill_flops(TINY, 3) == layers * (3 * block + 4 * e * 6) + head
    # a decoded token that attends 10 keys
    assert fl.decode_flops(TINY, 10) == layers * (block + 4 * e * 10) + head
    # tokens 0..3 of a request whose prompt has 5: the prefill, then three
    # decoded tokens attending 6, 7 and 8 keys
    assert fl.request_flops(TINY, 5, 0, 4) == fl.prefill_flops(TINY, 5) \
        + sum(fl.decode_flops(TINY, c) for c in (6, 7, 8))
    assert fl.request_flops(TINY, 5, 2, 4) \
        == fl.decode_flops(TINY, 7) + fl.decode_flops(TINY, 8)
    assert fl.request_flops(TINY, 5, 3, 3) == 0


def test_serve_bytes_against_hand_counts():
    fl = _module("flops", "gpt2.serve")
    e, f, layers, vocab = 256, 1024, 4, 2048
    block = 4 * e * e + 2 * e * f + 4 * e + 4 * e + f + e
    assert fl.weight_bytes(TINY, 2) == 2 * (layers * block + vocab * e + 2 * e)
    assert fl.cache_row_bytes(TINY, 2) == layers * 2 * e * 2
    # two live slots at 10 and 30 rows: 40 rows read, 2 written
    assert fl.decode_step_bytes(TINY, [10, 30], 2, 2) \
        == fl.weight_bytes(TINY, 2) + 42 * fl.cache_row_bytes(TINY, 2)
    # gpt2-medium in bf16: the 0.71 GB the issue sizes the cell with
    cfg = harness.load_json(harness.ROOT, "chipbench/configs/gpt2-medium.json")
    assert 0.70e9 < fl.weight_bytes(cfg, 2) < 0.72e9
    assert fl.cache_row_bytes(cfg, 2) == 98304


# ---- the cells, end to end ------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_the_line_is_strict(cell, capsys):
    bench = json.load(open("BENCHMARK.json"))
    line, out = last_line(capsys, ["--workload", cell, "--tiny", "--seed",
                                   "3000000019", "--seconds", "3"])
    assert line.pop("rehearsal") is True
    assert set(line) == E2E_KEYS
    assert line["correct"] is True, check_rows(out)
    assert set(line["device"]) == DEVICE_KEYS
    assert line["attempted"] > 50 and line["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    rows = check_rows(out)
    assert set(rows) == {"cache_projected_err_worst_layer",
                         "cache_plain_err_worst_layer",
                         "served_logit_gap_widest", "post_warmup_compiles",
                         "programs_compiled_in_window"}
    # enough rows that the projection is one: several times the width
    info = json.loads(next(l for l in out if l.startswith("# info "))[7:])
    assert info["checked"]["cache_rows"] > 4 * 257


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_and_set_up_cut_with_no_rest(cell, capsys):
    bench = json.load(open("BENCHMARK.json"))
    line, out = last_line(capsys, ["--workload", cell, "--tiny", "--seed",
                                   "11", "--seconds", "3", "--trace", "1"])
    line.pop("rehearsal")
    assert set(line) == E2E_KEYS | {"breakdown"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    known = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or cell in m["workloads"]}
    got = line["metrics"]
    assert set(got) <= known
    # every set-up metric the benchmark has reads on a serve cell too
    setup = {m["name"] for m in bench["per_layer"]
             if m["moves"] == "setup_s" and "workloads" not in m}
    assert setup <= set(got)
    info = json.loads(next(l for l in out if l.startswith("# info "))[7:])
    pieces = [n for n in setup if n.endswith("_s.setup")
              and n not in ("trace_s.setup", "lower_s.setup",
                            "cache_load_s.setup")]
    assert len(pieces) == 6
    assert abs(sum(got[n]["value"] for n in pieces) - info["setup_s"]) < 1e-6
    # the host-side serve readers read on the CPU too (the device-side
    # ones need a TPU trace: test_the_readers_on_a_recorded_trace)
    tail = "serve" if cell.endswith("flood") else "tpot"
    first = "serve" if cell.endswith("flood") else "ttft"
    for name in (f"batch_occupancy.{tail}", f"pad_share.{first}",
                 f"host_step_ms.{tail}", f"queue_depth.{first}",
                 f"device_idle.{first}", f"step_mfu.{tail}",
                 f"hbm_live_gb.{tail}"):
        assert got[name]["value"] >= 0
    assert 0 < got[f"batch_occupancy.{tail}"]["value"] <= 100
    assert 0 < got[f"pad_share.{first}"]["value"] < 100
    if tail == "tpot":       # the steady cell's own: the scheduler's tails
        for name in ("queue_ms_p95.ttft", "first_token_lag_ms.ttft",
                     "submit_late_ms_p95.ttft"):
            assert got[name]["value"] >= 0


def test_the_int8_control_is_not_correct(capsys):
    """``int8_weights`` as the cell's file gives it; the chip runs are in
    PERF.md section 2."""
    line, out = last_line(capsys, ["--workload", "gpt2m-serve-steady",
                                   "--tiny", "--seed", "7", "--seconds", "3",
                                   "--control"])
    assert line["control"] is True
    assert line["correct"] is False, check_rows(out)
    rows = check_rows(out)
    assert rows["cache_projected_err_worst_layer"]["holds"] is False
    assert rows["cache_projected_err_worst_layer"]["value"] \
        > 1.5 * rows["cache_projected_err_worst_layer"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    """The engine's sampler hands out the second-best token on every
    fifth id: what it then caches is true to what it said (the cache row
    holds), what it said is not the model's choice (the token row
    refuses)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serve import ServeEngine

    def second_best(self, logits, key):
        _, ids = jax.lax.top_k(logits.astype(jnp.float32), 2)
        return jnp.where(ids[..., 0] % 5 == 0, ids[..., 1],
                         ids[..., 0]).astype(jnp.int32)

    monkeypatch.setattr(ServeEngine, "_sample", second_best)
    line, out = last_line(capsys, ["--workload", "gpt2m-serve-flood",
                                   "--tiny", "--seed", "5", "--seconds", "3"])
    assert line["correct"] is False
    rows = check_rows(out)
    assert rows["served_logit_gap_widest"]["holds"] is False
    assert rows["cache_projected_err_worst_layer"]["holds"] is True


def test_stale_rows_in_the_cache_are_not_correct(capsys, monkeypatch):
    """One row in sixteen of what a slot caches is the row before it (a
    write that went one place off): nothing a weight does, so the
    projection hardly sees it, and the plain error refuses it."""
    import types
    import jax.numpy as jnp
    real_load = harness.load_module

    def load(kind, name):
        mod = real_load(kind, name)
        if (kind, name) != ("families", "gpt2.serve"):
            return mod

        def stale(eng, slot, size):
            k, v = mod.cache_rows(eng, slot, size)
            at = (jnp.arange(size) % 16 == 5)[None, :, None]
            return tuple(jnp.where(at, jnp.roll(a, 1, axis=1), a)
                         for a in (k, v))
        return types.SimpleNamespace(cache_rows=stale)

    monkeypatch.setattr(harness, "load_module", load)
    line, out = last_line(capsys, ["--workload", "gpt2m-serve-flood",
                                   "--tiny", "--seed", "9", "--seconds", "3"])
    assert line["correct"] is False
    rows = check_rows(out)
    assert rows["cache_plain_err_worst_layer"]["holds"] is False
    assert rows["cache_plain_err_worst_layer"]["value"] \
        > 5 * rows["cache_plain_err_worst_layer"]["limit"]
    assert rows["served_logit_gap_widest"]["holds"] is True


def test_the_engine_s_own_accessor_comes_first():
    """A program PR that lays the cache out anew (ROADMAP M2) gives the
    engine ``cache_rows(slot, size)``; the check then reads through it
    and never looks at ``_cache``."""
    import types
    family = _module("families", "gpt2.serve")
    asked = []
    eng = types.SimpleNamespace(
        cache_rows=lambda slot, size: asked.append((slot, size)) or "rows")
    assert family.cache_rows(eng, 3, 128) == "rows" and asked == [(3, 128)]


def test_no_tpu_no_result_line(capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "gpt2m-serve-steady", "--seed", "1"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_the_sweep_rehearses(tmp_path, capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_dev_sweep", os.path.join(harness.HERE, "dev", "sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    out = tmp_path / "sweep.json"
    sweep.main(["--workload", "gpt2m-serve-steady", "--tiny", "--rates",
                "20,400", "--seconds", "1.5", "--out", str(out)])
    table = json.load(open(out))
    assert table["rehearsal"] is True and len(table["rows"]) == 2
    slow, fast = table["rows"]
    assert slow["failed"] == fast["failed"] == 0
    assert fast["queued_at_close"] > slow["queued_at_close"]
    assert fast["ttft_ms_p95"] > slow["ttft_ms_p95"]
    assert slow["post_warmup_compiles"] == 0


# ---- the readers, on a trace recorded on the chip -------------------------

def test_the_readers_on_a_recorded_trace():
    """``dev/record_serve_trace.py`` ran the tiny flood cell traced on a
    v5e and kept the trace (less its ``/host:metadata`` plane, the
    programs' HLO, which no reader opens), what the driver observed and
    every value the run printed: the reduction gives the same numbers
    again, the device-side ones among them."""
    import peaks
    import xplane
    data = os.path.join(HERE, "data")
    obs = json.load(open(os.path.join(data, "serve_obs.json")))
    expected = obs.pop("expected")
    path = os.path.join(data, "serve.xplane.pb")
    obs.update(
        xplane=path, device_trace=xplane.reduce(path, 1),
        serve_flops=_module("flops", "gpt2.serve"),
        ctx={"cfg": obs.pop("cfg"), "chips": 1,
             "peak": peaks.peaks(obs.pop("device_kind"))})
    for name in ("decode_step_ms.serve", "decode_roofline.serve",
                 "prefill_ms.serve", "step_mfu.serve",
                 "batch_occupancy.serve", "device_idle.serve"):
        assert name in expected, sorted(expected)
    for name, value in expected.items():
        got = _module("layer_metrics", name).read(obs)
        assert got == pytest.approx(value, rel=1e-9), name
    # what is in use: the weights and the rows the live slots held, by hand
    # (the recording is older than this reader)
    fl, cfg = obs["serve_flops"], obs["ctx"]["cfg"]
    lo, hi = obs["serve_window"]
    rows = [s["contexts"] for s in obs["steps"]
            if s["live"] and lo <= s["t0"] <= hi]
    assert _module("layer_metrics", "hbm_live_gb.serve").read(obs) \
        == pytest.approx((fl.weight_bytes(cfg, 2) + sum(rows) / len(rows)
                          * fl.cache_row_bytes(cfg, 2)) / 1e9, rel=1e-12)
    # shares of a roofline or of the peak stay shares
    for name in ("decode_roofline.serve", "step_mfu.serve"):
        assert 0 < expected[name] < 100
    # the decode program is found by its name, once a step
    import serve_trace
    steps = serve_trace.traced_steps(obs)
    found = len(serve_trace.modules(obs, serve_trace.DECODE))
    assert steps and abs(found - len(steps)) <= 2
