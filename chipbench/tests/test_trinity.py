"""The cell ``trinity-train-8k``: a rehearsal end to end on the CPU (the
tiny stand-in against the plain reference — plain, traced, with the timed
path broken underneath and under the lower-precision control), its FLOP
functions against hand numbers, and its real-size step compiled for a
described v5e.  ``test_harness.py`` names its cells in a list and is
not edited, so the new cell's rehearsals live here."""
import json

import pytest

from test_compile_v5e import (BYTES_LIMIT, _report, _train_compile,  # noqa
                              as_v5e, topo)
from test_harness import (DEVICE_KEYS, E2E_KEYS, check_rows, last_line)

CELL = "trinity-train-8k"


def test_compiles_for_a_v5e_and_fits(topo, as_v5e):
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    total = _report(CELL, compiled, resident)
    # forward, dK/dV and dQ kernels in every layer, bf16 operands
    assert text.count("tpu_custom_call") >= 3 * cfg["n_layer"]
    assert "bf16" in text
    assert total < BYTES_LIMIT


def test_rehearsal_is_correct_and_the_line_is_strict(capsys):
    bench = json.load(open("BENCHMARK.json"))
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "3000000019", "--seconds", "3"])
    assert line.pop("rehearsal") is True
    assert set(line) == E2E_KEYS
    assert line["correct"] is True, check_rows(out)
    assert set(line["device"]) == DEVICE_KEYS
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]}
    assert set(line["metrics"]) == want == {"train_mfu", "setup_s"}
    # the counts the check compared, as the reference prints them
    counts = json.loads(next(l for l in out if l.startswith("# counts "))
                        [len("# counts "):])
    assert {n.split("[")[0] for n in counts} == {"moe.load",
                                                 "moe.rows_over"}
    assert all(v == 0 for n, v in counts.items() if "rows_over" in n)
    assert all(v < 0.05 for n, v in counts.items() if "load" in n)


def test_traced_line(capsys):
    bench = json.load(open("BENCHMARK.json"))
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "11", "--seconds", "3", "--trace", "1"])
    line.pop("rehearsal")
    assert set(line) == E2E_KEYS | {"breakdown"}
    assert line["correct"] is True, check_rows(out)
    known = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    assert line["metrics"] and set(line["metrics"]) <= known
    # the CPU has no device plane to read kernels from; the counter's
    # reader has its counts
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1
    assert "flash_roofline.train" not in known


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import jax
    from mxnet_tpu.parallel import ShardedTrainStep
    real = ShardedTrainStep.__call__

    def frozen(self, *batch):
        keep = jax.tree_util.tree_map(
            lambda a: a.copy(),
            (self.trainable, self.aux, self.states, self.extra))
        loss = real(self, *batch)
        self.trainable, self.aux, self.states, self.extra = keep
        return loss

    monkeypatch.setattr(ShardedTrainStep, "__call__", frozen)
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "5", "--seconds", "2"])
    assert line["correct"] is False
    assert check_rows(out)["change_norm_gap_worst_live_leaf"]["holds"] \
        is False


def test_an_expert_layer_that_drops_rows_is_not_correct(capsys, monkeypatch):
    """A bound under the rows the data sends: the layer leaves
    assignments out and counts them, and the check refuses the count
    whatever the loss says."""
    import run
    real = run.resolve

    def small_bound(*a, **k):
        entry, cell, cfg, traffic = real(*a, **k)
        return entry, cell, dict(cfg, rows_bound=16), traffic

    monkeypatch.setattr(run, "resolve", small_bound)
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "9", "--seconds", "2"])
    assert line["correct"] is False
    row = check_rows(out)["change_norm_gap_worst_live_leaf"]
    assert row["holds"] is False and row["where"].startswith("moe.rows_over")
    assert row["value"] >= 1


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "7", "--seconds", "2", "--control"])
    assert line["control"] is True
    assert line["correct"] is False, check_rows(out)


def test_needed_flops_against_hand_numbers():
    """ISSUE 27's arithmetic: 712.8 MFLOP a token forward at 8192."""
    import run
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "afmoe")
    e, hq, hk = 2048, 32 * 128, 4 * 128
    proj = 2 * e * (3 * hq + 2 * hk)
    assert proj == 54_525_952
    window, full = 4 * hq * 1792.125, 4 * hq * 4096
    sparse = 2 * e * 128 + 6 * e * 1024 + 0.5 * 6 * e * 1024
    want = 5 * proj + 4 * window + full + 6 * e * 6144 + 4 * sparse \
        + 2 * e * 25024
    assert flops.forward_flops_per_token(cfg, 8192) == want == 712_777_728
    assert flops.train_flops_per_token(cfg, 8192) == 3 * want
    # the attention core is 26 %, the five projections 38 % of it
    assert round(100 * (4 * window + full) / want) == 26
    assert round(100 * 5 * proj / want) == 38
    # the kernels' needed work: 6 products of 2*d a pair and head
    pairs = 8192 * (4 * 1792.125 + 4096)
    assert flops.flash_train_flops(cfg, 1, 8192) == 32 * pairs * 12 * 128
    assert flops.flash_train_bytes(cfg, 1, 8192) \
        == 5 * 6 * (32 + 4) * 8192 * 128 * 2
    assert flops.experts_train_flops(cfg, 4096) == 18 * 4096 * e * 1024


def test_the_experts_roofline_counts_the_expected_rows():
    """``moe_experts_roofline.train``: the four expert layers' grouped
    products at 0.5 rows a token (4096 a layer and update, what
    ``train_mfu`` counts), over the ``ragged-dot`` kernels' time alone;
    nothing where the trace has no such kernel."""
    import run
    from peaks import PEAKS
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "afmoe")
    reader = run.load_module("layer_metrics", "moe_experts_roofline.train")
    peak = PEAKS["TPU v5 lite"]

    def obs(ops):
        return {"ctx": {"flops": flops, "cfg": cfg, "chips": 1,
                        "peak": peak},
                "sequences": 1, "seq_len": 8192, "_update_ops": (ops, 2)}

    ms = 1e6        # an operation's stamps are in ns
    ops = [{"mosaic": True, "name": "ragged-dot-none.7", "start": 0,
            "end": 12 * ms},
           {"mosaic": True, "name": "ragged-dot-metadata.2", "start": 0,
            "end": 4 * ms},
           {"mosaic": True, "name": "mx_flash_fwd.3", "start": 0,
            "end": 50 * ms},
           {"mosaic": False, "name": "fusion.9", "start": 0, "end": 9 * ms}]
    needed_s = 4 * 18 * 4096 * 2048 * 1024 / peak["bf16_flops"]
    assert needed_s > 4 * flops.experts_train_bytes(cfg, 4096) \
        / peak["hbm_bytes_per_s"]
    assert reader.read(obs(ops)) == pytest.approx(
        100 * needed_s / 8e-3, rel=1e-9)
    assert reader.read(obs(ops[2:])) is None
    assert reader.read(obs(None)) is None
