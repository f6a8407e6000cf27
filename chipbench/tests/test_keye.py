"""The cell ``keye-train-8k``: a rehearsal end to end on the CPU (the
tiny stand-in against the plain reference — plain, traced, with the
selection broken underneath and under the lower-precision control), its
FLOP functions against hand numbers, its readers on made-up
observations, and its real-size step compiled for a described v5e.
``test_harness.py`` names its cells in a list and is not edited, so the
new cell's rehearsals live here."""
import json
import re

import pytest

from test_compile_v5e import (BYTES_LIMIT, _report, _train_compile,  # noqa
                              as_v5e, topo)
from test_harness import (DEVICE_KEYS, E2E_KEYS, check_rows, last_line)

CELL = "keye-train-8k"
COUNTS = {"moe.load", "moe.rows_over", "dsa.pairs", "dsa.grid"}


def _counts(out):
    return json.loads(next(l for l in out if l.startswith("# counts "))
                      [len("# counts "):])


def test_compiles_for_a_v5e_and_fits(topo, as_v5e):
    """Memory of the real-size step (PERF.md section 4 quotes the
    printed figures), and that neither the indexer's per-head products
    nor the alignment's per-head probabilities exist whole: no buffer of
    the compiled step has a heads x seq x seq shape."""
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    total = _report(CELL, compiled, resident)
    # forward, dK/dV and dQ kernels in every layer, bf16 operands
    assert text.count("tpu_custom_call") >= 3 * cfg["n_layer"]
    assert "bf16" in text
    assert total < BYTES_LIMIT
    hlo = compiled.as_text()
    seq, sizes = 8192, {}
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]+)\]", hlo):
        shape = tuple(int(d) for d in dims.split(","))
        size = 1
        for d in shape:
            size *= d
        sizes[(dtype, shape)] = size
    largest = sorted(sizes, key=sizes.get, reverse=True)[:8]
    print(f"[{CELL}] largest buffers: "
          + ", ".join(f"{d}{list(sh)}" for d, sh in largest))
    # the selection (s8) and the scores' ordered keys (u32) are
    # (seq, seq); the scores themselves come in query blocks; the
    # largest array is the logits, 2.3 x seq^2.  A (16, seq, seq) would
    # be 16 x seq^2
    assert ("s8", (1, seq, seq)) in sizes and ("u32", (1, seq, seq)) in sizes
    assert max(sizes.values()) < 4 * seq * seq, largest


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
    counts = _counts(out)
    assert {n.split("[")[0] for n in counts} == COUNTS
    assert all(v == 0 for n, v in counts.items()
               if "rows_over" in n or "pairs" in n)
    assert all(v < 0.05 for n, v in counts.items()
               if "load" in n or "grid" in n)


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
    # the CPU has no device plane to read scopes or kernels from; the
    # counters' readers have their counts: 2 x 64 tokens, topk 24
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1
    pairs = 2 * (24 * 25 // 2 + 40 * 24)
    assert line["metrics"]["dsa_selected_share.train"]["value"] \
        == pytest.approx(100 * pairs / (2 * 64 * 65 / 2))
    assert "flash_roofline.train" not in known


def _wrong_selection(monkeypatch, how):
    """``ops.sparse_index.select_topk`` replaced underneath the timed
    path by a selection that is dense, a window of ``topk``, or takes
    ``topk + 1`` keys."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import sparse_index
    real = sparse_index.select_topk

    def wrong(scores, topk):
        s = scores.shape[-1]
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(s)[None, :]
        if how == "dense":
            chosen = cols <= rows
        elif how == "window":
            chosen = (cols <= rows) & (rows - cols < topk)
        else:
            return real(scores, topk + 1)
        return jnp.broadcast_to(chosen, scores.shape).astype(jnp.int8)

    monkeypatch.setattr(sparse_index, "select_topk", wrong)


@pytest.mark.parametrize("how", ["dense", "window", "one-more"])
def test_a_wrong_selection_is_not_correct(capsys, monkeypatch, how):
    """Dense attention, a window of ``topk`` keys, ``topk`` off by one:
    the check refuses each by the selection's counts, whatever the loss
    says (a window selects exactly as many pairs as the indexer, and is
    caught by where they lie)."""
    _wrong_selection(monkeypatch, how)
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "9", "--seconds", "2"])
    assert line["correct"] is False
    row = check_rows(out)["change_norm_gap_worst_live_leaf"]
    assert row["holds"] is False
    counts = _counts(out)
    if how == "window":
        assert all(v == 0 for n, v in counts.items() if "pairs" in n)
        assert min(v for n, v in counts.items() if "grid" in n) > 0.1
    else:
        assert row["where"].startswith("dsa.pairs") and row["value"] >= 1


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "7", "--seconds", "2", "--control"])
    assert line["control"] is True
    assert line["correct"] is False, check_rows(out)


def test_needed_flops_against_hand_numbers():
    """ISSUE 32's arithmetic: 437.7 MFLOP a token forward at 8192; trained,
    three times that less the input gradient the indexer's projections
    do not have (their input is detached): 1.295 GFLOP, not ISSUE's
    1.313."""
    import run
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "keye")
    family = run.load_module("families", "keye")
    e, hq, hk = 2048, 32 * 128, 4 * 128
    proj = 2 * e * (2 * hq + 2 * hk)
    assert proj == 37_748_736
    index_proj = 2 * e * (16 * 64 + 64 + 16)
    assert index_proj == 4_521_984
    index_scores = 2 * 16 * 64 * 4096.5
    assert index_scores == 8_389_632
    core = 4 * hq * 1792.125
    assert core == 29_362_176
    sparse = 2 * e * 128 + 6 * e * 768 * 1.0
    assert sparse == 9_961_472
    head = 2 * e * 18992
    assert head == 77_791_232
    layer = proj + index_proj + index_scores + core + sparse
    want = 4 * layer + head
    assert flops.forward_flops_per_token(cfg, 8192) == want == 437_727_232
    assert flops.train_flops_per_token(cfg, 8192) \
        == 3 * want - 4 * index_proj == 1_295_093_760
    # indexer + selected core 39 %, projections 34 %, head 18 %, experts 9 %
    assert round(100 * 4 * (index_proj + index_scores + core) / want) == 39
    assert round(100 * 4 * proj / want) == 34
    assert round(100 * head / want) == 18
    assert round(100 * 4 * sparse / want) == 9
    assert flops.keys_per_query(8192, 2048) == 1792.125
    assert flops.selected_pairs(8192, 2048) == 14_681_088
    assert flops.expected_rows_per_token(cfg) == 1.0
    assert family.n_params(cfg) == cfg["parameters"] == 465_391_104
    # the kernels' needed work: 6 products of 2*d a selected pair and head
    assert flops.flash_train_flops(cfg, 1, 8192) \
        == 32 * 4 * 14_681_088 * 12 * 128
    assert flops.flash_train_bytes(cfg, 1, 8192) \
        == 4 * (6 * (32 + 4) * 8192 * 128 * 2 + 3 * 8192 * 4096.5)
    assert flops.index_train_flops(cfg, 1, 8192) \
        == 4 * 8192 * (2 * index_proj + 3 * index_scores)
    assert flops.index_train_bytes(cfg, 1, 8192) == 4 * (
        2 * (2 * e * 1104 + 8192 * (2 * e + 3 * 1104))
        + 8 * 8192 * 4096.5)
    assert flops.experts_train_flops(cfg, 8192) == 18 * 8192 * e * 768


def test_the_new_readers_on_made_up_observations():
    """The three scope times and the indexer's roofline share from a
    hand-made list of operations; the selected share from hand-made
    counts; nothing where there is nothing to read."""
    import run
    from peaks import PEAKS
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "keye")
    peak = PEAKS["TPU v5 lite"]
    ms = 1e6
    fwd, bwd = "jit(step)/jvp(mx.fwd)/", "jit(step)/transpose(jvp(mx.fwd))/"
    ops = [
        {"op_name": fwd + "mx.dsa.index/dot_general", "end": 10 * ms},
        {"op_name": bwd + "mx.dsa.index/checkpoint/dot_general",
         "end": 30 * ms},
        {"op_name": fwd + "mx.dsa.select/while/reduce_sum", "end": 8 * ms},
        {"op_name": fwd + "mx.dsa.align/exp", "end": 6 * ms},
        {"op_name": bwd + "mx.dsa.align/mul", "end": 2 * ms},
        {"op_name": fwd + "mx.attn/pallas_call", "end": 50 * ms},
    ]
    ops = [dict(o, start=0, collective=False, mosaic=False, name="f")
           for o in ops]

    def obs(ops, family=None):
        return {"ctx": {"flops": flops, "cfg": cfg, "chips": 1,
                        "peak": peak, "family": family},
                "sequences": 1, "seq_len": 8192, "_update_ops": (ops, 2)}

    def read(metric, o):
        return run.load_module("layer_metrics", metric).read(o)

    assert read("dsa_index_ms.train", obs(ops)) == 20.0
    assert read("dsa_select_ms.train", obs(ops)) == 4.0
    assert read("dsa_align_ms.train", obs(ops)) == 4.0
    needed_s = max(
        flops.index_train_flops(cfg, 1, 8192) / peak["bf16_flops"],
        flops.index_train_bytes(cfg, 1, 8192) / peak["hbm_bytes_per_s"])
    assert read("dsa_index_roofline.train", obs(ops)) == pytest.approx(
        100 * needed_s / 20e-3, rel=1e-9)
    for metric in ("dsa_index_ms.train", "dsa_select_ms.train",
                   "dsa_align_ms.train", "dsa_index_roofline.train"):
        assert read(metric, obs(ops[-1:])) is None
        assert read(metric, obs(None)) is None

    class Family:
        last_counts = {"dsa.pairs": [14_681_088] * 4}

    assert read("dsa_selected_share.train", obs(ops, Family)) \
        == pytest.approx(43.748, abs=1e-3)
    assert read("dsa_selected_share.train", obs(ops, object())) is None
