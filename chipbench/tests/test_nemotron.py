"""The cell ``nemotron-train-8k``: a rehearsal end to end on the CPU (the
tiny stand-in against the plain reference — plain, traced, with the scan
broken underneath and under the lower-precision control), its FLOP
functions against hand numbers, its readers on made-up observations, and
its real-size step compiled for a described v5e.  ``test_harness.py``
names its cells in a list and is not edited, so the new cell's
rehearsals live here."""
import json
import re

import pytest

from test_compile_v5e import (BYTES_LIMIT, _report, _train_compile,  # noqa
                              as_v5e, topo)
from test_harness import (DEVICE_KEYS, E2E_KEYS, check_rows, last_line)

CELL = "nemotron-train-8k"


def test_compiles_for_a_v5e_and_fits(topo, as_v5e):
    """Memory of the real-size step (PERF.md section 4 quotes the
    printed figures), and that the scan's decays exist a chunk wide
    only: no buffer of the compiled step has a heads x seq x seq
    shape."""
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    total = _report(CELL, compiled, resident)
    # forward, dK/dV and dQ kernels in the one attention layer
    assert text.count("tpu_custom_call") >= 3
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
    # the decays inside a chunk are (heads, seq, chunk) = seq^2 elements
    # at 64 heads x 128; a (heads, seq, seq) would be 64 x seq^2
    assert max(sizes.values()) <= 2 * seq * seq, largest


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
    assert {"ssm_ms.train", "ssm_scan_ms.train", "ssm_conv_ms.train",
            "ssm_scan_roofline.train",
            "moe_experts_roofline.train"} <= known
    # the CPU has no device plane to read scopes from; the counter's
    # reader has its counts
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1
    assert "flash_roofline.train" not in known


@pytest.mark.parametrize("how", ["no-decay", "no-carry"])
def test_a_scan_that_forgets_is_not_correct(capsys, monkeypatch, how):
    """The scan replaced underneath the timed path: one that never
    decays its state (``A = 0``), and one that carries no state from
    chunk to chunk (every chunk starts from zero)."""
    from mxnet_tpu.ops import ssm
    real = ssm.ssd_scan

    def wrong(x, dt, a_head, b_mat, c_mat, d_skip, chunk=128):
        if how == "no-decay":
            return real(x, dt, 0 * a_head, b_mat, c_mat, d_skip, chunk)
        b, s = x.shape[:2]
        pieces = [real(*(t[:, i:i + chunk] for t in (x, dt)), a_head,
                       *(t[:, i:i + chunk] for t in (b_mat, c_mat)),
                       d_skip, chunk) for i in range(0, s, chunk)]
        import jax.numpy as jnp
        return jnp.concatenate(pieces, axis=1)

    monkeypatch.setattr(ssm, "ssd_scan", wrong)
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "9", "--seconds", "2"])
    assert line["correct"] is False, check_rows(out)


def test_an_expert_layer_that_drops_rows_is_not_correct(capsys, monkeypatch):
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


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "7", "--seconds", "2", "--control"])
    assert line["control"] is True
    assert line["correct"] is False, check_rows(out)


def test_needed_flops_against_hand_numbers():
    """ISSUE 36's arithmetic: 586.7 MFLOP a token forward at 8192."""
    import run
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "nemotron_h")
    family = run.load_module("families", "nemotron_h")
    e = 2688
    mixer_proj = 2 * e * 10304 + 2 * 4096 * e
    assert mixer_proj == 77_414_400
    scan = 8 * 128 * 128 + 64 * (128 * 64 + 4 * 128 * 64)
    assert scan == 2_752_512 == flops.scan_forward_flops_per_token(cfg)
    attn_proj = 2 * e * (2 * 4096 + 2 * 256)
    core = 4 * 4096 * 4096
    assert (attn_proj, core) == (46_792_704, 67_108_864)
    experts = 2 * e * 128 + 4 * e * 3712 + 0.375 * 4 * e * 1856
    assert experts == 48_082_944
    head = 2 * e * 16384
    want = 3 * (mixer_proj + scan) + attn_proj + core + 3 * experts + head
    assert flops.forward_flops_per_token(cfg, 8192) == want == 586_731_520
    assert flops.train_flops_per_token(cfg, 8192) == 3 * want
    # mixers 41 %, attention 19 %, expert layers 25 %, head 15 %
    assert round(100 * 3 * (mixer_proj + scan) / want) == 41
    assert round(100 * (attn_proj + core) / want) == 19
    assert round(100 * 3 * experts / want) == 25
    assert round(100 * head / want) == 15
    assert flops.expected_rows_per_token(cfg) == 0.375
    assert family.n_params(cfg) == cfg["parameters"] == 528_093_120
    assert flops.flash_train_flops(cfg, 1, 8192) \
        == 32 * 8192 * 4096 * 12 * 128
    assert flops.flash_train_bytes(cfg, 1, 8192) \
        == 6 * (32 + 2) * 8192 * 128 * 2
    assert flops.experts_train_flops(cfg, 3072) == 12 * 3072 * e * 1856
    assert flops.experts_train_bytes(cfg, 3072) \
        == 6 * (8 * 2 * e * 1856 + 3072 * (2 * e + 2 * 1856))
    assert flops.scan_train_flops(cfg, 1, 8192) == 3 * 3 * 8192 * scan
    # x, B, C in bf16 and dt in float32 in; y out
    inputs, y = 2 * (4096 + 2048) + 4 * 64, 2 * 4096
    assert flops.scan_train_bytes(cfg, 1, 8192) \
        == 3 * 8192 * (3 * inputs + 2 * y) == 1_327_497_216


def test_the_new_readers_on_made_up_observations():
    """The scope times and the scan's and the grouped products' roofline
    shares from a hand-made list of operations; nothing where there is
    nothing to read."""
    import run
    from peaks import PEAKS
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "nemotron_h")
    peak = PEAKS["TPU v5 lite"]
    ms = 1e6
    fwd, bwd = "jit(step)/jvp(mx.fwd)/", "jit(step)/transpose(jvp(mx.fwd))/"
    ops = [
        {"op_name": fwd + "mx.ssm/dot_general", "end": 10 * ms},
        {"op_name": fwd + "mx.ssm/checkpoint/mx.ssm.scan/exp", "end": 6 * ms},
        {"op_name": bwd + "mx.ssm/checkpoint/mx.ssm.scan/dot_general",
         "end": 14 * ms},
        {"op_name": fwd + "mx.ssm/checkpoint/mx.ssm.conv/mul", "end": 2 * ms},
        {"op_name": bwd + "mx.ssm/checkpoint/mx.ssm.conv/mul", "end": 4 * ms},
        {"op_name": fwd + "mx.moe/mx.moe.experts/dot_general", "end": ms},
        {"op_name": fwd + "mx.attn/pallas_call", "end": 50 * ms},
    ]
    ops = [dict(o, start=0, collective=False, mosaic=False, name="f")
           for o in ops]
    # XLA's grouped-product kernels carry no op_name
    ops[-1:-1] = [{"op_name": "", "start": 0, "end": end * ms,
                   "collective": False, "mosaic": True,
                   "name": "ragged-dot-none.%d" % end} for end in (8, 12)]

    def obs(ops, flops=flops):
        return {"ctx": {"flops": flops, "cfg": cfg, "chips": 1,
                        "peak": peak},
                "sequences": 1, "seq_len": 8192, "_update_ops": (ops, 2)}

    def read(metric, o):
        return run.load_module("layer_metrics", metric).read(o)

    assert read("ssm_ms.train", obs(ops)) == 18.0
    assert read("ssm_scan_ms.train", obs(ops)) == 10.0
    assert read("ssm_conv_ms.train", obs(ops)) == 3.0
    needed_s = flops.scan_train_bytes(cfg, 1, 8192) / peak["hbm_bytes_per_s"]
    assert needed_s > flops.scan_train_flops(cfg, 1, 8192) \
        / peak["bf16_flops"]
    assert read("ssm_scan_roofline.train", obs(ops)) == pytest.approx(
        100 * needed_s / 10e-3, rel=1e-9)
    rows = 8192 * 6 * 8 / 128
    needed_s = 3 * flops.experts_train_flops(cfg, rows) / peak["bf16_flops"]
    assert needed_s > 3 * flops.experts_train_bytes(cfg, rows) \
        / peak["hbm_bytes_per_s"]
    assert read("moe_experts_roofline.train", obs(ops)) == pytest.approx(
        100 * needed_s / 10e-3, rel=1e-9)
    for metric in ("ssm_ms.train", "ssm_scan_ms.train", "ssm_conv_ms.train",
                   "ssm_scan_roofline.train", "moe_experts_roofline.train"):
        assert read(metric, obs(ops[-1:])) is None
        assert read(metric, obs(None)) is None
    # a family without the scan's functions (every other one)
    assert read("ssm_scan_roofline.train",
                obs(ops, run.load_module("flops", "afmoe"))) is None
