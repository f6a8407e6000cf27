"""The trace reducer: interval arithmetic on hand numbers, HLO-line
parsing, and the whole reduction against one small trace recorded on
the v5e (``data/small.xplane.pb``, ``dev/record_small_trace.py``: four
runs of two matmul fusions, 2 ms of sleep after each, under chipbench's
spans)."""
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")


def test_union_gaps_subtract():
    import xplane
    iv = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert xplane.union_length(iv) == 25
    assert xplane.gaps(iv, -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert xplane.gaps([], 0, 7) == [(0, 7)]
    # 10 of collective, of which [3, 6) and [8, 12) are under compute
    assert xplane.subtract([(0, 10)], [(3, 6), (8, 12)]) == 5
    assert xplane.subtract([(0, 10)], []) == 10
    # covers that overlap each other count once; intervals each on their own
    assert xplane.subtract([(0, 10), (4, 20)],
                           [(2, 6), (5, 8), (18, 30), (-9, -1)]) == 4 + 10
    assert xplane.merged([(5, 8), (2, 6), (8, 9), (20, 21)]) == [[2, 9],
                                                                 [20, 21]]


def test_idle_goes_to_the_span_that_covers_most_of_it():
    import xplane
    find = xplane.covering_span([
        {"name": "outer", "start": 0, "end": 100},
        {"name": "inner", "start": 10, "end": 20},
        {"name": "later", "start": 150, "end": 160}])
    assert find(12, 18) == "inner"      # a tie goes to the later start
    assert find(19, 60) == "outer"
    assert find(95, 158) == "later"
    assert find(100, 150) == "(no chipbench span)"
    assert find(155, 300) == "later"


def test_reduction_is_not_quadratic():
    """A traced window of the four-chip cell holds some 10^5 operations
    a chip and 10^4 collectives: the reduction of that many must take
    seconds (it once compared every pair, and a traced run outlived its
    limit on the chip)."""
    import time
    import xplane
    ops, t = [], 0
    for i in range(100_000):
        coll = i % 20 == 7
        ops.append({"name": f"all-reduce.{i}" if coll else f"fusion.{i}",
                    "opcode": "all-reduce" if coll else "fusion",
                    "mosaic": False, "start": t, "end": t + 900})
        t += 1000
    raw = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
           "spans": [{"name": "window", "start": 0, "end": t}]
           + [{"name": "train.step", "start": k * t // 50,
               "end": k * t // 50 + t // 100} for k in range(50)]}
    t0 = time.perf_counter()
    r = xplane.reduce_raw(raw, 1)
    assert time.perf_counter() - t0 < 20
    assert r["busy_s"] == pytest.approx(0.09)
    assert r["collective_exposed_s"] == [pytest.approx(5000 * 900e-9)]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(0.01)


def test_hlo_lines():
    import xplane
    fusion = ("%fusion.1542 = (f32[1024]{0:T(1024)}, bf16[8,1024,1024]"
              "{2,1,0:T(8,128)(2,1)S(1)}) fusion(f32[8,1024,1024]{2,1,0:"
              "T(8,128)S(1)} %get-tuple-element.1557), kind=kOutput")
    assert xplane.parse_op(fusion) == ("fusion.1542", "fusion", False)
    kernel = ('%transpose_jvp___.48 = (f32[128,1024,128]{2,1,0:T(8,128)}) '
              'custom-call(bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)} '
              '%custom-call.354), custom_call_target="tpu_custom_call"')
    assert xplane.parse_op(kernel) == ("transpose_jvp___.48", "custom-call",
                                       True)
    ar = ("%all-reduce-done.3 = f32[1280]{0:T(1024)} all-reduce-done("
          "f32[1280]{0:T(1024)} %all-reduce-start.3)")
    short, opcode, mosaic = xplane.parse_op(ar)
    assert (short, opcode, mosaic) == ("all-reduce-done.3",
                                       "all-reduce-done", False)
    assert xplane.is_collective({"name": short, "opcode": opcode})
    assert not xplane.is_collective({"name": "fusion.1", "opcode": "fusion"})
    assert xplane.family_of("transpose_jvp___.48") == "transpose_jvp___"
    assert xplane.family_of("copy-done.108") == "copy-done"


def test_reduction_of_the_recorded_trace():
    import xplane
    r = xplane.reduce(SMALL, 1)
    d0 = r["devices"][0]
    assert len(d0["modules"]) == 4 and len(d0["ops"]) == 16
    # the window is the chipbench/window span: 14.08 ms
    assert r["window_s"] == pytest.approx(0.014081, rel=1e-3)
    # each run: a 11.57 us matmul+tanh fusion and a 12.61 us matmul
    # fusion back to back (2 x 1024^3 FLOP in 11.6 us = 186 TFLOP/s)
    assert r["busy_s"] == pytest.approx(4 * 24.2e-6, rel=0.01)
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(4 * 12.614e-6, rel=1e-3)
    assert ops["convolution_tanh_fusion"] == pytest.approx(4 * 11.573e-6,
                                                           rel=1e-3)
    # idle time goes to the span the host was in: nearly all of it slept
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert gaps["small.idle"] > 0.95 * sum(gaps.values())
    assert r["idle_gaps"][0][0] == "small.idle"
    assert r["collective_exposed_s"] == [0.0]
    spans = [s["name"] for s in r["spans"]]
    assert spans.count("small.step") == 4 and "window" not in spans


def test_containers_are_neither_busy_nor_compute():
    """A ``while`` wraps the micro-batch loop of a train step: a stall
    inside it is idle time, a collective beside it is exposed, and it
    is no row of the breakdown."""
    import xplane

    def op(name, opcode, start, end):
        return {"name": name, "opcode": opcode, "mosaic": False,
                "start": start, "end": end}

    ops = [op("while.1", "while", 0, 100), op("fusion.2", "fusion", 0, 30),
           op("all-reduce.3", "all-reduce", 30, 60),       # exposed: 30
           op("fusion.4", "fusion", 80, 100)]              # stall: 60-80
    assert xplane.is_container(ops[0]) and not xplane.is_container(ops[1])
    raw = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
           "spans": [{"name": "window", "start": 0, "end": 100},
                     {"name": "train.step", "start": 55, "end": 90}]}
    r = xplane.reduce_raw(raw, 1)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["idle_gaps"] == [["train.step", pytest.approx(20e-9)]]
    assert r["collective_exposed_s"] == [pytest.approx(30e-9)]
    assert "while" not in dict(r["device_ops"])
    assert dict(r["device_ops"])["fusion"] == pytest.approx(50e-9)


def test_helper_programs_are_not_steps():
    import xplane
    mods = [{"name": "jit_step", "start": 0, "end": 1000},
            {"name": "jit__threefry_split", "start": 1001, "end": 1004},
            {"name": "jit_step", "start": 1010, "end": 1990}]
    assert [m["name"] for m in xplane.step_modules(mods)] == ["jit_step"] * 2
