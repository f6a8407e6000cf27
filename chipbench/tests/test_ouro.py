"""The cell ``ouro-train-8k``: a rehearsal end to end on the CPU (the tiny
stand-in against the plain reference — plain, traced, with the loop, the
entropy term or the gate broken underneath, and under the
lower-precision control), its FLOP and byte functions against hand
numbers, its five readers on made-up observations, and its real-size
step compiled for a described v5e with and without its recomputation
boundaries.  ``test_harness.py`` names its cells in a list and is not
edited, so the new cell's rehearsals live here."""
import json

import pytest

from test_compile_v5e import (BYTES_LIMIT, _report, _train_compile,  # noqa
                              as_v5e, topo)
from test_harness import (DEVICE_KEYS, E2E_KEYS, check_rows, last_line)

CELL = "ouro-train-8k"


def _with_cfg(monkeypatch, **over):
    import run
    real = run.resolve

    def patched(*a, **k):
        entry, cell, cfg, traffic = real(*a, **k)
        return entry, cell, dict(cfg, **over), traffic

    monkeypatch.setattr(run, "resolve", patched)


def test_compiles_for_a_v5e_and_fits_behind_its_boundaries(topo, as_v5e):
    """Memory of the real-size step (PERF.md section 4 quotes the printed
    figures: arguments, temporaries, the Block's copy), the three flash
    kernels once a layer application and the forward not made again
    (the policy keeps what a Pallas kernel wrote), and no buffer as wide
    as one exit's (8192, 49152) logits."""
    import re
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    total = _report(CELL, compiled, resident)
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    assert text.count("tpu_custom_call") == 3 * apps
    assert "rematted_computation" in compiled.as_text()
    assert "bf16[16,8192,128]" in compiled.as_text()
    assert total < BYTES_LIMIT - 3e9        # the margin PERF.md states
    sizes = {}
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]+)\]",
                                  compiled.as_text()):
        size = 1
        for d in dims.split(","):
            size *= int(d)
        sizes[(dtype, dims)] = size
    largest = sorted(sizes, key=sizes.get, reverse=True)[:6]
    print(f"[{CELL}] largest buffers: "
          + ", ".join(f"{d}[{sh}]" for d, sh in largest))
    # the four exits stacked against one vocabulary chunk
    assert max(sizes.values()) <= 4 * 8192 * 8192, largest


def test_without_a_boundary_the_step_does_not_fit(topo, as_v5e, monkeypatch):
    """What the cell cannot load without: the same step with
    ``layer_remat`` null asks for more than the chip has; and with every
    application recomputed whole it runs the flash forward twice."""
    _with_cfg(monkeypatch, layer_remat=None)
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    assert _report(CELL + " no boundary", compiled, resident) > BYTES_LIMIT
    assert "rematted_computation" not in compiled.as_text()
    _with_cfg(monkeypatch, layer_remat=True)
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    assert _report(CELL + " remat=True", compiled, resident) < BYTES_LIMIT
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    assert text.count("tpu_custom_call") == 4 * apps


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
    # the distribution the check compared, as the reference prints it
    counts = json.loads(next(l for l in out if l.startswith("# counts "))
                        [len("# counts "):])
    assert counts["exit.pdf"] < 1e-3
    for side in ("program", "reference"):
        assert len(counts[side]) == 3 and abs(sum(counts[side]) - 1) < 1e-4


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
    assert {"loop_ms.train", "recompute_ms.train", "exit_head_ms.train",
            "exit_head_roofline.train", "exit_mean_step.train",
            "attn_core_roofline.train"} <= known
    # the CPU has no device plane to read scopes from; the counter's
    # reader has its distribution: three exits at the stand-in's size
    assert 1 < line["metrics"]["exit_mean_step.train"]["value"] < 3
    assert not {"moe_ms.train", "flash_roofline.train"} & known


@pytest.mark.parametrize("how", ["a-pass-skipped", "no-entropy-term",
                                 "gate-detached", "carry-not-normed"])
def test_a_loop_that_is_not_the_models_is_not_correct(capsys, monkeypatch,
                                                      how):
    """The looped model broken underneath the timed path: the second pass
    over the stack left out (its exit repeats the first's state), the
    loss without its entropy term, a gate that learns nothing (the exit
    distribution detached), and passes that hand on the stack's output
    and not the final norm's."""
    import jax
    from mxnet_tpu.gluon.model_zoo import ouro as zoo
    from mxnet_tpu.numpy.multiarray import _wrap
    if how == "a-pass-skipped":
        real, calls = zoo.OuroModel.one_pass, []

        def one_pass(self, x):
            calls.append(1)
            return x if len(calls) % self._steps == 2 else real(self, x)

        monkeypatch.setattr(zoo.OuroModel, "one_pass", one_pass)
    elif how == "no-entropy-term":
        real = zoo.looped_lm_loss
        monkeypatch.setattr(
            zoo, "looped_lm_loss",
            lambda out, labels, beta=0.1: real(out, labels, beta=0.0))
    elif how == "gate-detached":
        real = zoo.OuroExitGate.forward
        monkeypatch.setattr(
            zoo.OuroExitGate, "forward",
            lambda self, z: _wrap(jax.lax.stop_gradient(real(self, z)._data)))
    else:
        def one_pass(self, x):
            for cell in self.layers:
                x = cell(x)
            return x

        monkeypatch.setattr(zoo.OuroModel, "one_pass", one_pass)
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "9", "--seconds", "2"])
    assert line["correct"] is False, check_rows(out)


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = last_line(capsys, ["--workload", CELL, "--tiny", "--seed",
                                   "7", "--seconds", "2", "--control"])
    assert line["control"] is True
    assert line["correct"] is False, check_rows(out)


def test_needed_flops_and_bytes_against_hand_numbers():
    """ISSUE 42's arithmetic: 2.986 GFLOP a token forward at 8192."""
    import run
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "ouro")
    family = run.load_module("families", "ouro")
    e = 2048
    layer = 2 * 4 * e * e + 4 * e * 4096 + 6 * e * 5632
    assert layer == 136_314_880 == flops.layer_flops_per_token(cfg, 8192)
    head = 2 * e * 49152
    assert head == 201_326_592 == flops.head_flops_per_token(cfg)
    want = 16 * layer + 4 * head + 3 * 2 * e
    assert flops.forward_flops_per_token(cfg, 8192) == want == 2_986_356_736
    assert flops.train_flops_per_token(cfg, 8192) == 3 * want \
        == 8_959_070_208
    # the update: 73.4 TFLOP, 0.373 s at the chip's peak
    assert round(3 * want * 8192 / 1e12, 1) == 73.4
    assert round(100 * 16 * layer / want) == 73
    assert round(100 * 4 * head / want) == 27
    assert family.n_params(cfg) == cfg["parameters"] == 406_884_353
    # sixteen applications of the kernels at 16 x 128, one KV head a
    # query head
    assert flops.flash_train_flops(cfg, 1, 8192) \
        == 16 * 16 * 8192 * 4096 * 12 * 128
    assert flops.flash_train_bytes(cfg, 1, 8192) \
        == 16 * 6 * (16 + 16) * 8192 * 128 * 2
    # the four heads: compute-bound by a factor of 80
    assert flops.exit_head_train_flops(cfg, 1, 8192) == 3 * 4 * 8192 * head
    assert flops.exit_head_train_bytes(cfg, 1, 8192) \
        == 6 * (49152 * e + 4 * 8192 * e)
    assert flops.exit_head_train_flops(cfg, 1, 8192) / 197e12 \
        > 80 * flops.exit_head_train_bytes(cfg, 1, 8192) / 819e9


def test_the_new_readers_on_made_up_observations():
    """The three scope times, the recomputed time and the roofline share
    from a hand-made list of operations — the final norm inside the loop
    is the exit head's, the replayed forward lies under the backward's
    name — and the counter from a made-up distribution; nothing where
    there is nothing to read (the parent's program has no such scope)."""
    import run
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    ms = 1e6
    fwd, bwd = "jit(step)/jvp(mx.fwd)/", "jit(step)/transpose(jvp(mx.fwd))/"
    ops = [
        {"op_name": fwd + "mx.loop/mx.loop.t1/checkpoint/dot_general",
         "end": 10 * ms},
        {"op_name": fwd + "mx.loop/mx.loop.t1/mx.exit/mul", "end": 2 * ms},
        {"op_name": bwd + "mx.loop/mx.loop.t2/checkpoint/"
         "rematted_computation/dot_general", "end": 6 * ms},
        {"op_name": bwd + "mx.loop/mx.loop.t2/checkpoint/dot_general",
         "end": 20 * ms},
        {"op_name": fwd + "mx.exit/while/body/dot_general", "end": 30 * ms},
        {"op_name": bwd + "mx.exit/while/body/dot_general", "end": 68 * ms},
        {"op_name": "jit(step)/mx.optimizer/add", "end": 4 * ms},
    ]
    ops = [dict(o, start=0, collective=False, mosaic=False, name="f")
           for o in ops]
    flops = run.load_module("flops", "ouro")

    class Family:
        last_pdf = [0.5, 0.25, 0.125, 0.125]

    def obs(ops, family=Family):
        return {"ctx": {"cfg": cfg, "flops": flops, "chips": 1,
                        "family": family,
                        "peak": {"bf16_flops": 197e12,
                                 "hbm_bytes_per_s": 819e9}},
                "sequences": 1, "seq_len": 8192, "_update_ops": (ops, 2)}

    def read(metric, o):
        return run.load_module("layer_metrics", metric).read(o)

    assert read("loop_ms.train", obs(ops)) == 18.0
    assert read("exit_head_ms.train", obs(ops)) == 50.0
    assert read("recompute_ms.train", obs(ops)) == 3.0
    # 19.79 TFLOP of needed work: 100.46 ms at the peak, over 50 ms
    assert round(read("exit_head_roofline.train", obs(ops)), 1) == 200.9
    assert read("exit_mean_step.train", obs(ops)) == 1.875
    for metric in ("loop_ms.train", "exit_head_ms.train",
                   "recompute_ms.train", "exit_head_roofline.train"):
        assert read(metric, obs(ops[-1:])) is None
        assert read(metric, obs(None)) is None
    assert read("exit_mean_step.train", obs(ops, family=object)) is None
