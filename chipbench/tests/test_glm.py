"""The cell ``glm-train-8k``: a rehearsal end to end on the CPU (the tiny
stand-in against the plain reference — plain, traced, with latent
attention broken underneath and under the lower-precision control), its
FLOP and byte functions against hand numbers, its two readers on made-up
observations, and its real-size step compiled for a described v5e.
``test_harness.py`` names its cells in a list and is not edited, so the
new cell's rehearsals live here."""
import json
import re

import pytest

from test_compile_v5e import (BYTES_LIMIT, _report, _train_compile,  # noqa
                              as_v5e, topo)
from test_harness import (DEVICE_KEYS, E2E_KEYS, check_rows, last_line)

CELL = "glm-train-8k"


def test_compiles_for_a_v5e_and_fits(topo, as_v5e):
    """Memory of the real-size step (PERF.md section 4 quotes the printed
    figures: arguments, temporaries, the Block's copy), the three flash
    kernels in every layer at a head width of 256, and no buffer a
    ``(seq, seq)`` wide."""
    compiled, text, resident, cfg = _train_compile(CELL, topo)
    total = _report(CELL, compiled, resident)
    # forward, dK/dV and dQ kernels in every layer, bf16 operands
    assert text.count("tpu_custom_call") >= 3 * cfg["n_layer"]
    assert "bf16" in text
    assert "bf16[20,8192,256]" in compiled.as_text()
    assert total < BYTES_LIMIT
    seq, sizes = 8192, {}
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]+)\]",
                                  compiled.as_text()):
        shape = tuple(int(d) for d in dims.split(","))
        size = 1
        for d in shape:
            size *= d
        sizes[(dtype, shape)] = size
    largest = sorted(sizes, key=sizes.get, reverse=True)[:8]
    print(f"[{CELL}] largest buffers: "
          + ", ".join(f"{d}{list(sh)}" for d, sh in largest))
    # the logits (8192, 19360) are 2.4 x seq^2 elements; a head's scores
    # would be seq^2 and twenty heads' 20 x
    assert max(sizes.values()) <= 3 * seq * seq, largest


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
    assert {"mla_ms.train", "mla_assemble_ms.train",
            "attn_core_roofline.train", "moe_experts_roofline.train"} <= known
    # the CPU has no device plane to read scopes from; the counter's
    # reader has its counts
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1
    assert "flash_roofline.train" not in known


@pytest.mark.parametrize("how", ["no-latent-norm", "own-rotary-key",
                                 "scaled-by-nope"])
def test_a_latent_attention_that_is_not_the_models_is_not_correct(
        capsys, monkeypatch, how):
    """Latent attention replaced underneath the timed path: one that
    leaves its two latents unnormed, one whose heads do not share the
    rotary key (each takes a different part of ``k_r``'s rotation: a roll
    by head), and one that scales its scores by the ``nope`` width alone.
    (One that rotates nothing is not among them: at the stand-in's 64
    positions and theta 1e6 three of its four rotary pairs hardly turn.)"""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.nn import transformer
    from mxnet_tpu.ops import attention
    if how == "no-latent-norm":
        class Unnormed(transformer.RMSNorm):
            def forward(self, x):
                super().forward(x)      # the scale exists, and is not used
                return x

        monkeypatch.setattr(transformer, "RMSNorm", Unnormed)
    elif how == "own-rotary-key":
        real = mx.np.broadcast_to

        def shifted(a, shape):
            out = real(a, shape)
            if a.ndim == 4 and a.shape[2] == 1:     # k_r over the heads
                out = mx.np.stack(
                    [mx.np.roll(out[:, :, h], h, axis=-1)
                     for h in range(shape[2])], axis=2)
            return out

        monkeypatch.setattr(transformer.np, "broadcast_to", shifted)
    else:
        real = attention._reference_attention

        def rescaled(q, k, v, heads, mask=None, causal=False, scale=None,
                     *rest):
            return real(q, k, v, heads, mask, causal,
                        (q.shape[-1] // heads // 2) ** -0.5, *rest)

        monkeypatch.setattr(attention, "_reference_attention", rescaled)
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


def test_needed_flops_and_bytes_against_hand_numbers():
    """ISSUE 40's arithmetic: 956.4 MFLOP a token forward at 8192."""
    import run
    _, _, cfg, _ = run.resolve(json.load(open("BENCHMARK.json")), CELL)
    flops = run.load_module("flops", "glm4_moe_lite")
    family = run.load_module("families", "glm4_moe_lite")
    e = 2048
    proj = 2 * (e * 768 + 768 * 20 * 256 + e * 576 + 512 * 20 * 448
                + 20 * 256 * e)
    assert proj == 43_515_904 == flops.mla_projection_flops_per_token(cfg)
    core = 20 * (2 * 256 + 2 * 256) * 4096
    assert core == 83_886_080 == flops.core_flops_per_token(cfg, 8192)
    experts = 2 * e * 64 + 6 * e * 1536 + 0.5 * 6 * e * 1536
    assert experts == 28_573_696 == flops.expert_layer_flops_per_token(cfg)
    dense, head = 6 * e * 10240, 2 * e * 19360
    assert (dense, head) == (125_829_120, 79_298_560)
    want = 5 * (proj + core) + dense + 4 * experts + head
    assert flops.forward_flops_per_token(cfg, 8192) == want == 956_432_384
    assert flops.train_flops_per_token(cfg, 8192) == 3 * want \
        == 2_869_297_152
    # attention core 44 %, MLA projections 23 %: latent attention is two
    # thirds of the step; experts 12, dense 13, head 8
    assert round(100 * 5 * core / want) == 44
    assert round(100 * 5 * proj / want) == 23
    assert round(100 * 4 * experts / want) == 12
    assert round(100 * dense / want) == 13
    assert round(100 * head / want) == 8
    assert flops.expected_rows_per_token(cfg) == 0.5
    assert family.n_params(cfg) == cfg["parameters"] == 591_294_720
    # the kernels at 256-wide heads: 6 products of 2 x 256 a pair and
    # head; K and V read once a head
    assert flops.flash_train_flops(cfg, 1, 8192) \
        == 5 * 20 * 8192 * 4096 * 12 * 256
    assert flops.flash_train_bytes(cfg, 1, 8192) \
        == 5 * 6 * (20 + 20) * 8192 * 256 * 2
    assert flops.experts_train_flops(cfg, 4096) == 18 * 4096 * e * 1536
    assert flops.experts_train_bytes(cfg, 4096) \
        == 6 * (8 * 3 * e * 1536 + 4096 * (2 * e + 3 * 1536))
    # the second depth, where a configuration trains it: the joining
    # projection, one more expert layer, one more head product
    with_mtp = dict(cfg, num_nextn_predict_layers=1)
    assert flops.forward_flops_per_token(with_mtp, 8192) - want \
        == proj + core + experts + head + 2 * 2 * e * e
    assert family.n_params(with_mtp) - cfg["parameters"] == 115_223_808


def test_the_new_readers_on_made_up_observations():
    """The two scope times from a hand-made list of operations, forward
    and backward, the assembly inside the block and the kernels inside
    ``mx.attn`` inside it; nothing where there is nothing to read (the
    parent's program has no such scope)."""
    import run
    ms = 1e6
    fwd, bwd = "jit(step)/jvp(mx.fwd)/", "jit(step)/transpose(jvp(mx.fwd))/"
    ops = [
        {"op_name": fwd + "mx.mla/dot_general", "end": 10 * ms},
        {"op_name": fwd + "mx.mla/mx.mla.assemble/concatenate", "end": 2 * ms},
        {"op_name": bwd + "mx.mla/mx.mla.assemble/slice", "end": 4 * ms},
        {"op_name": fwd + "mx.mla/mx.attn/pallas_call", "end": 20 * ms},
        {"op_name": bwd + "mx.mla/mx.attn/pallas_call", "end": 40 * ms},
        {"op_name": fwd + "mx.moe/mx.moe.experts/dot_general", "end": 8 * ms},
    ]
    ops = [dict(o, start=0, collective=False, mosaic=False, name="f")
           for o in ops]

    def obs(ops):
        return {"ctx": {}, "_update_ops": (ops, 2)}

    def read(metric, o):
        return run.load_module("layer_metrics", metric).read(o)

    assert read("mla_ms.train", obs(ops)) == 38.0
    assert read("mla_assemble_ms.train", obs(ops)) == 3.0
    for metric in ("mla_ms.train", "mla_assemble_ms.train"):
        assert read(metric, obs(ops[-1:])) is None
        assert read(metric, obs(None)) is None
