"""The afmoe decoder family (gluon/model_zoo/afmoe.py) and what it is built
from — RMSNorm, rotary embedding, gated grouped-query attention with a
window, SwiGLU, and ``nn.RoutedExperts``, one share of a drop-free expert
layer — against the benchmark's plain reference
(chipbench/reference/afmoe.py, which imports nothing of the program), on
seeded random weights at small sizes on the CPU.  What the families'
tests share is ``tests/family_harness.py``.
"""
import functools
import gc

import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu import functional

REF, FAMILY, FLOPS = H.load("afmoe")
_weights = functools.partial(H.weights, "afmoe")

CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "num_experts_held": 4, "experts_held_from": 0, "rows_bound": 128,
    "sliding_window": 6, "vocab_size": 64, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "route_scale": 2.826,
}
SIZES = {
    "small": CFG,
    # other experts held, a window as long as the sequence, two dense layers
    "other-share": dict(CFG, experts_held_from=8, sliding_window=16,
                        num_dense_layers=2, layer_types=[
                            "full_attention", "sliding_attention",
                            "sliding_attention", "full_attention"]),
}


@pytest.mark.parametrize("size", list(SIZES))
def test_zoo_model_loss_gradients_and_counts_against_the_reference(size):
    cfg = SIZES[size]
    weights = _weights(cfg, 7)
    net = FAMILY.build_net(cfg, weights)
    x, y = H.tokens(cfg)
    assert all(n.endswith((".expert_bias", ".expert_load", ".rows_over"))
               for n in functional.split_params(net)[1])
    params = dict(weights)
    bias = params.pop(REF.BIAS)
    n_layer = len(cfg["layer_types"])
    mutated, loads, _ = H.against_the_reference(
        "afmoe", net, FAMILY.loss_fn,
        lambda p, xs, ys: REF.sequence_loss_sum(p, bias, xs, ys, cfg),
        params, x, y, n_layer)
    # the counts ride the mutated aux: assignments per published expert
    counts = FAMILY.stack_program_tree(mutated, n_layer)
    onp.testing.assert_array_equal(counts[FAMILY.LOAD], loads)
    assert counts[FAMILY.LOAD].sum() == (n_layer - cfg["num_dense_layers"]) \
        * x.size * cfg["num_experts_per_tok"]
    assert not counts[FAMILY.ROWS_OVER].any()
    # expert_bias is aux that nothing mutates
    assert not any(n.endswith(".expert_bias") for n in mutated)


@pytest.fixture(scope="module")
def eager():
    """The family's eager case: the net of seed 1 op by op under
    ``mx.autograd.record``, and hybridized — (eager, hybridized) loss."""
    return H.three_updates("afmoe", CFG, 1, [H.tokens(CFG)],
                           3).eager_and_hybridized


def test_eager_and_hybridized_agree(eager):
    assert abs(eager[0] - eager[1]) < 1e-6


def test_parameter_count_of_the_cell():
    """The configuration file's count, from the family's shapes."""
    cfg = H.config("trinity-mini")
    assert FAMILY.n_params(cfg) == cfg["parameters"] == 504_147_712
    assert round(FLOPS.forward_flops_per_token(cfg, 8192)) == 712_777_728
    assert abs(FLOPS.keys_per_query(8192, 2048) - 1792.125) < 1e-9
    assert FLOPS.expected_rows_per_token(cfg) == 0.5


def _whole_layer(cfg, seed=3):
    return H.whole_experts(cfg["hidden_size"], cfg["moe_intermediate_size"],
                           cfg["num_experts"], seed,
                           shared=cfg["moe_intermediate_size"])


def _layer(cfg, w, held, rows_bound, shared=True):
    return H.routed_experts(w, *held, cfg["num_experts_per_tok"], rows_bound,
                            shared, route_scale=cfg["route_scale"])


@pytest.fixture(scope="module")
def uncut():
    """24 tokens through the whole layer, once for the three cuts."""
    w, u = _whole_layer(CFG), H.rows(24, CFG["hidden_size"])
    return (w, u) + tuple(H.uncut("afmoe", CFG, w, u))


@pytest.mark.parametrize("shares", [16, 4, 1])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(shares,
                                                                 uncut):
    """Each share routes over all the experts and computes its own; the
    shared expert is what every chip computes alike, so it is counted
    once."""
    H.assert_shares_add_up(uncut, shares, CFG["num_experts_per_tok"],
                           route_scale=CFG["route_scale"])


@pytest.mark.parametrize("bound,tokens", [(8, 40), (16, 40), (40, 40),
                                          (64, 40), (5, 12)])
def test_unbalanced_routing_drops_nothing_under_the_bound(bound, tokens):
    """A router that sends every token to held expert 0 (and most to
    expert 1): no capacity an expert, so nothing is dropped while the
    rows fit the layer's bound, and ``rows_over`` counts exactly the
    assignments past it."""
    cfg = dict(CFG, num_experts_per_tok=2)
    w = _whole_layer(cfg)
    w["router"] = onp.zeros_like(w["router"])
    w["bias"] = onp.zeros_like(w["bias"])
    w["bias"][0], w["bias"][1], w["bias"][9] = 3.0, 2.0, 2.001
    u = onp.random.RandomState(2).randn(tokens, cfg["hidden_size"])
    u = jnp.asarray(u, jnp.float32)
    layer = _layer(cfg, w, (0, 4), bound, shared=False)
    got, mutated = H.traced(lambda p: functional.functional_call(
        layer, p, u[None], train=True), functional.param_arrays(layer))
    load = onp.asarray(mutated["expert_load"])
    # all scores tie at sigmoid(0): the bias alone selects 0 and 9
    assert load[0] == tokens and load[9] == tokens and load.sum() == 2 * tokens
    held_rows = tokens          # expert 0's; expert 9 is on another chip
    assert int(mutated["rows_over"][0]) == max(held_rows - bound, 0)
    whole = dict(cfg, num_experts_held=4, experts_held_from=0)
    p, bias = H.reference_leaves(dict(w, sg=0 * w["sg"]), 0, 4)
    want, _ = H.traced(lambda u_: REF._experts(u_, p, bias, whole), u)
    kept = min(held_rows, bound)        # rows are in token order
    onp.testing.assert_allclose(got[0][:kept], want[:kept], atol=2e-5,
                                rtol=2e-5)
    assert not onp.asarray(got[0][kept:]).any()    # left out, and counted


def test_counts_accumulate_only_in_training_calls():
    cfg = CFG
    layer = _layer(cfg, _whole_layer(cfg), (0, 4), 64)
    u = mx.np.array(onp.random.RandomState(0).randn(1, 8, cfg["hidden_size"])
                    .astype(onp.float32))
    layer(u)
    assert layer.expert_load.data().asnumpy().sum() == 0
    for calls in (1, 2):
        with mx.autograd.record(train_mode=True):
            layer(u)
        assert layer.expert_load.data().asnumpy().sum() \
            == calls * 8 * cfg["num_experts_per_tok"]


def test_rms_norm_and_rotary_embedding():
    rs = onp.random.RandomState(0)
    x = rs.randn(2, 5, 4 * 8).astype(onp.float32)
    g = (1 + 0.1 * rs.randn(8)).astype(onp.float32)
    got = mx.npx.rms_norm(mx.np.array(x).reshape(2, 5, 4, 8),
                          mx.np.array(g)).asnumpy()
    xh = x.reshape(2, 5, 4, 8)
    want = xh / onp.sqrt((xh ** 2).mean(-1, keepdims=True) + 1e-5) * g
    onp.testing.assert_allclose(got, want, atol=1e-6)
    rot = mx.npx.rotary_embedding(mx.np.array(x), 4).asnumpy()
    want = H.traced(lambda x_: jnp.stack([
        REF._rope(xi.reshape(5, 4, 8), 10000.0) for xi in x_]),
        jnp.asarray(x))
    onp.testing.assert_allclose(rot.reshape(2, 5, 4, 8), want, atol=1e-6)
    # position 0 is not rotated; a rotation keeps every pair's length
    onp.testing.assert_allclose(rot[:, 0], x[:, 0], atol=1e-7)
    onp.testing.assert_allclose(
        (rot.reshape(2, 5, 4, 2, 4) ** 2).sum(3),
        (x.reshape(2, 5, 4, 2, 4) ** 2).sum(3), rtol=1e-5)


def test_amp_keeps_norms_and_router_in_float32(uncut):
    """Under mx.amp bf16 the norms are fp32 ops and the router never
    sees bf16: the selection equals the float32 reference's even where
    the expert products run in bf16."""
    cfg = CFG
    w = uncut[0]
    u = H.rows(32, cfg["hidden_size"])
    _, load = H.uncut("afmoe", cfg, w, u)
    layer = _layer(cfg, w, (0, 4), 128)
    mx.amp.init("bfloat16")
    try:
        with mx.autograd.record(train_mode=True):
            out = layer(mx.np.array(u)[None])
        assert mx.npx.rms_norm(mx.np.array(u).astype("bfloat16"),
                               mx.np.ones((cfg["hidden_size"],))
                               ).dtype == onp.float32
    finally:
        mx.amp._deactivate()
    assert out.dtype == onp.float32
    onp.testing.assert_array_equal(layer.expert_load.data().asnumpy(), load)


def test_sharded_train_step_carries_the_counts_in_aux(eager):
    """Through ShardedTrainStep the first loss is the eager net's, the
    counts accumulate in ``step.aux`` over updates, ``expert_bias`` stays
    as it was, and the family finds the counts of the step round the net
    it built — and nothing once that step is gone."""
    cfg = CFG
    net = FAMILY.build_net(cfg, _weights(cfg, 1))
    step = H.sharded_step(net, FAMILY.loss_fn)
    bias0 = {n: onp.asarray(a) for n, a in step.aux.items()
             if n.endswith("expert_bias")}
    x, y = H.tokens(cfg)
    for updates in (1, 2):
        loss = float(step(x, y).asnumpy())
        assert updates > 1 or abs(loss - eager[0]) < 1e-5
        counts = FAMILY.stack_program_tree(step.aux, 3)
        assert (counts[FAMILY.LOAD].sum(axis=1)
                == updates * x.size * cfg["num_experts_per_tok"]).all()
        assert not counts[FAMILY.ROWS_OVER].any()
    for n, a in bias0.items():
        onp.testing.assert_array_equal(onp.asarray(step.aux[n]), a)
    found = FAMILY.step_counts()
    assert set(found) == {
        n for n in step.aux if not n.endswith("expert_bias")}
    assert all(found[n] is step.aux[n] for n in found)
    norms = FAMILY.change_norms(cfg, 1, step.trainable)
    assert set(found) <= set(norms)
    onp.testing.assert_array_equal(
        FAMILY.last_counts[FAMILY.LOAD], counts[FAMILY.LOAD])
    del step, net
    gc.collect()
    assert FAMILY.step_counts() == {}


def test_scopes_of_the_afmoe_block_do_not_grow_with_depth():
    """``mx.attn`` once a layer, ``mx.moe`` / ``mx.moe.route`` /
    ``mx.moe.experts`` once an expert layer, whatever the depth."""
    for layers in (2, 4):
        cfg = dict(CFG, layer_types=["sliding_attention"] * layers)
        net = FAMILY.build_net(cfg, _weights(cfg, 1))
        text, entered = H.lowered_scopes(net, FAMILY.loss_fn, *H.tokens(cfg))
        assert dict(entered) == {
            "mx.fwd": 1, "mx.optimizer": 1, "mx.attn": layers,
            "mx.moe": layers - 1, "mx.moe.route": layers - 1,
            "mx.moe.experts": layers - 1}
        assert "mx.moe.experts" in text and "mx.moe.route" in text


def test_rows_bound_counter_once_a_traced_call():
    from mxnet_tpu import telemetry
    cfg = CFG
    layer = _layer(cfg, _whole_layer(cfg), (0, 4), 48)
    u = mx.np.array(onp.zeros((1, 8, cfg["hidden_size"]), onp.float32))
    _, got = H.counters("moe.rows_bound_total", layer, u)
    assert got == {"moe.rows_bound_total": 48}
    assert telemetry.CATALOG["moe.rows_bound_total"][0] == "counter"
