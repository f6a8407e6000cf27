"""The afmoe decoder family (gluon/model_zoo/afmoe.py) and what it is built
from — RMSNorm, rotary embedding, gated grouped-query attention with a
window, SwiGLU, and ``nn.RoutedExperts``, one share of a drop-free expert
layer — against the benchmark's plain reference
(chipbench/reference/afmoe.py, which imports nothing of the program), on
seeded random weights at small sizes on the CPU.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import functional
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chipbench(kind):
    path = os.path.join(_REPO, "chipbench", kind, "afmoe.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_afmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF, FAMILY, FLOPS = (_chipbench(k) for k in ("reference", "families",
                                              "flops"))

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


def _tokens(cfg, batch=2, seq=16, seed=0):
    t = onp.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq + 1), dtype=onp.int32)
    return t[:, :-1], t[:, 1:]


def _reference_loss(cfg, weights, x, y):
    """Mean token cross-entropy, its gradients, and the assignment
    counts, by the reference."""
    params = dict(weights)
    bias = params.pop(REF.BIAS)

    def loss(p):
        total, loads = 0.0, 0
        for xs, ys in zip(x, y):
            one, load = REF.sequence_loss_sum(p, bias, jnp.asarray(xs),
                                              jnp.asarray(ys), cfg)
            total, loads = total + one, loads + load
        return total / x.size, loads

    return jax.value_and_grad(loss, has_aux=True)(params)


@pytest.mark.parametrize("size", list(SIZES))
def test_zoo_model_loss_gradients_and_counts_against_the_reference(size):
    cfg = SIZES[size]
    weights = FAMILY.make_weights(cfg, 7)
    net = FAMILY.build_net(cfg, weights)
    x, y = _tokens(cfg)
    trainable, aux = functional.split_params(net)
    assert all(n.endswith((".expert_bias", ".expert_load", ".rows_over"))
               for n in aux)

    def loss(tr):
        logits, mutated = functional.functional_call(
            net, {**tr, **aux}, x, train=True)
        return FAMILY.loss_fn(logits, y), mutated

    with jax.default_matmul_precision("highest"):
        (got, mutated), grads = jax.value_and_grad(loss, has_aux=True)(
            trainable)
    (want, loads), ref_grads = _reference_loss(cfg, weights, x, y)
    assert abs(float(got) - float(want)) < 2e-5
    n_layer = len(cfg["layer_types"])
    stacked = FAMILY.stack_program_tree(grads, n_layer)
    assert set(stacked) == set(ref_grads)
    for name, ref in ref_grads.items():
        onp.testing.assert_allclose(stacked[name], ref, atol=3e-6,
                                    rtol=2e-3, err_msg=name)
    # the counts ride the mutated aux: assignments per published expert
    counts = FAMILY.stack_program_tree(mutated, n_layer)
    onp.testing.assert_array_equal(counts[FAMILY.LOAD], loads)
    assert counts[FAMILY.LOAD].sum() == (n_layer - cfg["num_dense_layers"]) \
        * x.size * cfg["num_experts_per_tok"]
    assert not counts[FAMILY.ROWS_OVER].any()
    # expert_bias is aux that nothing mutates
    assert not any(n.endswith(".expert_bias") for n in mutated)


def test_parameter_count_of_the_cell():
    """The configuration file's count, from the family's shapes."""
    import json
    cfg = json.load(open(os.path.join(
        _REPO, "chipbench", "configs", "trinity-mini.json")))
    assert FAMILY.n_params(cfg) == cfg["parameters"] == 504_147_712
    assert round(FLOPS.forward_flops_per_token(cfg, 8192)) == 712_777_728
    assert abs(FLOPS.keys_per_query(8192, 2048) - 1792.125) < 1e-9
    assert FLOPS.expected_rows_per_token(cfg) == 0.5


def _layer(cfg, held, rows_bound, shared=True):
    layer = nn.RoutedExperts(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["num_experts"], cfg["num_experts_per_tok"], held=held,
        rows_bound=rows_bound,
        shared_hidden_size=cfg["moe_intermediate_size"] if shared else 0,
        route_scale=cfg["route_scale"])
    layer.initialize()
    return layer


def _whole_layer(cfg, seed=3):
    """All of one expert layer's weights (every published expert)."""
    rs = onp.random.RandomState(seed)
    e, f, n = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    return {"router": rs.randn(n, e) * 0.3, "bias": rs.randn(n) * 0.05,
            "gate": rs.randn(n, e, f) * 0.2, "up": rs.randn(n, e, f) * 0.2,
            "down": rs.randn(n, f, e) * 0.2, "sg": rs.randn(f, e) * 0.2,
            "su": rs.randn(f, e) * 0.2, "sd": rs.randn(e, f) * 0.2}


def _load(layer, w, lo, hi, shared=True):
    def put(p, a):
        p.set_data(mx.np.array(onp.asarray(a, onp.float32)))
    put(layer.router, w["router"])
    put(layer.expert_bias, w["bias"])
    put(layer.w_gate, w["gate"][lo:hi])
    put(layer.w_up, w["up"][lo:hi])
    put(layer.w_down, w["down"][lo:hi])
    if shared:
        put(layer.shared_gate, w["sg"])
        put(layer.shared_up, w["su"])
        put(layer.shared_down, w["sd"])


def _uncut(cfg, w, u):
    """The whole layer by the reference: every published expert held."""
    whole = dict(cfg, num_experts_held=cfg["num_experts"],
                 experts_held_from=0)
    p = {"moe.router.w": w["router"], "moe.shared.gate.w": w["sg"],
         "moe.shared.up.w": w["su"], "moe.shared.down.w": w["sd"],
         "moe.gate.w": w["gate"], "moe.up.w": w["up"],
         "moe.down.w": w["down"]}
    p = {n: jnp.asarray(a, jnp.float32) for n, a in p.items()}
    with jax.default_matmul_precision("highest"):
        return REF._experts(u, p, jnp.asarray(w["bias"], jnp.float32),
                            whole)


@pytest.mark.parametrize("shares", [16, 4, 1])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(shares):
    """Each share routes over all the experts and computes its own; the
    shared expert is what every chip computes alike, so it is counted
    once."""
    cfg = CFG
    w = _whole_layer(cfg)
    u = jnp.asarray(onp.random.RandomState(5).randn(24, cfg["hidden_size"]),
                    jnp.float32)
    want, load = _uncut(cfg, w, u)
    per = cfg["num_experts"] // shares
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for s in range(shares):
            lo, hi = s * per, (s + 1) * per
            layer = _layer(cfg, (lo, hi), rows_bound=24 * 4,
                           shared=(s == 0))
            _load(layer, w, lo, hi, shared=(s == 0))
            with mx.autograd.record(train_mode=True):
                total = total + layer(mx.np.array(u)[None])._data[0]
            onp.testing.assert_array_equal(
                layer.expert_load.data().asnumpy(), load)
            assert int(layer.rows_over.data().asnumpy()[0]) == 0
    onp.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


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
    layer = _layer(cfg, (0, 4), rows_bound=bound, shared=False)
    _load(layer, w, 0, 4, shared=False)
    with jax.default_matmul_precision("highest"), \
            mx.autograd.record(train_mode=True):
        got = layer(mx.np.array(u)[None])._data[0]
    load = layer.expert_load.data().asnumpy()
    # all scores tie at sigmoid(0): the bias alone selects 0 and 9
    assert load[0] == tokens and load[9] == tokens and load.sum() == 2 * tokens
    held_rows = tokens          # expert 0's; expert 9 is on another chip
    over = int(layer.rows_over.data().asnumpy()[0])
    assert over == max(held_rows - bound, 0)
    whole = dict(cfg, num_experts_held=4, experts_held_from=0)
    p = {"moe.router.w": w["router"], "moe.gate.w": w["gate"][:4],
         "moe.up.w": w["up"][:4], "moe.down.w": w["down"][:4],
         "moe.shared.gate.w": 0 * w["sg"], "moe.shared.up.w": w["su"],
         "moe.shared.down.w": w["sd"]}
    p = {n: jnp.asarray(a, jnp.float32) for n, a in p.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = REF._experts(u, p, jnp.asarray(w["bias"], jnp.float32),
                               whole)
    kept = min(held_rows, bound)        # rows are in token order
    onp.testing.assert_allclose(got[:kept], want[:kept], atol=2e-5,
                                rtol=2e-5)
    assert not onp.asarray(got[kept:]).any()    # left out, and counted


def test_counts_accumulate_only_in_training_calls():
    cfg = CFG
    layer = _layer(cfg, (0, 4), rows_bound=64)
    _load(layer, _whole_layer(cfg), 0, 4)
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
    want = onp.stack([onp.asarray(REF._rope(jnp.asarray(xi).reshape(5, 4, 8),
                                            10000.0)) for xi in x])
    onp.testing.assert_allclose(rot.reshape(2, 5, 4, 8), want, atol=1e-6)
    # position 0 is not rotated; a rotation keeps every pair's length
    onp.testing.assert_allclose(rot[:, 0], x[:, 0], atol=1e-7)
    onp.testing.assert_allclose(
        (rot.reshape(2, 5, 4, 2, 4) ** 2).sum(3),
        (x.reshape(2, 5, 4, 2, 4) ** 2).sum(3), rtol=1e-5)


def test_amp_keeps_norms_and_router_in_float32():
    """Under mx.amp bf16 the norms are fp32 ops and the router never
    sees bf16: the selection equals the float32 reference's even where
    the expert products run in bf16."""
    cfg = CFG
    w = _whole_layer(cfg)
    u = jnp.asarray(onp.random.RandomState(5).randn(32, cfg["hidden_size"]),
                    jnp.float32)
    _, load = _uncut(cfg, w, u)
    layer = _layer(cfg, (0, 4), rows_bound=128)
    _load(layer, w, 0, 4)
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


def _loss(logits, labels):
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    return jnp.mean(sparse_softmax_xent(logits, labels))


def test_sharded_train_step_carries_the_counts_in_aux():
    """Through ShardedTrainStep the counts accumulate in ``step.aux``
    over updates, ``expert_bias`` stays as it was, and the family finds
    the counts of the step round the net it built — and nothing once
    that step is gone."""
    cfg = CFG
    net = FAMILY.build_net(cfg, FAMILY.make_weights(cfg, 1))
    mesh = MeshConfig(dp=1)
    step = ShardedTrainStep(
        net, _loss, mx.optimizer.create("adam", learning_rate=1e-3), mesh,
        batch_specs=mesh.batch_specs(2, 2), n_labels=1)
    bias0 = {n: onp.asarray(a) for n, a in step.aux.items()
             if n.endswith("expert_bias")}
    x, y = _tokens(cfg)
    for updates in (1, 2):
        step(x, y)
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
    import gc
    gc.collect()
    assert FAMILY.step_counts() == {}


def test_scopes_of_the_afmoe_block_do_not_grow_with_depth(monkeypatch):
    """``mx.attn`` once a layer, ``mx.moe`` / ``mx.moe.route`` /
    ``mx.moe.experts`` once an expert layer, whatever the depth."""
    import collections
    from jax._src import source_info_util
    entered = collections.Counter()
    real = source_info_util.ExtendNameStackContextManager.__enter__

    def counting(self):
        if self.name.startswith("mx"):
            entered[self.name] += 1
        return real(self)

    monkeypatch.setattr(source_info_util.ExtendNameStackContextManager,
                        "__enter__", counting)
    for layers in (2, 4):
        cfg = dict(CFG, layer_types=["sliding_attention"] * layers)
        net = FAMILY.build_net(cfg, FAMILY.make_weights(cfg, 1))
        mesh = MeshConfig(dp=1)
        step = ShardedTrainStep(
            net, _loss, mx.optimizer.create("adam", learning_rate=1e-3),
            mesh, batch_specs=mesh.batch_specs(2, 2), n_labels=1)
        x, y = _tokens(cfg)
        entered.clear()
        text = step.lower(x, y).as_text(debug_info=True)
        assert dict(entered) == {
            "mx.fwd": 1, "mx.optimizer": 1, "mx.attn": layers,
            "mx.moe": layers - 1, "mx.moe.route": layers - 1,
            "mx.moe.experts": layers - 1}
        assert "mx.moe.experts" in text and "mx.moe.route" in text


def test_rows_bound_counter_once_a_traced_call():
    from mxnet_tpu import telemetry
    cfg = CFG
    layer = _layer(cfg, (0, 4), rows_bound=48)
    u = mx.np.array(onp.zeros((1, 8, cfg["hidden_size"]), onp.float32))
    telemetry.enable()
    telemetry.reset()
    try:
        layer(u)
        got = telemetry.counters("moe.rows_bound_total")
    finally:
        telemetry.enable(False)
    assert got == {"moe.rows_bound_total": 48}
    assert telemetry.CATALOG["moe.rows_bound_total"][0] == "counter"
