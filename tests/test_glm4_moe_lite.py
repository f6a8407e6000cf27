"""The glm4_moe_lite decoder family (gluon/model_zoo/glm4_moe_lite.py) and
what it is built from — ``nn.LatentAttention`` on the XLA composition and
on the three flash kernels at a head width of 256, sigmoid-routed
``nn.RoutedExperts`` with a shared expert, the multi-token-prediction
depth — against the benchmark's plain reference
(chipbench/reference/glm4_moe_lite.py, importing nothing of the program),
on seeded random weights at small sizes on the CPU.  What the families'
tests share is ``tests/family_harness.py``.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu import functional
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import glm4_moe_lite as zoo
from mxnet_tpu.ops.attention import _reference_attention
from mxnet_tpu.ops.pallas import flash_attention as F

REF, FAMILY, FLOPS = H.load("glm4_moe_lite")
_weights = functools.partial(H.weights, "glm4_moe_lite")

CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 10,
    "qk_nope_head_dim": 6, "qk_rope_head_dim": 4, "v_head_dim": 10,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "num_experts_held": 4, "experts_held_from": 0, "rows_bound": 160,
    "vocab_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 0, "mtp_loss_weight": 0.3,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "routed_scaling_factor": 1.8,
    "published": {"num_hidden_layers": 47},
}
SIZES = {
    "small": CFG,
    "mtp": dict(CFG, num_nextn_predict_layers=1),
    # other experts held, two leading dense layers, the second depth
    "other-share-mtp": dict(CFG, experts_held_from=8, num_hidden_layers=4,
                            first_k_dense_replace=2,
                            num_nextn_predict_layers=1),
}
#: the published head: 192 | 64 and 256, two heads of it
WIDE = dict(CFG, num_attention_heads=2, qk_nope_head_dim=192,
            qk_rope_head_dim=64, v_head_dim=256)


_tokens = functools.partial(H.tokens, seq=20)


# ---- nn.LatentAttention against the reference's equations ----------------

_ATTN_PARAMS = {n: p.split("attention.")[1]
                for n, p in FAMILY.PROGRAM_NAMES.items()
                if n.startswith("attn.")}


def _param(layer, path):
    for part in path.split("."):
        layer = getattr(layer, part)
    return layer


def _latent_attention(cfg, seed=2, scale=4.0):
    """The layer and the reference's leaves of layer 0, the same values
    (matrices times ``scale``: scores that are not flat)."""
    one = dict(cfg, num_hidden_layers=1, first_k_dense_replace=1)
    w = _weights(one, seed)
    p = {n: onp.asarray(w[n][0]) * (scale if n.endswith(".w") else 1.0)
         for n in _ATTN_PARAMS}
    layer = nn.LatentAttention(
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"],
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"])
    layer.initialize()
    for n, pname in _ATTN_PARAMS.items():
        H.put(_param(layer, pname), p[n])
    return layer, {n: jnp.asarray(a, jnp.float32) for n, a in p.items()}


def _on_the_kernels(monkeypatch, block):
    """``multi_head_attention`` as on a TPU, its kernels interpreted in
    ``block``-sized tiles; returns the list of operand shapes seen."""
    from mxnet_tpu import runtime
    from mxnet_tpu.ops import attention
    seen, real = [], F.flash_attention

    def flash(q, k, v, causal=False, window=None):
        seen.append((q.shape, k.shape, v.shape))
        return real(q, k, v, causal=causal, window=window, interpret=True,
                    block_q=block, block_k=block, bwd_block_q=block,
                    bwd_block_k=block)

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ_CAUSAL", 8)
    monkeypatch.setattr(F, "flash_attention", flash, raising=True)
    return seen


@pytest.mark.parametrize("route,cfg,seq", [
    ("xla", CFG, 12), ("xla", WIDE, 24),
    ("kernels", CFG, 16), ("kernels", WIDE, 48)],
    ids=["xla-small", "xla-256", "kernels-small", "kernels-256"])
def test_latent_attention_and_every_gradient_against_the_reference(
        route, cfg, seq, monkeypatch):
    """Output, the input's gradient and all seven leaves', on the XLA
    composition and on the three flash kernels (interpret mode), at a
    small head and at the published 192 | 64 | 256."""
    layer, p = _latent_attention(cfg)
    u = onp.random.RandomState(5).randn(2, seq, cfg["hidden_size"]) \
        .astype(onp.float32)
    ct = onp.random.RandomState(6).randn(2, seq, cfg["hidden_size"]) \
        .astype(onp.float32)

    def plain(x, p_):
        out = jnp.stack([REF._attention(xi, p_, cfg) for xi in x])
        return jnp.sum(out * ct), out

    params = functional.param_arrays(layer)

    def program(x, p_):
        out = functional.functional_call(layer, p_, x, train=True)[0]
        return jnp.sum(out * ct), out

    seen = _on_the_kernels(monkeypatch, 16) if route == "kernels" else None
    (_, want), (dx, dp) = H.traced(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True), jnp.asarray(u), p)
    (_, out), (dx_got, dp_got) = H.traced(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True), jnp.asarray(u), params)
    if seen is not None:
        heads = cfg["num_attention_heads"]
        width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        # one key and one value head a query head, all of one width
        assert seen and set(seen[0]) == {(2, heads, seq, width)}
    close = dict(atol=3e-5, rtol=3e-4)
    onp.testing.assert_allclose(out, want, **close)
    onp.testing.assert_allclose(dx_got, dx, **close)
    for n, pname in _ATTN_PARAMS.items():
        onp.testing.assert_allclose(dp_got[pname], dp[n], err_msg=n, **close)


def test_latent_attention_has_the_latent_leaves_and_no_other():
    """Two down-projections with a norm each, two up-projections, the
    output projection: ``W_kva`` makes the latent and the **one** rotary
    key, ``W_kvb`` each head's ``nope`` key and its value — no per-head
    rotary key exists to be learned."""
    layer, _ = _latent_attention(CFG)
    assert sorted(layer.collect_params()) == [
        "kv_a_norm.gamma", "kv_a_proj.weight", "kv_b_proj.weight",
        "out_proj.weight", "q_a_norm.gamma", "q_a_proj.weight",
        "q_b_proj.weight"]
    assert layer.kv_a_proj.weight.shape == (10 + 4, 32)
    assert layer.kv_b_proj.weight.shape == (4 * (6 + 10), 10)
    assert layer.q_b_proj.weight.shape == (4 * (6 + 4), 12)
    assert layer.out_proj.weight.shape == (32, 4 * 10)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["fp32", "bf16"])
def test_flash_kernels_at_head_width_256(dtype, tol):
    """Forward, dK/dV and dQ at the width the family brings, unpadded,
    against the XLA composition (interpret mode)."""
    rs = onp.random.RandomState(0)
    b, h, s, d = 1, 2, 96, 256
    q, k, v = (jnp.asarray(rs.randn(b, h, s, d), dtype) for _ in range(3))
    w = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)

    def flash(q, k, v):
        return F.flash_attention(q, k, v, causal=True, interpret=True,
                                 block_q=32, block_k=32, bwd_block_q=32,
                                 bwd_block_k=32)

    def reference(q, k, v):
        def merge(t):
            return t.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
                b, s, h * d)
        out = _reference_attention(merge(q), merge(k), merge(v), h,
                                   causal=True)
        return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    out, grads = H.out_and_vjp(flash, w, q, k, v)
    ref, ref_grads = H.out_and_vjp(reference, w, q, k, v)
    assert out.dtype == dtype and out.shape == q.shape
    onp.testing.assert_allclose(out.astype(jnp.float32), ref, atol=tol,
                                rtol=tol)
    for g, r in zip(grads, ref_grads):
        scale = float(jnp.max(jnp.abs(r)))
        onp.testing.assert_allclose(g.astype(jnp.float32) / scale,
                                    r / scale, atol=tol)


@pytest.mark.parametrize("who", ["flash_attention", "LatentAttention"])
def test_unequal_query_and_value_widths_are_refused_by_name(who):
    """A sibling's 192-wide keys beside 128-wide values: the kernels take
    one head width, and say so rather than fail in a reshape."""
    if who == "LatentAttention":
        with pytest.raises(ValueError, match="one head width"):
            nn.LatentAttention(32, 4, 12, 10, qk_nope_head_dim=128,
                               qk_rope_head_dim=64, v_head_dim=128)
        return
    q = k = jnp.zeros((1, 2, 16, 192), jnp.float32)
    with pytest.raises(ValueError, match="one head width"):
        F.flash_attention(q, k, jnp.zeros((1, 2, 16, 128), jnp.float32),
                          causal=True, interpret=True)


# ---- the expert layer's shares -------------------------------------------

@pytest.fixture(scope="module")
def uncut():
    """All of one expert layer's weights (every published expert) and 24
    tokens through the whole layer by the reference, once for the three
    cuts."""
    cfg = CFG
    w = H.whole_experts(cfg["hidden_size"], cfg["moe_intermediate_size"],
                        cfg["n_routed_experts"],
                        shared=cfg["moe_intermediate_size"])
    u = H.rows(24, cfg["hidden_size"])
    return (w, u) + tuple(H.uncut("glm4_moe_lite", cfg, w, u))


@pytest.mark.parametrize("shares", [8, 2, 1])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(shares,
                                                                 uncut):
    """Eight chips share a layer in the deployment the cell stands for:
    each routes over all 64 (here 16) experts and computes its own; the
    shared expert is what every chip computes alike, so it is counted
    once; the sum is what the uncut reference gives."""
    H.assert_shares_add_up(uncut, shares, CFG["num_experts_per_tok"],
                           route_scale=CFG["routed_scaling_factor"])


# ---- the blocks that were there trace as they did ------------------------

@pytest.mark.parametrize("name", list(H.AS_BEFORE))
def test_the_blocks_that_were_there_trace_what_they_traced(name):
    """``nn.LatentAttention`` and the family change nothing of
    ``RoutedExperts`` and ``GroupedQueryAttention``: forward and backward
    they trace to the jaxpr files of ``tests/data``, so the four other
    one-chip cells' step programs are the parent's."""
    got, want = H.as_before(name)
    assert got == want


# ---- the zoo model -------------------------------------------------------

def _program_loss_and_grads(net, x, y, mtp_weight=0.3):
    return H.program_loss_and_grads(
        net, lambda out, y_: zoo.next_token_loss(out, y_, mtp_weight), x, y)


@pytest.mark.parametrize("size", list(SIZES))
def test_zoo_model_loss_gradients_and_counts_against_the_reference(size):
    cfg = SIZES[size]
    weights = _weights(cfg, 7)
    net = FAMILY.build_net(cfg, weights)
    x, y = _tokens(cfg)
    assert all(n.endswith((".expert_bias", ".expert_load", ".rows_over"))
               for n in functional.split_params(net)[1])
    params, bias, mtp_bias = REF.split_biases(weights)
    n_layer = cfg["num_hidden_layers"]
    mutated, loads, _ = H.against_the_reference(
        "glm4_moe_lite", net,
        lambda out, y_: zoo.next_token_loss(out, y_, 0.3),
        lambda p, xs, ys: REF.sequence_loss_sum(p, bias, xs, ys, cfg,
                                                mtp_bias), params, x, y,
        n_layer)
    counts = FAMILY.stack_program_tree(mutated, n_layer)
    onp.testing.assert_array_equal(counts[FAMILY.LOAD], loads)
    assert counts[FAMILY.LOAD].sum() \
        == (n_layer - cfg["first_k_dense_replace"]) * x.size \
        * cfg["num_experts_per_tok"]
    assert not counts[FAMILY.ROWS_OVER].any()


def test_without_the_second_depth_no_leaf_of_it_exists():
    net = FAMILY.build_net(CFG, _weights(CFG, 1))
    x, _ = _tokens(CFG)
    out = H.forward(net, x)
    assert out.shape == (2, 20, CFG["vocab_size"])      # logits alone
    assert not any("mtp" in n for n in net.collect_params())
    assert not any(n.startswith(FAMILY.MTP)
                   for n in FAMILY.leaf_shapes(CFG))
    with pytest.raises(ValueError, match="none or one"):
        zoo.Glm4MoeLiteForCausalLM(backbone=net.backbone,
                                   num_nextn_predict_layers=2)


def test_the_second_depth_shares_the_embedding_and_the_head():
    """One embedding and one head in the net; both receive the second
    term's gradient (weight 0 against 0.3) and the backbone's layers do
    too, through ``h``; the last position, which has no next token,
    touches nothing."""
    cfg = SIZES["mtp"]
    weights = _weights(cfg, 3)
    net = FAMILY.build_net(cfg, weights)
    names = list(net.collect_params())
    assert sum(n.endswith("word_embed.weight") for n in names) == 1
    assert sum(n.endswith("lm_head.weight") for n in names) == 1
    assert {n for n in names if n.startswith("mtp.") and "layer" not in n} \
        == {"mtp.embed_norm.gamma", "mtp.hidden_norm.gamma",
            "mtp.eh_proj.weight", "mtp.final_norm.gamma"}
    assert net.mtp.eh_proj.weight.shape == (32, 64)
    x, y = _tokens(cfg)
    logits, second = H.forward(net, x)
    assert logits.shape == second.shape == (2, 20, cfg["vocab_size"])
    (both, _), g_both = _program_loss_and_grads(net, x, y)
    (main, _), g_main = _program_loss_and_grads(net, x, y, 0.0)
    assert float(both) > float(main)
    for name in ("backbone.word_embed.weight", "lm_head.weight",
                 "backbone.layer0.attention.q_a_proj.weight"):
        assert float(jnp.max(jnp.abs(g_both[name] - g_main[name]))) \
            > 0.01 * float(jnp.max(jnp.abs(g_main[name]))), name
    assert float(jnp.max(jnp.abs(g_main["mtp.eh_proj.weight"]))) == 0.0
    # the last position has no next token and no target: whatever the
    # second depth says there, the loss does not hear it
    loss = jax.jit(lambda *out: zoo.next_token_loss(out, y))
    heard = float(loss(logits, second))
    assert float(loss(logits, second.at[:, -1, 0].add(50.0))) == heard
    assert float(loss(logits, second.at[:, 0, 0].add(50.0))) > heard + 0.1


@functools.lru_cache(maxsize=None)
def _updates(size):
    """Three updates by the step and by the reference, once a size."""
    cfg = SIZES[size]
    return H.three_updates(
        "glm4_moe_lite", cfg, 11, [_tokens(cfg, seed=s) for s in (4, 5, 6)],
        cfg["num_hidden_layers"])


@pytest.mark.parametrize("size", ["small", "mtp"])
def test_the_sharded_step_has_the_second_depths_leaves(size):
    """The step's side of the three updates: the first gradient reaches
    the second depth's leaves where there is one, and every assignment
    is counted."""
    cfg, run = SIZES[size], _updates(size)
    stacked = FAMILY.stack_program_tree(run.first, cfg["num_hidden_layers"])
    assert ("mtp.eh.w" in stacked) == bool(cfg["num_nextn_predict_layers"])
    assert not run.last_counts[FAMILY.ROWS_OVER].any()


def test_eager_hybridized_and_sharded_step_agree():
    """The same seeded net three ways — eager under autograd (the
    family's one eager case), hybridized, and the first loss of its
    ``ShardedTrainStep``."""
    eager, hybrid = _updates("small").eager_and_hybridized
    assert abs(eager - hybrid) < 1e-6
    assert abs(_updates("small").losses[0] - eager) < 1e-5


@pytest.mark.parametrize("size", ["small", "mtp"])
def test_hybridized_and_sharded_step_agree(size):
    """The seeded net hybridized and the first loss of its
    ``ShardedTrainStep``, without and with the second prediction depth."""
    run = _updates(size)
    if size == "small":     # the eager case's net, hybridized
        hybrid = run.eager_and_hybridized[1]
    else:
        cfg = SIZES[size]
        net = FAMILY.build_net(cfg, _weights(cfg, 11))
        net.hybridize()
        x, y = _tokens(cfg, seed=4)
        with jax.default_matmul_precision("highest"), \
                mx.autograd.record(train_mode=True):
            out = net(mx.np.array(x))
        hybrid = float(zoo.next_token_loss(tuple(o._data for o in out), y))
    assert abs(run.losses[0] - hybrid) < 1e-5


@pytest.mark.parametrize("size", ["small", "mtp"])
def test_the_sharded_steps_three_updates_follow_the_reference(size):
    """The step's three updates of loss, first gradient and Adam against
    the reference's, without and with the second prediction depth."""
    run = _updates(size)
    onp.testing.assert_allclose(run.losses, run.ref["losses"], atol=2e-5)
    assert REF.worst_leaf(run.g_gaps)[0] < 2e-3, REF.worst_leaf(run.g_gaps)
    assert REF.worst_leaf(run.c_gaps, skip=run.dead)[0] < 2e-3, \
        REF.worst_leaf(run.c_gaps, skip=run.dead)
    assert all(v == 0 for n, v in run.c_gaps.items() if "moe." in n
               and ("load" in n or "rows_over" in n))


def test_scopes_once_a_layer_and_on_the_backward_pass():
    """``mx.mla`` and ``mx.mla.assemble`` once a latent attention,
    ``mx.attn`` inside ``mx.mla``, ``mx.mtp`` once round the second
    depth (whose layer enters the others once more); all of them carried
    by the backward pass."""
    for cfg in (SIZES["small"], SIZES["mtp"]):
        mtp = cfg["num_nextn_predict_layers"]
        net = FAMILY.build_net(cfg, _weights(cfg, 1))
        text, entered = H.lowered_scopes(net, FAMILY.loss_fn, *_tokens(cfg))
        layers = cfg["num_hidden_layers"]
        assert entered["mx.mla"] == entered["mx.mla.assemble"] \
            == entered["mx.attn"] == layers + mtp
        assert entered["mx.moe"] \
            == layers - cfg["first_k_dense_replace"] + mtp
        assert entered["mx.mtp"] == mtp
        scopes = ["mx.mla/mx.mla.assemble", "mx.mla/mx.attn"] \
            + (["mx.mtp/mx.mla", "mx.mtp/mx.moe"] if mtp else [])
        for scope in scopes:
            assert H.on_the_backward_pass(text, scope), scope
        assert ("mx.mtp" in text) == bool(mtp)


def test_amp_hands_the_kernels_bf16_and_keeps_the_norms_float32():
    """Under ``mx.amp`` the five projections and the core take bf16
    operands (K is assembled in bf16), the two latent norms and the
    rotary angles are float32, and the result stays close to the float32
    layer's."""
    layer, _ = _latent_attention(CFG)
    u = mx.np.array(onp.random.RandomState(0).randn(2, 12, 32)
                    .astype(onp.float32))
    want = layer(u).asnumpy()
    mx.amp.init("bfloat16")
    try:
        got = layer(u)
        tr, _ = functional.split_params(layer)
        text = str(jax.make_jaxpr(lambda p, x: functional.functional_call(
            layer, p, x, train=True)[0])(tr, u._data))
    finally:
        mx.amp._deactivate()
    assert str(got.dtype) == "bfloat16"
    onp.testing.assert_allclose(got.asnumpy().astype(onp.float32), want,
                                atol=0.05, rtol=0.05)
    assert re.search(r"bf16\[2,12,4,10\] = concatenate", text)   # q and k
    assert not re.search(r"bf16\[[0-9,]*\] = (rsqrt|cos|sin) ", text)
    assert len(re.findall(r"rsqrt", text)) == 2


def test_the_configuration_file_of_the_cell():
    """Every width as published, the four reduced keys and no other, the
    catalog's numbers under their keys, the count from the family's
    shapes, ISSUE 40's FLOPs."""
    cfg = H.config("glm-4.7-flash")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size", "num_nextn_predict_layers"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 20,
        "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "intermediate_size": 10240, "moe_intermediate_size": 1536,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
        "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 202752, "model_type": "glm4_moe_lite",
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "partial_rotary_factor": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"]["num_hidden_layers"] == 47
    assert cfg["published"]["num_nextn_predict_layers"] == 1
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]) \
        == (5, 0)
    assert len(cfg["layer_types"]) == cfg["n_layer"] == 5
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"]
    assert cfg["num_experts_held"] * 8 == cfg["n_routed_experts"] \
        == cfg["published"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 154880
    assert cfg["rows_bound"] == 2 * 8192 * 4 * 8 // 64
    assert FAMILY.n_params(cfg) == cfg["parameters"] == 591_294_720
    assert round(FLOPS.forward_flops_per_token(cfg, 8192)) == 956_432_384
    assert FLOPS.expected_rows_per_token(cfg) == 0.5
    tiny = dict(cfg, **cfg["tiny"])
    assert tiny["qk_nope_head_dim"] + tiny["qk_rope_head_dim"] \
        == tiny["v_head_dim"]
