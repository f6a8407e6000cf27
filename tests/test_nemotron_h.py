"""The nemotron_h decoder family (gluon/model_zoo/nemotron_h.py) and what
it is built from — the chunked state-space scan, the causal depthwise
convolution and the gated grouped norm of ``ops/ssm.py``,
``nn.Mamba2Mixer``, relu^2 ``nn.RoutedExperts`` and
``nn.GroupedQueryAttention`` without q/k norms — against the benchmark's
plain reference (chipbench/reference/nemotron_h.py: the recurrence token
by token, importing nothing of the program), on seeded random weights at
small sizes on the CPU.  What the families' tests share is
``tests/family_harness.py``.
"""
import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu import functional
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import ssm

REF, FAMILY, FLOPS = H.load("nemotron_h")
_weights = functools.partial(H.weights, "nemotron_h")

CFG = {
    "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_num_heads": 4, "mamba_head_dim": 6,
    "n_groups": 2, "ssm_state_size": 5, "chunk_size": 8, "conv_kernel": 4,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "num_experts_held": 4,
    "experts_held_from": 0, "rows_bound": 128, "vocab_size": 64,
    "hybrid_override_pattern": "ME*E", "layer_norm_epsilon": 1e-5,
    "routed_scaling_factor": 2.5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "published": {"num_hidden_layers": 52},
}
SIZES = {
    "small": CFG,
    # other experts held, two mixers running, attention first
    "other-share": dict(CFG, experts_held_from=8,
                        hybrid_override_pattern="*MMEM"),
}


_tokens = functools.partial(H.tokens, seq=20)


# ---- ops/ssm.py against the recurrence, token by token ------------------

def _scan_inputs(batch, seq, seed=0, heads=4, dim=3, groups=2, state=5):
    rs = onp.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    return (f(batch, seq, heads, dim),
            jnp.asarray(rs.uniform(0.01, 0.5, (batch, seq, heads)),
                        jnp.float32),
            -jnp.asarray(rs.uniform(1, 4, (heads,)), jnp.float32),
            f(batch, seq, groups, state), f(batch, seq, groups, state),
            f(heads))


def _recurrence(x, dt, a, b_mat, c_mat, d_skip):
    per = x.shape[2] // b_mat.shape[2]
    one = lambda x_, dt_, b_, c_: REF.recurrence(  # noqa: E731
        x_, dt_, a, jnp.repeat(b_, per, axis=1), jnp.repeat(c_, per, axis=1))
    return jax.vmap(one)(x, dt, b_mat, c_mat) + d_skip[:, None] * x


@pytest.mark.parametrize("batch,seq,chunk", [
    (1, 32, 8), (2, 32, 8),     # the chunk divides the sequence
    (1, 29, 8), (2, 29, 8),     # it does not: the last chunk is padded
    (1, 5, 8),                  # shorter than one chunk
    (2, 24, 24)])               # one chunk: no state is carried
def test_ssd_scan_values_and_every_gradient(batch, seq, chunk):
    args = _scan_inputs(batch, seq)
    ct = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                     jnp.float32)
    got, g_got = H.value_and_grads(
        lambda *a: ssm.ssd_scan(*a, chunk=chunk), args, ct)
    want, g_want = H.value_and_grads(_recurrence, args, ct)
    onp.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), g_got, g_want):
        onp.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4,
                                    err_msg=name)


def test_the_pad_of_a_ragged_sequence_contributes_nothing():
    """29 tokens at chunk 8 are the first 29 of 32 whatever follows, and
    no chunk size changes a value."""
    long = _scan_inputs(2, 32, seed=3)
    short = tuple(t[:, :29] if t.ndim > 1 else t for t in long)
    want = H.traced(lambda *a: ssm.ssd_scan(*a, chunk=8), *long)[:, :29]
    for chunk in (8, 16, 5, 128):
        onp.testing.assert_allclose(
            H.traced(lambda *a: ssm.ssd_scan(*a, chunk=chunk), *short),
            want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("batch,seq", [(1, 9), (2, 3)])
@pytest.mark.parametrize("activation", [None, "silu"])
def test_causal_conv1d_values_and_every_gradient(batch, seq, activation):
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, seq, 6), jnp.float32)
    w = jnp.asarray(rs.randn(6, 4), jnp.float32)
    b = jnp.asarray(rs.randn(6), jnp.float32)
    act = jax.nn.silu if activation else (lambda t: t)

    def plain(x_, w_, b_):
        return jnp.stack([act(REF._conv(xi, w_, b_)) for xi in x_])

    ct = jnp.asarray(rs.randn(batch, seq, 6), jnp.float32)
    got, g_got = H.value_and_grads(
        lambda *a: ssm.causal_conv1d(*a, activation), (x, w, b), ct)
    want, g_want = H.value_and_grads(plain, (x, w, b), ct)
    onp.testing.assert_allclose(got, want, atol=1e-6)
    # causal: an output sees its own input through the last tap only
    conv = jax.jit(ssm.causal_conv1d)
    assert float(conv(x.at[:, -1].add(1.0), w, b)[0, 0, 0]) \
        == float(conv(x, w, b)[0, 0, 0])
    for a, c in zip(g_got, g_want):
        onp.testing.assert_allclose(a, c, atol=1e-5, rtol=1e-5)


def test_gated_rms_norm_gates_first_and_norms_by_group():
    rs = onp.random.RandomState(0)
    y, z = (jnp.asarray(rs.randn(2, 5, 12), jnp.float32) for _ in "yz")
    w = jnp.asarray(1 + 0.1 * rs.randn(12), jnp.float32)
    gated = onp.asarray(y * jax.nn.silu(z)).reshape(2, 5, 3, 4)
    want = (gated / onp.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 12) * onp.asarray(w)
    onp.testing.assert_allclose(
        jax.jit(lambda *a: ssm.gated_rms_norm(*a, 3, 1e-5))(y, z, w), want,
        atol=1e-6)


def test_an_unknown_activation_or_grouping_is_refused():
    x, dt, a, b_mat, c_mat, d = _scan_inputs(1, 8, heads=3)
    with pytest.raises(ValueError, match="do not group"):
        ssm.ssd_scan(x, dt, a, b_mat, c_mat, d)
    with pytest.raises(ValueError, match="neither"):
        ssm.causal_conv1d(jnp.zeros((1, 4, 2)), jnp.zeros((2, 4)),
                          jnp.zeros(2), "gelu")
    with pytest.raises(ValueError, match="neither"):
        nn.RoutedExperts(8, 4, 4, 2, held=(0, 2), rows_bound=8,
                         activation="gelu")
    with pytest.raises(ValueError, match="known: M"):
        mx.gluon.model_zoo.nemotron_h.NemotronHModel(
            8, 8, "MX", 2, 1, 4, 2, 4, 1, 4, 4, 2, 4, 4, (0, 2), 8)


# ---- the three sublayers against plain jax.numpy -------------------------

def _layer_leaves(cfg, kind, seed=2):
    """One sublayer's leaves as the generator makes them (layer 0 of its
    kind), as float32 host arrays under the reference's names."""
    one = dict(cfg, hybrid_override_pattern=kind)
    w = _weights(one, seed)
    return {n: onp.asarray(w[n][0]) for n in REF.KIND_LEAVES[kind]}


def test_mamba2_mixer_against_the_reference():
    cfg = CFG
    p = _layer_leaves(cfg, "M")
    # D = 1 and a zero-ish start would hide a swapped leaf
    p["ssm.D"] = 1 + 0.3 * onp.random.RandomState(0).randn(4)
    layer = nn.Mamba2Mixer(cfg["hidden_size"], cfg["mamba_num_heads"],
                           cfg["mamba_head_dim"], cfg["n_groups"],
                           cfg["ssm_state_size"], chunk_size=8)
    layer.initialize()
    for n, pname in FAMILY.PROGRAM_NAMES.items():
        if n in REF.KIND_LEAVES["M"]:
            param = layer
            for part in pname.split("mixer.")[1].split("."):
                param = getattr(param, part)
            H.put(param, p[n])
    u = onp.random.RandomState(5).randn(2, 21, cfg["hidden_size"]) \
        .astype(onp.float32)
    p = {n: jnp.asarray(a, jnp.float32) for n, a in p.items()}
    want = H.traced(lambda u_: jnp.stack([REF._mixer(ui, p, cfg)
                                          for ui in u_]), jnp.asarray(u))
    onp.testing.assert_allclose(H.forward(layer, u), want, atol=2e-5,
                                rtol=2e-4)


def test_the_mixer_starts_as_mamba2_does():
    layer = nn.Mamba2Mixer(32, 8, 4, 2, 5, time_step_min=0.001,
                           time_step_max=0.1)
    layer.initialize()
    dt = onp.log1p(onp.exp(layer.dt_bias.data().asnumpy()))
    assert (dt >= 0.001 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    rate = onp.exp(layer.A_log.data().asnumpy())
    assert (rate >= 1).all() and (rate <= 16).all() and rate.std() > 0
    assert (layer.D.data().asnumpy() == 1).all()
    assert (layer.norm_gamma.data().asnumpy() == 1).all()
    assert layer.conv_weight.shape == (8 * 4 + 2 * 2 * 5, 4)
    assert not hasattr(layer.in_proj, "bias") or layer.in_proj.bias is None


def _whole_experts(cfg, seed=3):
    return H.whole_experts(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["n_routed_experts"], seed, gate=False,
        shared=cfg["moe_shared_expert_intermediate_size"])


_RELU2 = dict(route_scale=CFG["routed_scaling_factor"], activation="relu2")


@pytest.fixture(scope="module")
def uncut():
    """24 tokens through the whole layer, once for the three cuts."""
    w, u = _whole_experts(CFG), H.rows(24, CFG["hidden_size"])
    return (w, u) + tuple(H.uncut("nemotron_h", CFG, w, u))


@pytest.mark.parametrize("shares", [16, 4, 1])
def test_the_shares_of_a_relu2_expert_layer_add_up_to_the_uncut_layer(
        shares, uncut):
    """Each share routes over all the experts and computes its own; the
    shared expert is what every chip computes alike, so it is counted
    once.  A relu^2 layer has no gate matrix, routed or shared."""
    for layer in H.assert_shares_add_up(
            uncut, shares, CFG["num_experts_per_tok"], **_RELU2):
        assert not any("gate" in n for n in layer.collect_params())


@pytest.mark.parametrize("held,rows_bound,widths", [
    ((4, 8), 96, None), ((0, 16), 96, None),
    ((4, 8), 12, None), ((0, 16), 12, None),    # rows past the bound
    ((4, 8), 96, (288, 272))])   # widths the products pad to 512
def test_a_relu2_share_and_every_gradient_against_the_reference(
        held, rows_bound, widths):
    """One share of the layer — output, the input's gradient and every
    matrix's — against the reference's loop over its held experts; with
    fewer rows than the share is assigned, the rest are counted and
    dropped, and nothing else of the counts moves.  Widths over a block
    of the grouped product that it does not divide are padded with zeros
    inside it, and no value moves."""
    cfg = CFG if widths is None else dict(
        CFG, hidden_size=widths[0], moe_intermediate_size=widths[1])
    w = _whole_experts(cfg)
    if widths:
        # keep the products at the small case's size
        w = {n: a * (0.3 if n in ("up", "down", "su", "sd") else 0.35)
             for n, a in w.items()}
    lo, hi = held
    u = jnp.asarray(onp.random.RandomState(7).randn(2, 12,
                                                    cfg["hidden_size"]),
                    jnp.float32)
    part = dict(cfg, num_experts_held=hi - lo, experts_held_from=lo)
    leaves, bias = H.reference_leaves(w, lo, hi)

    def plain(x, p):
        out, load = REF._experts(x.reshape(-1, x.shape[-1]), p, bias, part)
        return jnp.sum(out * out), (out, load)

    layer = H.routed_experts(w, lo, hi, cfg["num_experts_per_tok"],
                             rows_bound, **_RELU2)
    params, aux = functional.split_params(layer)

    def program(x, p):
        out, mutated = functional.functional_call(layer, {**p, **aux}, x,
                                                  train=True)
        return jnp.sum(out * out), (out, mutated)

    (_, (want, load)), (dx, dp) = H.traced(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True), u, leaves)
    (_, (out, mutated)), (dx_got, dp_got) = H.traced(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True), u, params)
    onp.testing.assert_array_equal(mutated["expert_load"], load)
    assigned = int(load[lo:hi].sum())
    assert int(mutated["rows_over"][0]) == max(assigned - rows_bound, 0)
    if assigned > rows_bound:
        assert onp.isfinite(out).all()
        return
    close = dict(atol=3e-5, rtol=3e-5)
    onp.testing.assert_allclose(out.reshape(want.shape), want, **close)
    onp.testing.assert_allclose(dx_got, dx, **close)
    for got, name in (("router", "moe.router.w"), ("w_up", "moe.up.w"),
                      ("w_down", "moe.down.w"),
                      ("shared_up", "moe.shared.up.w"),
                      ("shared_down", "moe.shared.down.w")):
        onp.testing.assert_allclose(dp_got[got], dp[name], err_msg=name,
                                    **close)


def test_attention_without_qk_norm_against_the_reference():
    cfg = CFG
    p = _layer_leaves(cfg, "*")
    layer = nn.GroupedQueryAttention(
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], gate=False,
        qk_norm=False)
    layer.initialize()
    assert sorted(layer.collect_params()) == [
        "key_proj.weight", "out_proj.weight", "query_proj.weight",
        "value_proj.weight"]
    for n, proj in (("attn.q.w", layer.query_proj),
                    ("attn.k.w", layer.key_proj),
                    ("attn.v.w", layer.value_proj),
                    ("attn.o.w", layer.out_proj)):
        H.put(proj.weight, p[n] * 5)        # scores that are not flat
        p[n] = p[n] * 5
    u = onp.random.RandomState(5).randn(2, 12, cfg["hidden_size"]) \
        .astype(onp.float32)
    p = {n: jnp.asarray(a) for n, a in p.items()}
    want = H.traced(lambda u_: jnp.stack([REF._attention(ui, p, cfg)
                                          for ui in u_]), jnp.asarray(u))
    onp.testing.assert_allclose(H.forward(layer, u), want, atol=2e-5,
                                rtol=2e-4)


# ---- the default layers trace as they did --------------------------------

@pytest.mark.parametrize("name", list(H.AS_BEFORE))
def test_a_call_without_the_new_arguments_traces_what_it_traced(name):
    """``RoutedExperts()`` without ``activation`` and
    ``GroupedQueryAttention()`` without ``qk_norm`` trace, forward and
    backward, to the jaxpr they traced to at the parent of the PR that
    added the arguments (``tests/data/<name>.jaxpr.txt``, written there
    by ``family_harness.jaxpr_text``): the two expert cells' step
    programs are then the parent's and are found in its compile cache."""
    got, want = H.as_before(name)
    assert got == want


# ---- the zoo model -------------------------------------------------------

@pytest.mark.parametrize("size", list(SIZES))
def test_zoo_model_loss_gradients_and_counts_against_the_reference(size):
    cfg = SIZES[size]
    weights = _weights(cfg, 7)
    net = FAMILY.build_net(cfg, weights)
    x, y = _tokens(cfg)
    assert all(n.endswith((".expert_bias", ".expert_load", ".rows_over"))
               for n in functional.split_params(net)[1])
    params = dict(weights)
    bias = params.pop(REF.BIAS)
    n_layer = len(cfg["hybrid_override_pattern"])
    mutated, loads, _ = H.against_the_reference(
        "nemotron_h", net, FAMILY.loss_fn,
        lambda p, xs, ys: REF.sequence_loss_sum(p, bias, xs, ys, cfg),
        params, x, y, n_layer)
    counts = FAMILY.stack_program_tree(mutated, n_layer)
    onp.testing.assert_array_equal(counts[FAMILY.LOAD], loads)
    assert counts[FAMILY.LOAD].sum() \
        == cfg["hybrid_override_pattern"].count("E") * x.size \
        * cfg["num_experts_per_tok"]
    assert not counts[FAMILY.ROWS_OVER].any()


@pytest.fixture(scope="module")
def updates():
    """Three updates by the step and by the reference, once a file."""
    return H.three_updates(
        "nemotron_h", CFG, 11, [_tokens(CFG, seed=s) for s in (4, 5, 6)],
        len(CFG["hybrid_override_pattern"]))


def test_the_sharded_step_counts_every_assignment(updates):
    """The step's side of the three updates: its expert layers' counts
    after them, as ``change_norms`` reads them."""
    counts = updates.last_counts
    assert (counts[FAMILY.LOAD].sum(axis=1)
            == 3 * 2 * 20 * CFG["num_experts_per_tok"]).all()
    assert not counts[FAMILY.ROWS_OVER].any()


def test_eager_hybridized_and_sharded_step_agree(updates):
    """The same seeded net three ways — eager under autograd (the
    family's one eager case), hybridized, and the first loss of its
    ``ShardedTrainStep``."""
    eager, hybrid = updates.eager_and_hybridized
    assert abs(eager - hybrid) < 1e-6
    assert abs(updates.losses[0] - eager) < 1e-5


def test_the_sharded_steps_three_updates_follow_the_reference(updates):
    """The step's three updates of loss, first gradient and Adam against
    the reference's."""
    run = updates
    onp.testing.assert_allclose(run.losses, run.ref["losses"], atol=2e-5)
    assert REF.worst_leaf(run.g_gaps)[0] < 2e-3, REF.worst_leaf(run.g_gaps)
    assert REF.worst_leaf(run.c_gaps, skip=run.dead)[0] < 2e-3, \
        REF.worst_leaf(run.c_gaps, skip=run.dead)
    assert all(v == 0 for n, v in run.c_gaps.items() if "moe." in n
               and ("load" in n or "rows_over" in n))


def test_amp_runs_the_products_in_bf16_and_the_state_in_float32():
    """Under ``mx.amp`` the scan's four products take bf16 operands with
    float32 accumulation, the decays' running sum, their exponentials and
    the carried state are float32, and the result stays close to the
    float32 mixer's."""
    layer = nn.Mamba2Mixer(32, 4, 8, 2, 8, chunk_size=8)
    layer.initialize()
    u = mx.np.array(onp.random.RandomState(0).randn(2, 24, 32)
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
    assert got.dtype == onp.dtype("bfloat16") or str(got.dtype) == "bfloat16"
    onp.testing.assert_allclose(got.asnumpy().astype(onp.float32), want,
                                atol=0.05, rtol=0.05)
    # bf16 operands, float32 accumulation: the scan's four products and
    # nothing else asks for an accumulation type
    assert len(re.findall(r"preferred_element_type=float32", text)) >= 4
    assert "bf16" in text
    # the cumulative sum, the exponentials and the scan's carry are f32
    assert re.search(r"f32\[[0-9,]*\] = cumsum", text)
    assert not re.search(r"bf16\[[0-9,]*\] = (cumsum|exp) ", text)
    assert re.search(r"scan\[", text)


def test_scopes_once_a_mixer_and_on_the_backward_pass():
    """``mx.ssm`` once a mixer layer whatever the depth; the scan and the
    convolution carry their scopes forward and in the backward pass
    (where they are made again)."""
    for pattern in ("ME", "MEM*M"):
        cfg = dict(CFG, hybrid_override_pattern=pattern)
        net = FAMILY.build_net(cfg, _weights(cfg, 1))
        text, entered = H.lowered_scopes(net, FAMILY.loss_fn, *_tokens(cfg))
        assert entered["mx.ssm"] == pattern.count("M")
        assert entered["mx.moe"] == pattern.count("E")
        assert entered["mx.attn"] == pattern.count("*")
        for scope in ("mx.ssm.scan", "mx.ssm.conv"):
            assert H.on_the_backward_pass(text, scope, sep='[^"]*'), scope


def test_scan_counters_once_a_traced_call():
    from mxnet_tpu import telemetry
    args = _scan_inputs(2, 29)
    _, got = H.counters("ssm.", jax.jit(
        lambda *a: ssm.ssd_scan(*a, chunk=8)), *args)
    # 2 x 29 tokens; 2 sequences x 4 chunks (the last one padded) x 4 heads
    assert got == {"ssm.scan_tokens_total": 58, "ssm.scan_chunks_total": 32}
    for name in got:
        assert telemetry.CATALOG[name][0] == "counter"


def test_no_other_family_imports_the_scan():
    """``ops/ssm.py`` is loaded where ``Mamba2Mixer.forward`` runs, not
    with the package: no other cell's set-up pays for it."""
    code = ("import sys, mxnet_tpu as mx; "
            "from mxnet_tpu.gluon.model_zoo import afmoe, keye, gpt, "
            "nemotron_h; "
            "print('mxnet_tpu.ops.ssm' in sys.modules)")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=H.REPO))
    assert out.stdout.strip() == "False", out.stderr[-2000:]


def test_the_configuration_file_of_the_cell():
    """Every width as published, the four reduced keys and no other, the
    count from the family's shapes, ISSUE 36's FLOPs."""
    cfg = H.config("nemotron-twotower-30b-a3b")
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "num_experts_held", "vocab_size"]
    published = {
        "hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "mamba_num_heads": 64,
        "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
        "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "intermediate_size": 1856, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "n_routed_experts": 128, "num_experts_per_tok": 6,
        "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["hybrid_override_pattern"] == "MEMEM*E" \
        == cfg["published"]["hybrid_override_pattern"][:7]
    assert len(cfg["layer_types"]) == cfg["n_layer"] \
        == cfg["num_hidden_layers"] == 7
    assert cfg["num_dense_layers"] == 7 - cfg["layer_types"].count("experts")
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["rows_bound"] == 2 * 8192 * 6 * 8 // 128
    assert FAMILY.n_params(cfg) == cfg["parameters"] == 528_093_120
    assert round(FLOPS.forward_flops_per_token(cfg, 8192)) == 586_731_520
    assert FLOPS.expected_rows_per_token(cfg) == 0.375
    tiny = dict(cfg, **cfg["tiny"])
    assert set(tiny["hybrid_override_pattern"]) == {"M", "E", "*"}
