"""The keye decoder family (gluon/model_zoo/keye.py) and what it is built
from — the flash kernels' ``selection`` operand, the sparse indexer's
scores, top-k selection and alignment loss (ops/sparse_index.py),
``nn.SparseIndexer`` / ``nn.IndexedAttention``, softmax routing in
``nn.RoutedExperts`` — against the benchmark's plain reference
(chipbench/reference/keye.py, which imports nothing of the program), on
seeded random weights at small sizes on the CPU.  What the families'
tests share is ``tests/family_harness.py``.
"""
import functools
import gc

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import sparse_index
from mxnet_tpu.ops.attention import _reference_attention, multi_head_attention
from mxnet_tpu.ops.pallas.flash_attention import flash_attention
from mxnet_tpu.parallel import MeshConfig

REF, FAMILY, FLOPS = H.load("keye")
_weights = functools.partial(H.weights, "keye")

CFG = {
    "hidden_size": 32, "moe_intermediate_size": 16, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 16,
    "num_experts_per_tok": 4, "num_experts_held": 4, "experts_held_from": 0,
    "rows_bound": 128, "vocab_size": 64, "num_hidden_layers": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 1e7,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "topk": 6},
}
SIZES = {
    "small": CFG,
    # other experts held, three layers, a topk as long as the sequence
    "other-share": dict(CFG, experts_held_from=8, num_hidden_layers=3,
                        sa_config=dict(CFG["sa_config"], topk=16)),
}


@pytest.mark.parametrize("size", list(SIZES))
def test_zoo_model_loss_gradients_and_counts_against_the_reference(size):
    cfg = SIZES[size]
    weights = _weights(cfg, 7)
    net = FAMILY.build_net(cfg, weights)
    x, y = H.tokens(cfg)
    n_layer = cfg["num_hidden_layers"]
    # L_lm + L_I, its gradients, and the counts, by the reference
    mutated, (loads, grids), _ = H.against_the_reference(
        "keye", net, FAMILY.loss_fn,
        lambda p, xs, ys: REF.sequence_loss_sum(p, xs, ys, cfg), weights,
        x, y, n_layer)
    counts = FAMILY.stack_program_tree(mutated, n_layer)
    onp.testing.assert_array_equal(counts[FAMILY.LOAD], loads)
    onp.testing.assert_array_equal(counts[FAMILY.GRID], grids)
    pairs = x.shape[0] * REF.selected_pairs(x.shape[1],
                                            cfg["sa_config"]["topk"])
    assert (counts[FAMILY.PAIRS] == pairs).all()
    assert (onp.asarray(grids).sum(axis=(1, 2)) == pairs).all()
    assert not counts[FAMILY.ROWS_OVER].any()


@pytest.fixture(scope="module")
def updates():
    """Three updates by the step and by the reference, once a file."""
    return H.three_updates("keye", CFG, 3, [H.tokens(CFG, seed=s)
                                            for s in (0, 1, 2)],
                           CFG["num_hidden_layers"])


def test_the_sharded_step_carries_the_last_updates_pairs(updates):
    """The step's side of the three updates: the counters hold the last
    update's values, not a sum over three."""
    pairs = 2 * REF.selected_pairs(16, CFG["sa_config"]["topk"])
    assert (updates.last_counts[FAMILY.PAIRS] == pairs).all()
    assert len(updates.losses) == 3


def test_eager_and_hybridized_agree_with_the_sharded_step(updates):
    """The family's eager case: the seeded net op by op under
    ``mx.autograd.record``, hybridized, and the first loss of its
    ``ShardedTrainStep``."""
    eager, hybrid = updates.eager_and_hybridized
    assert abs(eager - hybrid) < 1e-6
    assert abs(updates.losses[0] - eager) < 1e-5


def test_three_adam_updates_follow_the_reference(updates):
    """What the chip check compares, at a small size in float32: losses,
    first-gradient norms (from Adam's first moment), the parameters'
    change and the counts after three updates through
    ``ShardedTrainStep``."""
    run, cfg = updates, CFG
    onp.testing.assert_allclose(run.losses, run.ref["losses"], atol=2e-5)
    worst, leaf = REF.worst_leaf(run.c_gaps, skip=run.dead)
    assert worst < 2e-3, leaf
    assert {n.split("[")[0] for n in run.c_gaps} >= {
        REF.LOAD, REF.ROWS_OVER, REF.PAIRS, REF.GRID}
    # the counters hold the last update's values, not a sum over three
    pairs = 2 * REF.selected_pairs(16, cfg["sa_config"]["topk"])
    assert (run.ref["change_norms"][REF.PAIRS] == pairs).all()


# ---- the flash kernels with a selection ---------------------------------

def _random_selection(rs, b, s, topk):
    """A causal selection of ``min(t + 1, topk)`` random keys a row."""
    sel = onp.zeros((b, s, s), onp.int8)
    for i in range(b):
        for t in range(s):
            keys = rs.permutation(t + 1)[:topk]
            sel[i, t, keys] = 1
    return sel


@pytest.mark.parametrize("b,h,hk,s,d,block,topk", [
    (2, 4, 2, 64, 16, 32, 24),      # grouped heads, seq over topk
    (1, 4, 4, 70, 16, 32, 100),     # no block multiple, seq under topk
    (2, 8, 2, 50, 8, 16, 7),        # groups of four, ragged, tiny topk
    (1, 2, 1, 96, 32, 32, 33),      # one KV head, three blocks
])
def test_flash_kernels_with_a_selection_match_the_composition(
        b, h, hk, s, d, block, topk):
    """Forward, dQ and dK/dV kernels (interpret mode) with the selection
    as an operand against the XLA composition with it as a mask: values
    and gradients."""
    rs = onp.random.RandomState(s)
    q, k, v = (jnp.asarray(rs.randn(b, n, s, d), jnp.float32)
               for n in (h, hk, hk))
    sel = jnp.asarray(_random_selection(rs, b, s, topk))

    def merged(t, n):
        return t.transpose(0, 2, 1, 3).reshape(b, s, n * d)

    def kernels(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=block,
                              block_k=block, bwd_block_q=block,
                              bwd_block_k=block, interpret=True,
                              selection=sel)
        return jnp.sum(out * jnp.cos(out)), out

    def composed(q, k, v):
        out = _reference_attention(
            merged(q, h), merged(k, hk), merged(v, hk), h,
            (sel != 0)[:, None], True, None, 0.0, hk)
        out = out.reshape(b, s, h, d).transpose(0, 2, 1, 3)
        return jnp.sum(out * jnp.cos(out)), out

    (_, got), g_got = H.traced(jax.value_and_grad(
        kernels, (0, 1, 2), has_aux=True), q, k, v)
    (_, want), g_want = H.traced(jax.value_and_grad(
        composed, (0, 1, 2), has_aux=True), q, k, v)
    onp.testing.assert_allclose(got, want, atol=2e-5)
    for a, r in zip(g_got, g_want):
        onp.testing.assert_allclose(a, r, atol=5e-5)


def test_selection_reaches_the_composition_off_the_chip():
    """``multi_head_attention(selection=)`` off a TPU is the XLA
    composition with the selection as its mask; a selection of every
    causal pair changes nothing."""
    rs = onp.random.RandomState(0)
    b, s, h, hk, d = 2, 12, 4, 2, 8
    q = mx.np.array(rs.randn(b, s, h * d).astype(onp.float32))
    k, v = (mx.np.array(rs.randn(b, s, hk * d).astype(onp.float32))
            for _ in range(2))
    sel = _random_selection(rs, b, s, 5)
    got = multi_head_attention(q, k, v, h, causal=True, kv_heads=hk,
                               selection=mx.np.array(sel)).asnumpy()
    want = _reference_attention(q._data, k._data, v._data, h,
                                jnp.asarray(sel != 0)[:, None], True,
                                None, 0.0, hk)
    onp.testing.assert_allclose(got, want, atol=1e-6)
    full = onp.tril(onp.ones((s, s), onp.int8))[None].repeat(b, 0)
    dense = multi_head_attention(q, k, v, h, causal=True, kv_heads=hk)
    same = multi_head_attention(q, k, v, h, causal=True, kv_heads=hk,
                                selection=mx.np.array(full))
    onp.testing.assert_allclose(same.asnumpy(), dense.asnumpy(), atol=1e-6)
    assert not onp.allclose(got, dense.asnumpy(), atol=1e-3)


def test_selection_through_the_shard_map(monkeypatch):
    """Under a mesh each device runs the kernels on its own block of the
    batch and of the heads; the selection is split with the batch and
    whole on every head shard."""
    from mxnet_tpu import runtime
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import flash_attention as F
    from mxnet_tpu.parallel.mesh import activation_sharding
    seen, real = [], F.flash_attention

    def flash(q, k, v, causal=False, window=None, selection=None):
        seen.append((q.shape, k.shape, selection.shape))
        return real(q, k, v, causal=causal, window=window, interpret=True,
                    block_q=8, block_k=8, bwd_block_q=8, bwd_block_k=8,
                    selection=selection)

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ_CAUSAL", 8)
    monkeypatch.setattr(F, "flash_attention", flash, raising=True)
    rs = onp.random.RandomState(0)
    q = mx.np.array(rs.randn(4, 8, 8 * 8).astype("float32"))
    k, v = (mx.np.array(rs.randn(4, 8, 4 * 8).astype("float32"))
            for _ in range(2))
    sel = _random_selection(rs, 4, 8, 3)
    want = _reference_attention(q._data, k._data, v._data, 8,
                                jnp.asarray(sel != 0)[:, None], True, None,
                                0.0, 4)
    mesh = MeshConfig(dp=2, tp=2).build(jax.devices()[:4])
    with activation_sharding(mesh):
        got = multi_head_attention(q, k, v, heads=8, causal=True,
                                   kv_heads=4, selection=mx.np.array(sel))
    assert seen[-1] == ((2, 4, 8, 8), (2, 2, 8, 8), (2, 8, 8))
    onp.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)


# ---- the indexer: scores, selection, alignment ---------------------------

def _index_operands(rs, b=2, s=24, heads=4, d=8):
    return (jnp.asarray(rs.randn(b, s, heads, d), jnp.float32),
            jnp.asarray(rs.randn(b, s, d), jnp.float32),
            jnp.asarray(rs.randn(b, s, heads), jnp.float32))


@pytest.mark.parametrize("dtype,block,atol", [("float32", 8, 1e-5),
                                              ("float32", 2, 1e-5),
                                              ("bfloat16", 8, 0.2)])
def test_index_scores_in_blocks_are_the_plain_sum(dtype, block, atol,
                                                  monkeypatch):
    """Blocked over queries (three blocks or twelve) and in either
    operand type: ``sum_j w relu(q_j . k) /
    sqrt(d)`` in float32, values and gradients."""
    monkeypatch.setattr(sparse_index, "_BLOCK_BYTES", 4 * 4 * 24 * block)
    assert sparse_index._query_block(24, 4) == block
    q, k, w = _index_operands(onp.random.RandomState(1))

    def plain(q, k, w):
        per_head = jnp.einsum("bqhd,bkd->bqhk", q, k)
        return jnp.sum(jax.nn.relu(per_head) * w[..., None], 2) / 8 ** 0.5

    def got(q, k, w):
        return sparse_index.index_scores(q.astype(dtype), k.astype(dtype), w)

    ct = jnp.asarray(onp.random.RandomState(2).randn(2, 24, 24),
                     jnp.float32)
    (scores, g_got), (want, g_want) = (H.value_and_grads(f, (q, k, w), ct)
                                       for f in (got, plain))
    assert scores.dtype == jnp.float32
    onp.testing.assert_allclose(scores, want, atol=atol)
    for a, r in zip(g_got, g_want):
        onp.testing.assert_allclose(a, r, atol=30 * atol)


@functools.lru_cache(maxsize=None)
def _select_program(topk):
    return jax.jit(lambda i: sparse_index.select_topk(i, topk))


def _select(scores, topk):
    return _select_program(topk)(jnp.asarray(scores))


def _reference_select(scores, topk):
    return H.traced(lambda i: jnp.stack([
        REF.select(row, 0, topk) for row in i]), jnp.asarray(scores))


@pytest.mark.parametrize("s,topk", [(40, 8), (40, 40), (12, 50), (33, 1)])
def test_select_topk_takes_exactly_the_best_of_every_row(s, topk):
    """All positions while ``t < topk``, exactly ``min(t + 1, topk)`` a
    row, nothing above the diagonal, and the same keys as the
    reference's ``lax.top_k``."""
    scores = jnp.asarray(onp.random.RandomState(s + topk).randn(2, s, s),
                         jnp.float32)
    sel = onp.asarray(_select(scores, topk))
    assert sel.dtype == onp.int8 and set(onp.unique(sel)) <= {0, 1}
    want_rows = onp.minimum(onp.arange(s) + 1, topk)
    onp.testing.assert_array_equal(sel.sum(-1), want_rows[None].repeat(2, 0))
    assert not onp.triu(sel, 1).any()
    for t in range(min(topk, s)):
        assert sel[:, t, :t + 1].all()
    ref = _reference_select(scores, topk)
    onp.testing.assert_array_equal(sel != 0, ref)
    pairs, grid = H.traced(sparse_index.selection_counts, jnp.asarray(sel))
    assert int(pairs[0]) == 2 * REF.selected_pairs(s, topk) \
        == int(grid.sum())


def test_select_topk_gives_ties_to_the_lower_index():
    """Scores that tie at the threshold: the first of them along the row
    are taken (the path a ``lax.cond`` enters only then), as
    ``lax.top_k`` orders equals."""
    s, topk = 16, 4
    scores = onp.zeros((1, s, s), onp.float32)
    scores[0, :, 3] = 2.0           # one clear winner, the rest tie at 0
    scores[0, 10, 12:] = 5.0        # above the diagonal: never taken
    scores[0, 12] = -1.0            # a whole row of negative ties
    sel = onp.asarray(_select(scores, topk))
    assert sel[0, 10].nonzero()[0].tolist() == [0, 1, 2, 3]
    assert sel[0, 12].nonzero()[0].tolist() == [0, 1, 2, 3]
    scores[0, 10, 3] = 2.0
    scores[0, 10, 7] = 1.0
    sel = onp.asarray(_select(scores, topk))
    assert sel[0, 10].nonzero()[0].tolist() == [0, 1, 3, 7]
    onp.testing.assert_array_equal(sel != 0,
                                   _reference_select(scores, topk))
    # signs and zeros order as floats do: -0.0 ties with 0.0
    scores[0, 15, :] = onp.linspace(-3, 3, s)
    sel = onp.asarray(_select(scores, topk))
    assert sel[0, 15].nonzero()[0].tolist() == [12, 13, 14, 15]


def test_align_loss_is_the_kl_and_its_gradient_the_closed_form():
    """Against the formula written plainly (every head's probabilities
    whole) and differentiated by JAX; q and k get no gradient."""
    rs = onp.random.RandomState(4)
    b, s, h, hk, d, topk = 2, 16, 4, 2, 8, 5
    q = jnp.asarray(rs.randn(b, s, h * d), jnp.float32)
    k = jnp.asarray(rs.randn(b, s, hk * d), jnp.float32)
    scores = jnp.asarray(rs.randn(b, s, s), jnp.float32)
    sel = _select(scores, topk)

    def plain(scores):
        chosen = sel != 0
        qh = q.reshape(b, s, hk, h // hk, d)
        kh = k.reshape(b, s, hk, d)
        att = jnp.einsum("bqngd,bknd->bngqk", qh, kh) / d ** 0.5
        att = jax.nn.softmax(jnp.where(chosen[:, None, None], att,
                                       -jnp.inf), -1)
        p = jnp.mean(att, (1, 2))
        logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
        kl = jnp.where(chosen, p * (jnp.log(jnp.where(chosen, p, 1.0))
                                    - jnp.where(chosen, logq, 0.0)), 0.0)
        return jnp.sum(kl) / (b * s)

    want, g_want = H.traced(jax.value_and_grad(plain), scores)
    got, (g_got, g_q, g_k) = H.traced(jax.value_and_grad(
        lambda i, q, k: sparse_index.align_loss(i, sel, q, k, h, hk),
        (0, 1, 2)), scores, q, k)
    assert float(got) > 0
    onp.testing.assert_allclose(got, want, rtol=1e-5)
    onp.testing.assert_allclose(g_got, g_want, atol=1e-7)
    assert not onp.asarray(g_q).any() and not onp.asarray(g_k).any()
    assert not onp.asarray(g_got)[onp.asarray(sel) == 0].any()


def test_each_loss_trains_only_its_own_leaves():
    """The indexer's leaves get their gradient from L_I only; every
    other leaf from L_lm only."""
    cfg = CFG
    net = FAMILY.build_net(cfg, _weights(cfg, 5))
    x, y = H.tokens(cfg)
    from mxnet_tpu.ops.xent import sparse_softmax_xent

    def lm_only(out, labels):
        return jnp.mean(sparse_softmax_xent(out[0], labels))

    def index_only(out, labels):
        return out[1]

    _, g_lm = H.program_loss_and_grads(net, lm_only, x, y)
    _, g_index = H.program_loss_and_grads(net, index_only, x, y)
    assert any(".indexer." in n for n in g_lm)
    for name in g_lm:
        lm, index = (float(jnp.abs(g[name]).max()) for g in (g_lm, g_index))
        if ".indexer." in name:
            assert lm == 0.0 and index > 0.0, name
        else:
            assert index == 0.0 and lm > 0.0, name


# ---- the expert layer under softmax routing ------------------------------

def _whole_layer(cfg, seed=3):
    return H.whole_experts(cfg["hidden_size"], cfg["moe_intermediate_size"],
                           cfg["num_experts"], seed, bias=False)


@pytest.fixture(scope="module")
def uncut():
    """24 tokens through the whole layer, once for the three cuts."""
    w, u = _whole_layer(CFG), H.rows(24, CFG["hidden_size"])
    return (w, u) + tuple(H.uncut("keye", CFG, w, u))


@pytest.mark.parametrize("shares", [8, 4, 1])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(shares,
                                                                 uncut):
    """Under softmax routing, weights normalised over all the selected
    experts, held or not: the parts the shares compute add up to what
    the reference gives for the whole layer.  No shared expert."""
    H.assert_shares_add_up(uncut, shares, CFG["num_experts_per_tok"],
                           score_func="softmax")


def test_score_func_is_checked_and_sigmoid_stays_the_default():
    with pytest.raises(ValueError, match="score_func"):
        nn.RoutedExperts(8, 4, 4, 2, held=(0, 2), rows_bound=8,
                         score_func="tanh")
    assert nn.RoutedExperts(8, 4, 4, 2, held=(0, 2),
                            rows_bound=8)._score is jax.nn.sigmoid


# ---- AMP, scopes, the step -----------------------------------------------

@pytest.mark.parametrize("amp", [False, True])
def test_amp_keeps_norms_router_and_types_the_indexer_products(
        amp, monkeypatch):
    """Under mx.amp bf16 the norms are fp32 ops, the router never sees
    bf16 (its selection equals the float32 reference's), and the
    indexer's score products take bf16 operands as a ``Dense``'s do;
    without AMP they take float32.  Their sums are float32 either way,
    and the selection is the best ``topk`` of them."""
    cfg = CFG
    w = _whole_layer(cfg)
    u = H.rows(32, cfg["hidden_size"])
    _, load = H.uncut("keye", cfg, w, u)
    layer = H.routed_experts(w, 0, 4, cfg["num_experts_per_tok"], 128,
                             score_func="softmax")
    indexer = nn.SparseIndexer(cfg["hidden_size"], 4, 8, topk=6)
    indexer.initialize()
    seen = {}
    real = sparse_index.index_scores

    def watched(q, k, wt):
        seen.update(q=q, k=k, w=wt)
        return real(q, k, wt)

    monkeypatch.setattr(sparse_index, "index_scores", watched)
    if amp:
        mx.amp.init("bfloat16")
    try:
        with mx.autograd.record(train_mode=True):
            out = layer(mx.np.array(u)[None])
        index, chosen = indexer(mx.np.array(u)[None])
        normed = mx.npx.rms_norm(mx.np.array(u).astype("bfloat16"),
                                 mx.np.ones((cfg["hidden_size"],)))
    finally:
        if amp:
            mx.amp._deactivate()
    assert normed.dtype == (onp.float32 if amp else jnp.bfloat16)
    assert out.dtype == onp.float32 and index.dtype == onp.float32
    onp.testing.assert_array_equal(layer.expert_load.data().asnumpy(), load)
    want = jnp.bfloat16 if amp else jnp.float32
    assert seen["q"].dtype == seen["k"].dtype == want
    q, k, wt = (seen[n].astype(jnp.float32) for n in "qkw")
    plain = H.traced(lambda q, k, wt: jnp.sum(
        jax.nn.relu(jnp.einsum("bqhd,bkd->bqhk", q, k)) * wt[..., None], 2)
        / 8 ** 0.5, q, k, wt)
    onp.testing.assert_allclose(index._data, plain, atol=1e-4)
    onp.testing.assert_array_equal(chosen._data, _select(index._data, 6))


def test_sharded_train_step_carries_the_selection_counts_in_aux():
    """Through ShardedTrainStep ``selected_pairs`` / ``select_grid`` hold
    the last update's values in ``step.aux`` (not a sum), the expert
    layers' counts accumulate beside them, and the family finds them
    all."""
    cfg = CFG
    net = FAMILY.build_net(cfg, _weights(cfg, 1))
    step = H.sharded_step(net, FAMILY.loss_fn)
    x, y = H.tokens(cfg)
    pairs = x.shape[0] * REF.selected_pairs(x.shape[1],
                                            cfg["sa_config"]["topk"])
    for updates in (1, 2):
        step(x, y)
        counts = FAMILY.stack_program_tree(step.aux, 2)
        assert (counts[FAMILY.PAIRS].ravel() == pairs).all()
        assert (counts[FAMILY.GRID].sum(axis=(1, 2)) == pairs).all()
        assert (counts[FAMILY.LOAD].sum(axis=1)
                == updates * x.size * cfg["num_experts_per_tok"]).all()
    found = FAMILY.step_counts()
    assert set(found) == {n for n in step.aux
                          if not n.endswith("expert_bias")}
    norms = FAMILY.change_norms(cfg, 1, step.trainable)
    assert set(found) <= set(norms)
    assert FAMILY.last_counts[FAMILY.GRID].shape == (2, 16, 16)
    del step, net
    gc.collect()
    assert FAMILY.step_counts() == {}


def test_scopes_of_the_keye_block_do_not_grow_with_depth():
    """``mx.attn``, ``mx.dsa.index``, ``mx.dsa.select``, ``mx.dsa.align``,
    ``mx.moe`` / ``mx.moe.route`` / ``mx.moe.experts`` once a layer,
    whatever the depth."""
    for layers in (1, 3):
        cfg = dict(CFG, num_hidden_layers=layers)
        net = FAMILY.build_net(cfg, _weights(cfg, 1))
        text, entered = H.lowered_scopes(net, FAMILY.loss_fn, *H.tokens(cfg))
        assert dict(entered) == {
            "mx.fwd": 1, "mx.optimizer": 1, "mx.attn": layers,
            "mx.dsa.index": layers, "mx.dsa.select": layers,
            "mx.dsa.align": layers, "mx.moe": layers,
            "mx.moe.route": layers, "mx.moe.experts": layers}
        for scope in ("mx.dsa.index", "mx.dsa.select", "mx.dsa.align"):
            assert scope in text


def test_parameter_count_and_needed_work_of_the_cell():
    """The configuration file's count, from the family's shapes; the
    published widths unchanged."""
    cfg = H.config("keye-vl2-30b-a3b")
    assert FAMILY.n_params(cfg) == cfg["parameters"] == 465_391_104
    assert round(FLOPS.forward_flops_per_token(cfg, 8192)) == 437_727_232
    assert FLOPS.keys_per_query(8192, 2048) == 1792.125
    assert FLOPS.selected_pairs(8192, 2048) == REF.selected_pairs(
        8192, 2048) == 14_681_088
    assert FLOPS.expected_rows_per_token(cfg) == 1.0
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_experts"], cfg["rope_theta"]) \
        == (2048, 32, 4, 128, 768, 8, 128, 10_000_000)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
