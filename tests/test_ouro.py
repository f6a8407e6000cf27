"""The looped decoder family (gluon/model_zoo/ouro.py: a stack whose
layers run ``total_ut_steps`` times on shared weights, an exit gate, a
loss over every exit through the chunked head) against the benchmark's
plain reference (chipbench/reference/ouro.py, importing nothing of the
program), on seeded random weights at small sizes on the CPU; and the
recomputation boundary at a child block (``layer.hybridize(remat=...)``
inside ``ShardedTrainStep``, gluon/block.py) that the cell cannot load
without.

Tolerances: the suite computes float32 products exactly
(``jax_default_matmul_precision`` float32) and the comparisons below ask
the program for ``highest`` too, so program and reference differ by the
order of float32 sums only: losses to 2e-5 of ~4.2, gradients to rtol
2e-3 / atol 3e-6 (the zoo tests' own bounds).  A bf16 product anywhere
moves a loss by 1e-3 and a gradient leaf by percents: neither passes.
What the families' tests share is ``tests/family_harness.py``.
"""
import functools
import gc

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from mxnet_tpu import functional
from mxnet_tpu.gluon import block as gluon_block
from mxnet_tpu.gluon.block import save_these
from mxnet_tpu.gluon.model_zoo import ouro as zoo
from mxnet_tpu.ops.xent import sparse_softmax_xent
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep
from family_harness import one_v5e  # noqa: F401  (a fixture)

REF, FAMILY, FLOPS = H.load("ouro")
_weights = functools.partial(H.weights, "ouro")

CFG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 64,
    "num_hidden_layers": 2, "total_ut_steps": 3, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "entropy_beta": 0.1, "layer_remat": None,
    "published": {"num_hidden_layers": 48},
}
SIZES = {
    "small": CFG,
    # two KV heads under four query heads, four passes over one layer
    "grouped": dict(CFG, num_key_value_heads=2, num_hidden_layers=1,
                    total_ut_steps=4),
    "boundaries": dict(CFG, layer_remat=["attn.qkv", "attn.proj"]),
}
_tokens = functools.partial(H.tokens, seq=24)


def _reference_loss(cfg, weights, x, y):
    """The reference takes the batch whole: one 'sequence' of it."""
    (loss, pdf), grads = H.reference_loss_and_grads(
        lambda p, xs, ys: REF.batch_loss(p, xs, ys, cfg), weights,
        x[None], y[None])
    return (loss, pdf / x.size), grads


def _program_loss(cfg, weights, x, y):
    net = FAMILY.build_net(cfg, weights)
    assert list(functional.split_params(net)[1]) == ["exit.pdf"]
    return H.program_loss_and_grads(net, FAMILY.loss_fn, x, y)


# ---- the zoo model against the reference ---------------------------------

@pytest.mark.parametrize("size", list(SIZES))
def test_zoo_model_loss_gradients_and_exit_distribution(size):
    cfg = SIZES[size]
    weights = _weights(cfg, 7)
    x, y = _tokens(cfg)
    (got, mutated), grads = _program_loss(cfg, weights, x, y)
    (want, pdf), ref_grads = _reference_loss(cfg, weights, x, y)
    assert abs(float(got) - float(want)) < 2e-5
    H.assert_leaves_close(FAMILY.stack_program_tree(
        grads, cfg["num_hidden_layers"]), ref_grads)
    # the gate learns (from both terms), and every exit has its share
    assert float(jnp.linalg.norm(ref_grads["gate.w"])) > 1e-4
    onp.testing.assert_allclose(mutated["exit.pdf"], pdf, atol=1e-6)
    assert abs(float(pdf.sum()) - 1.0) < 1e-6 and float(pdf.min()) > 0.05


def test_a_shared_leafs_gradient_is_the_sum_over_its_uses():
    """The reference's own statement of it: the loop written over T
    *copies* of the stack gives one gradient a copy, and their sum is
    the looped model's gradient of the shared leaf — in the reference by
    autodiff of the plain loop, in the program through the parameter
    swap."""
    cfg = CFG
    weights = dict(_weights(cfg, 3))
    x, y = _tokens(cfg, batch=1)
    steps, n = cfg["total_ut_steps"], cfg["num_hidden_layers"]

    def unshared(copies):
        """The same computation over a stack of T x N layers' leaves that
        happen to hold the same values: layer i of pass t reads row
        t * N + i."""
        p = dict(weights, **copies)
        layer = jax.checkpoint(lambda h, q: REF._layer(h, q, cfg))
        h, states = p["wte"][x[0]], []
        for t in range(steps):
            for i in range(n):
                h = layer(h, {k: p[k][t * n + i] for k in REF.LAYER_LEAVES})
            h = REF._rms(h, p["ln_f.g"], cfg["rms_norm_eps"])
            states.append(h)
        log_p = REF.exit_log_pdf(p, states)
        ce = jnp.stack([REF.exit_xent(z, p["head.w"], jnp.asarray(y[0]))
                        for z in states])
        return jnp.mean(jnp.sum(jnp.exp(log_p) * (
            ce + cfg["entropy_beta"] * log_p), axis=0))

    copies = {k: jnp.concatenate([weights[k]] * steps)
              for k in REF.LAYER_LEAVES}
    per_use = H.traced(jax.grad(unshared), copies)
    (_, _), ref_grads = _reference_loss(cfg, weights, x, y)
    (_, _), grads = _program_loss(cfg, weights, x, y)
    stacked = FAMILY.stack_program_tree(grads, n)
    for k in REF.LAYER_LEAVES:
        summed = per_use[k].reshape((steps, n) + weights[k].shape[1:]).sum(0)
        for name, got in (("reference", ref_grads[k]), ("program",
                                                        stacked[k])):
            onp.testing.assert_allclose(got, summed, atol=3e-6, rtol=2e-3,
                                        err_msg=f"{k} ({name})")
        # and no single use is the whole of it
        first = per_use[k][:n]
        assert float(jnp.linalg.norm(first - summed)) \
            > 0.05 * float(jnp.linalg.norm(summed)), k


def test_one_pass_is_the_same_stack_unlooped_and_has_no_loop_residue():
    """``total_ut_steps=1``: one exit that takes everything (p = 1, no
    entropy), so the loss is the plain stack's mean cross-entropy, and
    the trace holds no loop."""
    cfg = dict(CFG, total_ut_steps=1)
    weights = _weights(cfg, 5)
    x, y = _tokens(cfg)
    net = FAMILY.build_net(cfg, weights)
    params = functional.param_arrays(net)

    def states(x_):
        return functional.functional_call(net, params, x_, train=True)[0]

    def losses(x_):
        h, w, log_p = states(x_)
        return (h, w, log_p), jnp.mean(sparse_softmax_xent(
            jnp.einsum("bsd,vd->bsv", h[0], w), y)), \
            FAMILY.loss_fn((h, w, log_p), y)

    (h, w, log_p), plain, got = H.traced(losses, x)
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(states)(x))
    assert h.shape == (1, 2, 24, 64) and not float(jnp.abs(log_p).max())
    assert "scan" not in text and "while" not in text
    (want, _), _ = _reference_loss(cfg, weights, x, y)
    assert abs(float(got) - float(plain)) < 1e-6
    assert abs(float(got) - float(want)) < 2e-5


def test_the_exit_distribution_sums_to_one_and_beta_zero_leaves_the_expected_xent():
    cfg = dict(CFG, total_ut_steps=4)
    weights = dict(_weights(cfg, 9))
    # a gate far from its seeded 0.5, saturating on some tokens
    weights["gate.w"] = weights["gate.w"] * 400.0
    net = FAMILY.build_net(cfg, weights)
    x, y = _tokens(cfg)
    def run(params):
        out, mutated = functional.functional_call(net, params, x, train=True)
        h, w, log_p = out
        p = jnp.exp(log_p)
        ce = jnp.stack([sparse_softmax_xent(
            jnp.einsum("bsd,vd->bsv", h[t], w), y) for t in range(4)])
        return (p, log_p, mutated, jnp.mean(jnp.sum(p * ce, axis=0)),
                zoo.looped_lm_loss(out, y, beta=0.0),
                zoo.looped_lm_loss(out, y, beta=0.1),
                -jnp.mean(jnp.sum(p * log_p, axis=0)))

    p, log_p, mutated, expected, got0, got, entropy = H.traced(
        run, functional.param_arrays(net))
    onp.testing.assert_allclose(p.sum(0), 1.0, atol=2e-6)
    assert float(p.max()) > 0.99 and float(p.min()) < 1e-3
    onp.testing.assert_allclose(mutated["exit.pdf"], p.mean((1, 2)),
                                atol=1e-6)
    assert abs(float(got0) - float(expected)) < 2e-6
    assert float(entropy) > 5e-3
    assert abs(float(got) - float(expected - 0.1 * entropy)) < 2e-6


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 0.0)])
def test_the_stacked_chunked_head_against_four_dense_heads(dtype, tol):
    """``chunked_lm_xent`` over the exits stacked to (T b s, units), in
    vocabulary chunks that do not divide the vocabulary, against one
    dense ``sparse_softmax_xent`` head an exit: values, and gradients to
    the states, the head and the token weights.  In bfloat16 both take
    bf16 operands and accumulate in float32, so the losses agree to the
    last bit of the dense head's bf16 logits: compared at float32 after
    rounding the dense logits as the dense head stores them."""
    rng = onp.random.default_rng(3)
    steps, tokens, units, vocab = 4, 48, 32, 100
    h = jnp.asarray(rng.normal(size=(steps, tokens, units)), dtype)
    w = jnp.asarray(rng.normal(size=(vocab, units)) * 0.3, dtype)
    labels = jnp.asarray(rng.integers(0, vocab, tokens), jnp.int32)
    log_p = jax.nn.log_softmax(
        jnp.asarray(rng.normal(size=(steps, tokens)), jnp.float32), axis=0)

    def stacked(h, w, log_p):
        return zoo.looped_lm_loss((h[:, None], w, log_p[:, None]),
                                  labels[None], beta=0.1, chunk=48)

    def dense(h, w, log_p):
        ce = jnp.stack([sparse_softmax_xent(
            jnp.einsum("sd,vd->sv", h[t], w,
                       preferred_element_type=jnp.float32), labels)
            for t in range(steps)])
        return jnp.mean(jnp.sum(jnp.exp(log_p) * (ce + 0.1 * log_p), axis=0))

    got, g_got = H.traced(jax.value_and_grad(stacked, (0, 1, 2)), h, w, log_p)
    want, g_want = H.traced(jax.value_and_grad(dense, (0, 1, 2)), h, w, log_p)
    assert abs(float(got) - float(want)) <= (tol or 1e-6)
    # bf16: dz and dW are bf16 sums of float32-accumulated products on
    # both sides, a unit in the last place of bf16 (0.8 %) apart at most
    rtol, atol = (1e-4, 1e-6) if dtype == "float32" else (1.6e-2, 2e-4)
    for a, b in zip(g_got, g_want):
        onp.testing.assert_allclose(onp.asarray(a, onp.float32),
                                    onp.asarray(b, onp.float32),
                                    rtol=rtol, atol=atol)


@pytest.fixture(scope="module")
def updates():
    """Three updates by the step, its layers flagged as the cell flags
    them, and by the reference: once a file."""
    cfg = dict(CFG, layer_remat=["attn.qkv"])
    return H.three_updates("ouro", cfg, 11, [_tokens(cfg, seed=s)
                                             for s in (4, 5, 6)],
                           cfg["num_hidden_layers"],
                           look=lambda step: step._remat_on)


def test_the_sharded_steps_flag_is_the_layers_alone(updates):
    """The step's side of the three updates: the cell's flag is on the
    layers, not on the step, and the step keeps the exit distribution."""
    assert not updates.seen                # the flag is the layers' alone
    assert updates.last_pdf and abs(sum(updates.last_pdf) - 1) < 1e-5


def test_eager_and_hybridized_agree_with_the_sharded_step(updates):
    """The family's eager case: the seeded net op by op under
    ``mx.autograd.record``, hybridized, and the first loss of its
    ``ShardedTrainStep`` (whose layers are boundaries)."""
    eager, hybrid = updates.eager_and_hybridized
    assert abs(eager - hybrid) < 1e-6
    assert abs(updates.losses[0] - eager) < 1e-5


def test_the_sharded_step_follows_the_reference_and_keeps_the_exit_pdf(
        updates):
    """Three updates through ``ShardedTrainStep``, the layers flagged as
    the cell flags them: losses, the first gradient (from Adam's first
    moment), the parameters' change and ``exit.pdf`` in the step's
    ``aux`` against the reference's."""
    run, ref = updates, updates.ref
    onp.testing.assert_allclose(run.losses, ref["losses"], atol=2e-5)
    n = CFG["num_hidden_layers"]
    norms = {k: onp.sqrt((v.astype(onp.float64) ** 2).sum(
        axis=tuple(range(1, v.ndim)) if k in REF.STACKED else None))
        for k, v in FAMILY.stack_program_tree(run.first, n).items()}
    g_gaps = REF.leaf_gaps({k: onp.atleast_1d(v) for k, v in norms.items()},
                           ref["grad_norms"])
    assert max(g_gaps.values()) < 1e-3, REF.worst_leaf(g_gaps)
    c_gaps = run.c_gaps
    # Adam divides by sqrt(v): a float32 rounding of a small gradient
    # entry moves its step by more than it moves the gradient's norm
    assert max(c_gaps.values()) < 5e-3, REF.worst_leaf(c_gaps)
    assert c_gaps["exit.pdf"] < 1e-5
    onp.testing.assert_allclose(run.aux["exit.pdf"],
                                ref["change_norms"]["exit.pdf"], atol=1e-5)


def test_needed_flops_against_hand_numbers():
    """ISSUE 42's arithmetic at the published widths, four layers."""
    cfg = dict(hidden_size=2048, intermediate_size=5632, head_dim=128,
               num_attention_heads=16, num_key_value_heads=16,
               vocab_size=49152, num_hidden_layers=4, total_ut_steps=4)
    layer = 2 * 4 * 2048 * 2048 + 4 * 2048 * 4096 + 6 * 2048 * 5632
    assert layer == 136_314_880 == FLOPS.layer_flops_per_token(cfg, 8192)
    head = 2 * 2048 * 49152
    assert head == 201_326_592 == FLOPS.head_flops_per_token(cfg)
    want = 16 * layer + 4 * head + 3 * 2 * 2048
    assert FLOPS.forward_flops_per_token(cfg, 8192) == want == 2_986_356_736
    assert FLOPS.train_flops_per_token(cfg, 8192) == 8_959_070_208
    assert round(100 * 16 * layer / want) == 73
    assert round(100 * 4 * head / want) == 27
    # one pass is a plain four-layer decoder
    assert FLOPS.forward_flops_per_token(dict(cfg, total_ut_steps=1), 8192) \
        == 4 * layer + head
    assert FAMILY.n_params(dict(cfg)) == 406_884_353 \
        == 4 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049


# ---- the recomputation boundary at a child block -------------------------

def _step_of(cfg, seed=13, **kw):
    with jax.default_matmul_precision("highest"):
        return H.sharded_step(FAMILY.build_net(cfg, _weights(cfg, seed)),
                              FAMILY.loss_fn, **kw)


#: ``f(*args)`` and the ``block.boundary_*`` counters of what it traced
_counted = functools.partial(H.counters, "block.boundary")


def _flagged_below(block, depth=float("inf")):
    """Flagged blocks under ``block`` — those that open a region inside
    its own when its policy is a list of names; ``depth`` 1: those it
    calls itself."""
    if depth < 1:
        return 0
    return sum(1 + _flagged_below(c, depth - 1)
               for c in block._children.values()
               if getattr(c, "_flags", {}).get("remat"))


def _regions(jaxpr, inside=False):
    """(``remat2`` regions in a jaxpr, those of them inside another),
    every sub-jaxpr walked."""
    total = nested = 0
    for eqn in jaxpr.eqns:
        region = eqn.primitive.name == "remat2"
        total, nested = total + region, nested + (region and inside)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            t, n = _regions(sub, inside or region)
            total, nested = total + t, nested + n
    return total, nested


CELL_NAMES = ["pallas_call", "attn.qkv", "attn.proj", "ffn.down"]


@functools.lru_cache(maxsize=None)
def _three_plain_updates():
    """Three updates without a boundary, once for the five flags: (losses,
    Adam's first moments after the first, parameters and pdf at the end)."""
    x, y = _tokens(CFG, seed=2)
    plain = _step_of(CFG)
    losses = [plain(x, y).asnumpy()]
    moments = {n: onp.asarray(s[0]) for n, s in plain.states.items()}
    losses += [plain(x, y).asnumpy() for _ in range(2)]
    return (losses, moments,
            {n: onp.asarray(w) for n, w in plain.trainable.items()},
            onp.asarray(plain.aux["exit.pdf"]))


@pytest.mark.parametrize("remat", [True, "dots", ["attn.qkv", "ffn.inner"],
                                   ["dot_general"], CELL_NAMES])
def test_a_flagged_child_inside_the_step_changes_no_value(remat):
    """Three updates with every layer application a boundary against
    three without — under a list of names every flagged child of the
    layer a region inside the layer's (the counter says so): the first
    gradient
    (Adam's first moment after one update) and the parameters after
    three to float32 rounding — the replayed forward is the forward's
    own operations, but XLA groups them into other fusions the second
    time, so a last bit of a sum may differ (seen here: 7e-8 on a
    parameter, three updates of Adam after 4e-9 on a gradient entry, 1e-5
    of the leaf's largest); a
    forward replayed in another precision, or from other inputs, is
    1e-3 away.  The losses are equal to the last bit behind one region an
    application; regions inside it cut the forward's fusions too, and a
    loss may then differ in its last place (seen: one unit, 2.4e-7 of
    3.7)."""
    x, y = _tokens(CFG, seed=2)
    names = isinstance(remat, list)
    losses, moments, trainable, pdf = _three_plain_updates()
    flagged = _step_of(dict(CFG, layer_remat=remat))
    for i, a in enumerate(losses):
        b, counts = _counted(lambda: flagged(x, y).asnumpy())
        assert abs(a - b) <= (4 * onp.spacing(a) if names else 0)
        if i == 0:
            assert (counts.get("block.boundary_nested_total", 0) > 0) == names
            for n, m in moments.items():
                onp.testing.assert_allclose(
                    m, onp.asarray(flagged.states[n][0]), rtol=0,
                    atol=1e-5 * onp.abs(m).max(), err_msg=n)
    for n, w in trainable.items():
        onp.testing.assert_allclose(w, onp.asarray(flagged.trainable[n]),
                                    rtol=0, atol=1e-5, err_msg=n)
    onp.testing.assert_allclose(pdf, flagged.aux["exit.pdf"], atol=1e-7)


@pytest.mark.parametrize("remat,alone", [
    (["attn.qkv"], False), (True, False), ("dots", False), (None, False),
    (["attn.qkv"], True)],
    ids=["names", "true", "dots", "unflagged", "names_on_the_layer_alone"])
def test_the_lowered_step_holds_one_region_a_flagged_application(remat,
                                                                 alone):
    """``remat2`` regions in the step's jaxpr, counted by walking it.
    Under ``True`` and ``'dots'``: one a layer application (T x N of
    them), none nested inside another although ``hybridize`` flags every
    descendant.  Under a list of names: the application's region and,
    inside it, one for each flagged descendant (attention, feed-forward
    and the four norms, and inside the first two their ``Dense``s and
    activation), and at least as many ``optimization_barrier``s in the
    lowered text.
    None without the flag.  The lowered text names a replayed forward
    ``rematted_computation``; the counters read what the jaxpr holds."""
    x, y = _tokens(CFG, seed=2)
    apps = CFG["num_hidden_layers"] * CFG["total_ut_steps"]
    step = _step_of(dict(CFG, layer_remat=remat))
    net, params = step.block, {**step.trainable, **step.aux}
    layer = net.backbone.layer0
    if alone:       # the way back: the flags decide, so take the children's
        for each in net.backbone.layers:
            for child in each._children.values():
                child.hybridize()
        assert _flagged_below(layer) == 0
        remat = True        # what is counted below: one region, none inside

    def loss(p):
        out, _ = functional.functional_call(net, p, x, train=True)
        return FAMILY.loss_fn(out, y)

    jaxpr, counts = _counted(jax.make_jaxpr(loss), params)
    text = step.lower(x, y).as_text(debug_info=True)
    if remat is None:
        assert _regions(jaxpr.jaxpr) == (0, 0) and counts == {}
        assert "rematted_computation" not in text and "checkpoint" not in text
        return
    if not alone:
        assert layer.attention._flags["remat"] == remat
        assert layer.attention.query_proj._flags["remat"] == remat
    inner = _flagged_below(layer) if isinstance(remat, list) else 0
    if isinstance(remat, list):
        assert inner == 15 and _flagged_below(layer, 1) == 6
    assert _regions(jaxpr.jaxpr) == (apps * (1 + inner), apps * inner)
    assert counts == {k: v for k, v in (
        ("block.boundary_regions_total", apps * (1 + inner)),
        ("block.boundary_nested_total", apps * inner)) if v}
    assert "rematted_computation" in text
    assert text.count("optimization_barrier") >= apps * (1 + inner)


def test_an_unflagged_block_traces_what_it_traced():
    """The boundary is opt-in: a block that was never ``hybridize``d, and
    one hybridized without ``remat``, called inside a trace, give the
    jaxpr the pinned files hold (written at the parents of the PRs that
    pinned them)."""
    name = "grouped_query_attention_plain"
    got, want = H.as_before(name)
    assert got == want
    layer = H.AS_BEFORE[name]()
    layer.hybridize()
    nested = H.jaxpr_text(layer, (2, 8, 32))
    assert "remat2" not in nested and "name=_pure" in nested


def test_what_a_policy_of_names_saves():
    """``save_these``: a value named with ``checkpoint_name``, or made by
    a primitive of that name, is a residual of the boundary; nothing
    else is."""
    from jax.ad_checkpoint import checkpoint_name, print_saved_residuals

    def f(x, w):
        h = checkpoint_name(jnp.sin(x @ w), "kept")
        return jnp.sum(jnp.tanh(checkpoint_name(jnp.cos(h), "dropped")))

    x, w = jnp.ones((4, 8)), jnp.ones((8, 8))

    def saved(policy):
        import contextlib
        import io
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            print_saved_residuals(jax.checkpoint(f, policy=policy), x, w)
        return buf.getvalue()

    def made(text):
        """Where the residuals that are no argument were made."""
        return [l.split(" from ")[1] for l in text.splitlines()
                if "from the argument" not in l]

    kept, dropped = made(saved(save_these("kept"))), \
        made(saved(save_these("dropped")))
    assert len(kept) == len(dropped) == 1 and kept != dropped
    by_prim = made(saved(save_these("dot_general")))
    assert len(by_prim) == 1 and by_prim not in (kept, dropped)
    assert made(saved(save_these())) == []
    assert len(made(saved(save_these("kept", "dropped")))) == 2
    with pytest.raises(mx.base.MXNetError):
        save_these("kept", 3)
    net = mx.gluon.nn.Dense(4)
    net.hybridize(remat=["kept"])
    assert net._flags["remat"] == ["kept"]


@pytest.mark.parametrize("where", ["one_region", "two_regions_deep"])
def test_a_boundary_hands_aux_state_out_of_its_region(where):
    """Aux state a flagged child rebinds inside its forward (the expert
    layers' counts, BatchNorm's running statistics) reaches the
    enclosing ``functional_call``'s ``mutated`` as without the flag —
    also where the child's region lies inside its flagged parent's
    (a policy of names): the inner region hands the statistics to the
    outer's trace, the outer hands them on, and what the outer puts back
    in the storage after its trace does not undo it."""
    from mxnet_tpu.gluon import nn

    def run(remat):
        mx.random.seed(0)
        net = nn.HybridSequential()
        norms = nn.HybridSequential()
        norms.add(nn.BatchNorm(axis=-1, in_channels=8),
                  nn.Dense(8, in_units=8, flatten=False),
                  nn.BatchNorm(axis=-1, in_channels=8))
        net.add(nn.Dense(8, in_units=6, flatten=False), norms)
        net.initialize()
        if remat is True:
            for norm in (norms[0], norms[2]):
                norm.hybridize(remat=True)
        elif remat is not None:
            norms.hybridize(remat=remat)
        params = functional.param_arrays(net)
        x = jnp.asarray(onp.random.default_rng(0).normal(size=(5, 6)),
                        jnp.float32)

        def loss(p):
            out, mutated = functional.functional_call(net, p, x, train=True)
            return jnp.sum(out ** 2), mutated

        return _counted(jax.jit(jax.value_and_grad(loss, has_aux=True)),
                        params)

    ((a, mut_a), g_a), _ = run(None)
    ((b, mut_b), g_b), counts = run(
        True if where == "one_region" else ["nothing"])
    assert counts == ({"block.boundary_regions_total": 2}
                      if where == "one_region" else
                      {"block.boundary_regions_total": 4,
                       "block.boundary_nested_total": 3})
    assert set(mut_a) == set(mut_b) and len(mut_a) == 4
    assert float(a) == float(b)
    for n in mut_a:
        onp.testing.assert_array_equal(mut_a[n], mut_b[n])
    for n in g_a:
        onp.testing.assert_allclose(g_a[n], g_b[n], rtol=1e-5, atol=1e-6)


class _DropCell(mx.gluon.nn.HybridBlock):
    """x + dropout(dense(x)): a child that draws a random number."""

    def __init__(self):
        super().__init__()
        self.dense = mx.gluon.nn.Dense(8, in_units=8, flatten=False)
        self.drop = mx.gluon.nn.Dropout(0.5)

    def forward(self, x):
        return x + self.drop(self.dense(x))


def _drop_net(remat, parent_hybridized=False, head=True):
    from mxnet_tpu.gluon import nn
    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(_DropCell(), _DropCell())
    if head:
        net.add(nn.Dense(4, in_units=8, flatten=False))
    net.initialize()
    if parent_hybridized:
        net.hybridize()     # first: it sets every descendant's flags
    for cell in (net[0], net[1]):
        cell.hybridize(remat=remat)
    return net


def _keep_all(*_, **__):
    """A policy under which a boundary saves everything: nothing is made
    again, so no mask is drawn a second time."""
    return True


@pytest.mark.parametrize("where", ["sharded_step", "hybridized_parent"])
def test_a_boundary_takes_its_rng_key_as_an_argument(where):
    """Two flagged children that each hold a Dropout, inside
    ``ShardedTrainStep`` and under a hybridized parent.  The key a
    boundary's forward splits is drawn in the enclosing trace and handed
    in as an argument, so the second child meets no tracer of the first's
    region (an ``UnexpectedTracerError`` before).  The replayed forward
    draws the mask the forward drew: losses, gradients and updated
    parameters equal those of a boundary that keeps everything and
    replays nothing, to float32 rounding (XLA groups the replay
    otherwise; another mask would move half of a gradient's entries by
    their own size).  A call draws a fresh mask."""
    x = onp.random.default_rng(0).normal(size=(2, 6, 8)).astype("float32")
    y = onp.zeros((2, 6, 4), "float32")

    def run(remat, lr=0.1):
        net = _drop_net(remat, where == "hybridized_parent")
        if where == "sharded_step":
            mesh = MeshConfig(dp=1)
            step = ShardedTrainStep(
                net, lambda out, t: jnp.mean((out - t) ** 2),
                mx.optimizer.create("sgd", learning_rate=lr), mesh,
                batch_specs=mesh.batch_specs(3, 3), n_labels=1)
            losses = [float(step(x, y).asnumpy()) for _ in range(3)]
            return losses, {n: onp.asarray(w)
                            for n, w in step.trainable.items()}
        losses = []
        for _ in range(2):
            with mx.autograd.record():
                loss = ((net(mx.np.array(x)) - mx.np.array(y)) ** 2).mean()
            loss.backward()
            losses.append(float(loss.asnumpy()))
        return losses, {n: p.grad().asnumpy()
                        for n, p in net.collect_params().items()}

    (replayed, a), (kept, b) = run(True), run(_keep_all)
    onp.testing.assert_allclose(replayed, kept, rtol=1e-6)
    for n in b:
        onp.testing.assert_allclose(a[n], b[n], rtol=1e-5, atol=1e-7,
                                    err_msg=n)
    still = run(True, lr=0.0)[0]    # the same parameters at every call
    assert still[0] != still[1]


def test_two_boundaries_draw_two_masks_and_a_trace_takes_its_key():
    """Two flagged cells ``x + dropout(x)`` (their Dense the identity)
    scale an entry by 1 or 3 each: under one mask the product is 1 or 9,
    under two it is 3 somewhere.  The keys come from the enclosing
    trace's stream: the same key gives the same output, another key
    another."""
    net = _drop_net(True, head=False)
    for cell in (net[0], net[1]):
        cell.dense.weight.set_data(mx.np.array(onp.eye(8, dtype="float32")))
        cell.dense.bias.set_data(mx.np.zeros((8,)))
    params = functional.param_arrays(net)
    x = jnp.ones((4, 16, 8), jnp.float32)

    @jax.jit
    def forward(key):
        with mx.random.trace_key_scope(key):
            out, _ = functional.functional_call(net, params, x, train=True)
        return out

    out = onp.asarray(forward(jax.random.PRNGKey(3)))
    assert set(onp.unique(onp.round(out))) == {1.0, 3.0, 9.0}
    onp.testing.assert_array_equal(out, forward(jax.random.PRNGKey(3)))
    assert (out != onp.asarray(forward(jax.random.PRNGKey(4)))).any()


@pytest.mark.parametrize("outer,inner,want", [
    (["kept"], None, (6, 5)),          # the flag recursed: names all through
    (["kept"], True, (3, 2)),          # a child flagged otherwise: its policy
    (True, None, (1, 0)),
    ("dots", None, (1, 0)),
    (_keep_all, None, (1, 0)),
    (True, ["kept"], (1, 0)),          # names below another policy: plain
], ids=["names", "names_over_true", "true", "dots", "callable",
        "true_over_names"])
def test_which_flagged_blocks_open_a_region(outer, inner, want):
    """``a(b(dense, dense), dense)`` with ``a`` flagged ``outer`` (which
    flags everything below it) and then ``b`` flagged ``inner``, called
    inside a trace: (regions, those inside another) in the jaxpr and in
    the two counters.  Only under a list of names do the flagged blocks
    a boundary calls open regions, each with its own flag's policy: all
    of them where the names go all the way down (``b``, its two
    ``Dense``s, the first one's activation, ``a``'s own ``Dense``); where
    ``b`` is flagged ``True``, ``b`` and ``a``'s ``Dense`` but nothing
    inside ``b``.  The gradients are the unflagged net's."""
    from mxnet_tpu.gluon import nn

    def build(flag):
        mx.random.seed(3)
        net, a, b = (nn.HybridSequential() for _ in range(3))
        b.add(nn.Dense(8, in_units=8, flatten=False, activation="tanh"),
              nn.Dense(8, in_units=8, flatten=False))
        a.add(b, nn.Dense(8, in_units=8, flatten=False))
        net.add(a)      # ``functional_call`` runs the root's forward itself
        net.initialize()
        if flag:
            a.hybridize(remat=outer)
            if inner is not None:
                b.hybridize(remat=inner)
        return net

    x = jnp.asarray(onp.random.default_rng(0).normal(size=(3, 8)),
                    jnp.float32)

    def grad_of(net):
        def loss(p, x):
            return jnp.sum(functional.functional_call(
                net, p, x, train=True)[0] ** 2)
        return jax.grad(loss), functional.param_arrays(net)

    g, params = grad_of(build(True))
    jaxpr, counts = _counted(jax.make_jaxpr(g), params, x)
    assert _regions(jaxpr.jaxpr)[0] >= want[0]   # the backward holds them too
    fwd = jax.make_jaxpr(
        lambda p, x: functional.functional_call(build(True), p, x,
                                                train=True)[0])(params, x)
    assert _regions(fwd.jaxpr) == want
    assert (counts.get("block.boundary_regions_total", 0),
            counts.get("block.boundary_nested_total", 0)) == want
    # the ``remat2`` equations, outermost first
    policies = [e.params["policy"] for e in H.eqns(fwd.jaxpr)
                if e.primitive.name == "remat2"]
    if outer == ["kept"]:
        # the outer region first, then b's, then the Dense's
        assert policies[0] is not None and policies[2] is not None
        assert (policies[1] is None) == (inner is True)
    g0, params0 = grad_of(build(False))
    want_g, got_g = jax.jit(g0)(params0, x), jax.jit(g)(params, x)
    for n in want_g:
        onp.testing.assert_allclose(got_g[n], want_g[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)


class _CountedCell(mx.gluon.nn.HybridBlock):
    """batchnorm(dense(x)), counting how often its forward's Python runs."""

    def __init__(self):
        super().__init__()
        self.dense = mx.gluon.nn.Dense(8, in_units=8, flatten=False)
        self.norm = mx.gluon.nn.BatchNorm(axis=-1, in_channels=8)
        self.ran = 0

    def forward(self, x):
        self.ran += 1
        return self.norm(self.dense(x))


@pytest.mark.parametrize("remat", [["nothing"], True],
                         ids=["names", "true"])
def test_a_looped_block_is_traced_once_and_reads_what_was_rebound(remat):
    """A flagged cell applied three times on shared weights inside one
    trace: its forward's Python runs once (the second and third
    application use the first's trace, regions inside it and all — the
    counters still read every region of the program), the running
    statistics the first application rebinds are what the second reads
    (aux state is an argument of the region), and loss, gradients and
    statistics are the unflagged loop's.  No tracer outlives the trace
    (JAX's own leak check), and nothing kept for the cell outlives it."""
    x = jnp.asarray(onp.random.default_rng(0).normal(size=(5, 8)),
                    jnp.float32)

    def run(flag):
        mx.random.seed(5)
        net = mx.gluon.nn.HybridSequential()
        cell = _CountedCell()
        net.add(cell)
        net.initialize()
        if flag:
            cell.hybridize(remat=remat)
        params = functional.param_arrays(net)

        def loss(p, x):
            def fwd(x):
                for _ in range(3):
                    x = cell(x)
                return x
            net.forward = fwd
            out, mutated = functional.functional_call(net, p, x, train=True)
            return jnp.sum(out ** 2), mutated

        with jax.checking_leaks():      # what a region keeps holds no tracer
            out, counts = _counted(
                jax.jit(jax.value_and_grad(loss, has_aux=True)), params, x)
        return out, counts, cell.ran

    ((a, mut_a), g_a), _, ran_plain = run(False)
    ((b, mut_b), g_b), counts, ran = run(True)
    assert (ran_plain, ran) == (3, 1)
    inner = 2 if isinstance(remat, list) else 0
    assert counts == {k: v for k, v in (
        ("block.boundary_regions_total", 3 * (1 + inner)),
        ("block.boundary_nested_total", 3 * inner)) if v}
    gc.collect()        # the function kept for a block goes with the block
    assert not len(gluon_block._boundary_tls.traced)
    onp.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    assert set(mut_a) == set(mut_b) and len(mut_a) == 2
    for n in mut_a:
        onp.testing.assert_allclose(mut_a[n], mut_b[n], rtol=1e-6, atol=1e-7)
    for n in g_a:
        onp.testing.assert_allclose(g_a[n], g_b[n], rtol=1e-5, atol=1e-6,
                                    err_msg=n)


def test_a_dropout_two_regions_deep_draws_from_a_key_handed_down():
    """A flagged pair of cells, each ``x + dropout(dense(x))``, inside
    ``ShardedTrainStep`` under a policy of names: the pair is a region,
    each cell a region inside it, and the cell's ``Dense`` and
    ``Dropout`` regions inside the cell's.  A region's key is drawn in
    the trace round it from the key that trace was handed, so every
    replay (of the Dropout inside the cell's, of the cell inside the
    pair's, of the pair) draws the mask the forward drew — a policy that names nothing and makes everything again gives
    the losses and parameters of one that names every primitive and
    makes nothing again; a call draws a fresh mask; and the Dropout
    after the pair, which splits the step's own stream, meets no tracer
    of either region."""
    from mxnet_tpu.gluon import nn
    x = onp.random.default_rng(0).normal(size=(2, 6, 8)).astype("float32")
    y = onp.zeros((2, 6, 4), "float32")

    def build(remat):
        mx.random.seed(7)
        net, pair = nn.HybridSequential(), nn.HybridSequential()
        pair.add(_DropCell(), _DropCell())
        net.add(pair, nn.Dropout(0.25),
                nn.Dense(4, in_units=8, flatten=False))
        net.initialize()
        if remat is not None:
            pair.hybridize(remat=remat)
        return net

    def traced(key):
        net = build(None)
        with mx.random.trace_key_scope(key):
            return functional.functional_call(
                net, functional.param_arrays(net), x, train=True)[0]

    everything = sorted({e.primitive.name for e in H.eqns(
        jax.make_jaxpr(traced)(jax.random.PRNGKey(0)).jaxpr)})
    assert "dot_general" in everything and any(
        "random" in n or "threefry" in n for n in everything)

    def run(remat, lr=0.1):
        mesh = MeshConfig(dp=1)
        step = ShardedTrainStep(
            build(remat), lambda out, t: jnp.mean((out - t) ** 2),
            mx.optimizer.create("sgd", learning_rate=lr), mesh,
            batch_specs=mesh.batch_specs(3, 3), n_labels=1)
        first, counts = _counted(lambda: float(step(x, y).asnumpy()))
        assert counts == {"block.boundary_regions_total": 7,
                          "block.boundary_nested_total": 6}
        losses = [first] + [float(step(x, y).asnumpy()) for _ in range(2)]
        return losses, {n: onp.asarray(w) for n, w in step.trainable.items()}

    (replayed, a), (kept, b) = run(["nothing"]), run(everything)
    onp.testing.assert_allclose(replayed, kept, rtol=1e-6)
    for n in b:
        onp.testing.assert_allclose(a[n], b[n], rtol=1e-5, atol=1e-7,
                                    err_msg=n)
    still = run(["nothing"], lr=0.0)[0]   # the same parameters at every call
    assert len(set(still)) == 3
    assert mx.np.random.uniform(size=(2,)).asnumpy().shape == (2,)


def _fp8_histories(remat):
    """Two fp8 updates with the layers flagged ``remat``: (losses, each
    site's largest amaxes, the boundary counters of the first)."""
    x, y = _tokens(CFG, seed=2)
    with jax.default_matmul_precision("highest"):
        step = H.sharded_step(
            FAMILY.build_net(dict(CFG, layer_remat=remat),
                             _weights(CFG, 13)),
            FAMILY.loss_fn, precision="fp8")
        first, counts = _counted(lambda: float(step(x, y).asnumpy()))
        losses = [first, float(step(x, y).asnumpy())]
    return losses, {s: {k: float(v.max()) for k, v in h.items()}
                    for s, h in step.extra["fp8"].items()}, counts


#: without a flag, once for both flags it is compared with
_unflagged_fp8_histories = functools.lru_cache(maxsize=None)(
    lambda: _fp8_histories(None))


@pytest.mark.parametrize("remat", [["attn.qkv"], True],
                         ids=["names", "true"])
def test_an_fp8_step_sees_through_a_boundary(remat):
    """``precision="fp8"`` with the layers flagged: every ``Dense`` inside
    a boundary still finds its site (the boundary renames no parameter)
    and the amaxes it records reach the step's histories — the largest
    over a looped layer's uses, although the second to last application
    reuse the first's trace — as they do without the flag.  An fp8 step
    keeps one region an application under a list of names too (regions
    inside it cost it 2.7 GB at the cell's size: the control would not
    load)."""
    plain_losses, plain, _ = _unflagged_fp8_histories()
    flagged_losses, flagged, counts = _fp8_histories(remat)
    apps = CFG["num_hidden_layers"] * CFG["total_ut_steps"]
    assert counts == {"block.boundary_regions_total": apps}
    # seven products a layer; the embedding and the head are sites by
    # their names and no ``Dense`` runs them (the loss reads the head)
    layers = {s: h for s, h in plain.items() if ".layer" in s}
    assert len(layers) == 2 * 7 and set(plain) == set(flagged)
    onp.testing.assert_allclose(flagged_losses, plain_losses, atol=1e-5)
    for site, h in layers.items():
        assert min(h.values()) > 0, site
        for k in h:
            assert abs(flagged[site][k] - h[k]) <= 1e-4 * h[k], (site, k)


# ---- what regions inside a region cost in memory ---------------------------

def _kernel_product(x, w):
    """``x @ w`` by a Pallas kernel, in ``x``'s type."""
    from jax.experimental import pallas as pl

    def body(x_ref, w_ref, o_ref):
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...], precision="default",
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)
    (m, k), n = x.shape, w.shape[1]
    return pl.pallas_call(
        body, grid=(m // 256, n // 256),
        in_specs=[pl.BlockSpec((256, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, 256), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((256, 256), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype))(x, w)


@jax.custom_vjp
def _kernel_dense(x, w):
    return _kernel_product(x, w)


_kernel_dense.defvjp(lambda x, w: (_kernel_product(x, w), (x, w)),
                     lambda res, g: (g @ res[1].T, res[0].T @ g))


class _KernelDense(mx.gluon.nn.HybridBlock):
    """A ``Dense`` whose product is a Pallas kernel."""

    def __init__(self, units):
        super().__init__()
        self.weight = mx.gluon.Parameter("weight", shape=(units, units),
                                         dtype="bfloat16")

    def forward(self, x):
        return mx.np.array(_kernel_dense(x._data, self.weight.data()._data))


class _KernelLayer(mx.gluon.nn.HybridBlock):
    def __init__(self, units):
        super().__init__()
        self.up, self.down = _KernelDense(units), _KernelDense(units)

    def forward(self, x):
        h = self.up(x)
        return x + self.down(h * mx.npx.sigmoid(h))


def test_regions_inside_a_region_cost_memory_and_the_flags_take_them_back(
        one_v5e):
    """Eight bf16 layers of two kernel products, each layer flagged with a
    list that keeps what a ``pallas_call`` wrote, compiled for a described
    v5e.  With the products' blocks flagged too they are regions inside
    the layer's, and the program holds more: +41 % here (41.1 -> 57.9 MB;
    +6 % on ``ouro-train-8k``, +17 % on its fp8 control, PERF.md section
    6, PR 44).  The flags decide, so taking the children's gives one
    region a layer again, and its memory.  No form runs a kernel twice:
    the list keeps what a kernel wrote."""
    from jax.experimental.compilation_cache import compilation_cache
    layers, tokens, units = 8, 4096, 1024

    def compiled(flag, children):
        net = mx.gluon.nn.HybridSequential()
        for _ in range(layers):
            net.add(_KernelLayer(units))
        net.initialize()
        for layer in net if flag else ():
            layer.hybridize(remat=["pallas_call"])
            for child in () if children else layer._children.values():
                child.hybridize()
        params = functional.param_arrays(net)

        def loss(p, x):
            out, _ = functional.functional_call(net, p, x, train=True)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=one_v5e)
        lowered, counts = _counted(
            jax.jit(jax.grad(loss)).lower,
            jax.tree_util.tree_map(spec, params),
            jax.ShapeDtypeStruct((tokens, units), jnp.bfloat16,
                                 sharding=one_v5e))
        exe = lowered.compile()
        assert exe.as_text().count("tpu_custom_call") == 2 * layers
        return exe.memory_analysis().temp_size_in_bytes, counts

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        kept, none = compiled(False, False)
        one, counts_one = compiled(True, False)
        nested, counts_nested = compiled(True, True)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert none == {} and counts_one == {
        "block.boundary_regions_total": layers}
    assert counts_nested == {"block.boundary_regions_total": 3 * layers,
                             "block.boundary_nested_total": 2 * layers}
    assert one <= kept      # the list keeps the products: little to save
    assert 1.2 * one < nested < 1.6 * one  # what regions inside it cost
