"""The indexer's top-k as a Pallas pass (ops/pallas/dsa_select.py)
against the XLA bisection it replaces on the chip
(ops/sparse_index.py::_composed_select, its oracle): the mask bit for
bit, rows that tie at their threshold included, the dispatch in
``select_topk``, the ``shard_map`` under a mesh, the scope the kernel is
traced under, the panel counter, and ``nn.SparseIndexer`` end to end.
Interpret mode on the CPU.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import runtime
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import sparse_index
from mxnet_tpu.ops.pallas import dsa_scores, dsa_select
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.parallel.mesh import activation_sharding
from family_harness import eqns as _eqns, kernel_tiles as _counted, \
    pallas_calls as _pallas_calls, pallas_names as _names


def _scores(b, s, kind, seed=0):
    """(b, s, s) float32 scores.  ``normal``: both signs; ``kernel``:
    zeros above the diagonal, as ``mx_dsa_scores`` writes its skipped
    tiles; ``zeros``: a third of the entries ``+0.0`` and a seventh
    ``-0.0`` (``-0.0`` orders under ``+0.0``), so rows tie;
    ``negative``: nothing above ``-0.0``; ``rounded``: whole numbers,
    so every row ties at its threshold."""
    rs = onp.random.RandomState(seed)
    x = rs.randn(b, s, s).astype("float32")
    if kind == "kernel":
        x = onp.tril(x)
    elif kind == "zeros":
        x[:, :, ::3] = 0.0
        x[:, :, 1::7] = -0.0
    elif kind == "negative":
        x = -onp.abs(x)
        x[:, :, 2::5] = -0.0
    elif kind == "rounded":
        x = onp.round(x)
    return jnp.asarray(x)


@functools.lru_cache(maxsize=None)
def _kernel_program(topk, block):
    return jax.jit(lambda i: jnp.swapaxes(dsa_select.select_pass(
        jnp.swapaxes(i, 1, 2), topk, interpret=True, block=block), 1, 2))


def _kernel(scores, topk, block):
    """``select_pass`` on ``select_topk``'s own layouts: one program a
    shape, whatever the kind of scores."""
    return _kernel_program(topk, block)(scores)


@functools.lru_cache(maxsize=None)
def _composed_program(topk):
    return jax.jit(lambda i: sparse_index._composed_select(i, topk))


def _composed(scores, topk):
    return _composed_program(topk)(scores)


# -- the kernel against the composition -------------------------------------

SHAPES = [
    (1, 16, 4, 16),       # one panel
    (2, 64, 16, 16),      # four panels, one wholly under topk
    (1, 64, 64, 32),      # s == topk: every row takes all, no search
    (2, 32, 100, 8),      # topk > s
    (1, 96, 7, 32),       # a topk that is no power of two, three panels
    (3, 48, 1, 16),       # the best key alone, an odd batch
    (1, 128, 40, 128),    # a panel of whole lanes
    (1, 512, 130, 256),   # two panels of two lane tiles
]
IDS = ["one-panel", "batch-2", "s-eq-topk", "topk-gt-s", "topk-7", "topk-1",
       "lanes", "two-panels"]


KINDS = ["normal", "kernel", "zeros", "negative", "rounded"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("b,s,topk,block", SHAPES, ids=IDS)
def test_the_mask_is_the_compositions(b, s, topk, block, kind):
    """Bit for bit, rows that tie at their threshold included (they take
    the composition's second path, the kernel a second bisection):
    exactly ``min(t + 1, topk)`` a row, nothing above the diagonal."""
    scores = _scores(b, s, kind, seed=s + topk)
    got = _kernel(scores, topk, block)
    assert got.dtype == jnp.int8 and got.shape == (b, s, s)
    onp.testing.assert_array_equal(got, _composed(scores, topk))
    rows = onp.minimum(onp.arange(s) + 1, topk)
    onp.testing.assert_array_equal(onp.asarray(got, onp.int32).sum(-1),
                                   rows[None].repeat(b, 0))
    assert not onp.triu(onp.asarray(got), 1).any()


@pytest.mark.parametrize("kind", ["normal", "kernel"])
@pytest.mark.parametrize("b,s,topk,block", SHAPES, ids=IDS)
def test_without_ties_the_mask_is_the_selection(b, s, topk, block, kind):
    """Distinct scores: all of them while ``t < topk``, then the keys
    ``lax.top_k`` takes."""
    scores = _scores(b, s, "normal", seed=3 * s + topk)
    if kind == "kernel":    # zeros above the diagonal tie with nothing
        scores = jnp.tril(scores)
    got = onp.asarray(_kernel(scores, topk, block))
    rows = onp.minimum(onp.arange(s) + 1, topk)
    for t in range(min(topk, s)):
        assert got[:, t, :t + 1].all()
    best = onp.asarray(jax.jit(lambda i: jax.lax.top_k(jnp.where(
        jnp.tril(jnp.ones((s, s), bool)), i, -jnp.inf), min(topk, s))[1])(
            scores))
    for t in range(s):
        for i in range(b):
            assert set(got[i, t].nonzero()[0]) == set(best[i, t, :rows[t]])


def test_a_sequence_the_block_does_not_divide_is_refused():
    with pytest.raises(ValueError, match="no multiple of the block"):
        dsa_select.select_pass(jnp.zeros((1, 48, 48)), 4, interpret=True,
                               block=32)
    # at a sequence no block divides ``panel_block`` has none to give
    with pytest.raises(ValueError, match="no multiple of the block"):
        dsa_select.select_pass(jnp.zeros((1, 48, 48)), 4, interpret=True)


def test_the_passes_are_one_loop_of_32_and_the_compares_signed():
    """Read the kernel: one loop of 32 passes holding one chunk loop
    (nothing unrolled: set-up must not grow), the ties' second bisection
    a loop of the key index's bits under a condition of its own, every
    comparison of keys on int32, and no unsigned value in the body."""
    call, = _pallas_calls(jax.make_jaxpr(lambda i: dsa_select.select_pass(
        i, 8, interpret=True, block=16))(jnp.zeros((1, 64, 64))).jaxpr)
    body = call.params["jaxpr"]
    for e in _eqns(body):
        for v in list(e.invars) + list(e.outvars):
            assert "uint" not in str(getattr(v.aval, "dtype", ""))

    def loops(jaxpr):
        return [e for e in jaxpr.eqns if e.primitive.name in ("while", "scan")]

    def conds(jaxpr):
        return [e for e in jaxpr.eqns if e.primitive.name == "cond"]

    assert len(loops(body)) == 3                    # keys, mask, zeros
    search, = conds(body)
    search = search.params["branches"][1].jaxpr
    passes, = loops(search)
    assert passes.primitive.name == "scan" and passes.params["length"] == 32
    assert len(loops(passes.params["jaxpr"].jaxpr)) == 1    # the chunks
    ties, = conds(search)
    ties = ties.params["branches"][1].jaxpr
    above, index = loops(ties)
    assert above.primitive.name == "while"          # ``key > thr``, once
    assert index.primitive.name == "scan" and index.params["length"] == 7


# -- select_topk: the dispatch ----------------------------------------------

def _on_the_kernel(monkeypatch, block):
    """A CPU that takes the TPU's route, its kernels interpreted."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    monkeypatch.setattr(dsa_select, "BLOCKS", (block,))


def _select(topk):
    return jax.jit(lambda scores: sparse_index.select_topk(scores, topk))


@pytest.mark.parametrize("s", [16, 64, 512])
def test_off_the_tpu_select_topk_is_the_composition(s):
    """On the CPU no kernel is traced and no panel counted, whatever the
    sequence."""
    scores = _scores(1, s, "normal")
    got, tiles = _counted(_select(5), scores)
    assert tiles == {}
    assert _names(_select(5), scores) == []
    onp.testing.assert_array_equal(got, _composed(scores, 5))


@pytest.mark.parametrize("s", [40, 72, 100])
def test_a_ragged_sequence_falls_to_the_composition(monkeypatch, s):
    """On the TPU's route a sequence no block divides takes the
    composition: no kernel, no panel, the composition's mask."""
    _on_the_kernel(monkeypatch, 16)
    scores = _scores(1, s, "zeros", seed=s)
    got, tiles = _counted(_select(9), scores)
    assert tiles == {}
    assert _names(_select(9), scores) == []
    onp.testing.assert_array_equal(got, _composed(scores, 9))


def test_a_panel_vmem_cannot_hold_falls_to_the_composition(monkeypatch):
    """``fits`` reckons the scores' panel and the mask's twice and the
    keys' scratch: a longer sequence takes a narrower panel, then none;
    the cell's takes the widest."""
    assert dsa_select.panel_block(8192) == 512
    assert dsa_select.panel_block(8192 + 256) == 256
    assert dsa_select.panel_block(16384) == 256
    assert dsa_select.panel_block(32768) == 128
    assert dsa_select.panel_block(8192 + 64) is None        # ragged
    assert not dsa_select.fits(1 << 16)                     # no panel fits
    assert dsa_select._resident(8192, 512) + dsa_scores._VMEM_ROOM \
        <= dsa_scores._VMEM_MAX
    _on_the_kernel(monkeypatch, 16)
    scores = _scores(1, 32, "normal")
    assert _names(_select(4), scores) == ["mx_dsa_select"]
    monkeypatch.setattr(dsa_select, "_VMEM_MAX", dsa_select._VMEM_ROOM)
    assert _names(_select(4), scores) == []


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("b,s,topk", [(2, 64, 16), (1, 48, 48), (1, 32, 50),
                                      (2, 80, 3)])
def test_on_the_tpu_select_topk_is_the_kernel(monkeypatch, b, s, topk, kind):
    """At a sequence a block divides ``select_topk`` traces one
    ``mx_dsa_select`` — counted: panels searched + skipped = all panels,
    a call — and gives the composition's mask, ties or none."""
    scores = _scores(b, s, kind, seed=s + topk)
    want = _composed(scores, topk)
    _on_the_kernel(monkeypatch, 16)
    got, tiles = _counted(_select(topk), scores)
    assert got.dtype == jnp.int8
    onp.testing.assert_array_equal(got, want)
    searched = sum((a + 1) * 16 > topk for a in range(s // 16))
    assert tiles == {"dsa_select": {"computed": searched * b,
                                    "skipped": (s // 16 - searched) * b}}
    assert _names(_select(topk), scores) == ["mx_dsa_select"]
    rows = onp.minimum(onp.arange(s) + 1, topk)
    onp.testing.assert_array_equal(onp.asarray(got, onp.int32).sum(-1),
                                   rows[None].repeat(b, 0))


def test_the_cells_panels(monkeypatch):
    """16 panels of 512 at batch 1 and ``topk`` 2048: 12 searched, 4
    wholly under ``topk`` (counted where the call is traced; nothing
    runs)."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    spec = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.float32)
    _, tiles = _counted(jax.eval_shape, _select(2048), spec)
    assert tiles == {"dsa_select": {"computed": 12, "skipped": 4}}


def test_ties_at_the_threshold_go_to_the_lower_index(monkeypatch):
    """``tests/test_keye.py``'s tie cases through the kernel: rows that
    tie take the first of the equals along the row, as the
    composition's running count does."""
    _on_the_kernel(monkeypatch, 8)
    s, topk = 16, 4
    scores = onp.zeros((1, s, s), onp.float32)
    scores[0, :, 3] = 2.0           # one clear winner, the rest tie at 0
    scores[0, 10, 12:] = 5.0        # above the diagonal: never taken
    scores[0, 12] = -1.0            # a whole row of negative ties

    def select():
        assert _names(_select(topk), jnp.asarray(scores)) == [
            "mx_dsa_select"]
        sel = onp.asarray(_select(topk)(jnp.asarray(scores)))
        onp.testing.assert_array_equal(
            sel, _composed(jnp.asarray(scores), topk))
        return sel

    sel = select()
    assert sel[0, 10].nonzero()[0].tolist() == [0, 1, 2, 3]
    assert sel[0, 12].nonzero()[0].tolist() == [0, 1, 2, 3]
    scores[0, 10, 3] = 2.0
    scores[0, 10, 7] = 1.0
    assert select()[0, 10].nonzero()[0].tolist() == [0, 1, 3, 7]
    scores[0, 15, :] = onp.linspace(-3, 3, s)
    assert select()[0, 15].nonzero()[0].tolist() == [12, 13, 14, 15]
    # -0.0 orders under +0.0: of a row of zeros the positive ones first
    scores[0, 14, :] = 0.0
    scores[0, 14, [0, 2, 4]] = -0.0
    assert select()[0, 14].nonzero()[0].tolist() == [1, 3, 5, 6]
    # one row that ties in a panel of rows that do not
    scores = onp.random.RandomState(0).randn(1, s, s).astype("float32")
    scores[0, 13, 2:9] = scores[0, 13].max() + 1.0
    assert select()[0, 13].nonzero()[0].tolist() == [2, 3, 4, 5]


def test_the_kernel_keeps_the_callers_scope(monkeypatch):
    """``dsa_select_ms.train`` reads operations whose name holds
    ``mx.dsa.select``: exactly one ``mx_dsa_select`` is traced, under
    the caller's scope, and the lowered text carries both names."""
    _on_the_kernel(monkeypatch, 16)
    scores = _scores(1, 32, "normal")

    def select(scores):
        with jax.named_scope("mx.dsa.select"):
            return sparse_index.select_topk(scores, 4)

    calls = list(_pallas_calls(jax.make_jaxpr(select)(scores).jaxpr))
    assert [e.params["name"] for e in calls] == ["mx_dsa_select"]
    assert "mx.dsa.select" in str(calls[0].source_info.name_stack)
    text = jax.jit(select).lower(scores).as_text(debug_info=True)
    assert "mx.dsa.select" in text and "mx_dsa_select" in text


# -- under a mesh -----------------------------------------------------------

def test_under_a_mesh_the_kernel_sits_in_a_shard_map(monkeypatch):
    """On a dp x tp mesh each device searches its rows of the batch; the
    mask is the composition's, and the call lowers for the TPU with the
    kernel inside (GSPMD partitions no Mosaic call)."""
    _on_the_kernel(monkeypatch, 16)
    seen = []
    real = dsa_select.select_pass

    def select_pass(scores, topk, **kw):
        seen.append(scores.shape)
        return real(scores, topk, **kw)

    monkeypatch.setattr(dsa_select, "select_pass", select_pass)
    b, s, topk = 4, 32, 5
    scores = _scores(b, s, "zeros", seed=5)
    want = _composed(scores, topk)
    mesh = MeshConfig(dp=2, tp=2).build(jax.devices()[:4])
    with activation_sharding(mesh):
        got = _select(topk)(scores)
    assert seen[-1] == (2, s, s)
    onp.testing.assert_array_equal(got, want)
    # for Mosaic: panels of 128 lanes, nothing interpreted
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: False)
    monkeypatch.setattr(dsa_select, "BLOCKS", (128,))
    s = 256
    with activation_sharding(mesh):
        text = _select(64).trace(jax.ShapeDtypeStruct(
            (b, s, s), jnp.float32)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert seen[-1] == (2, s, s)
    assert text.count("tpu_custom_call") == 1
    manual = text[:text.index("tpu_custom_call")].rindex(
        "sdy.manual_computation")
    assert '[{"dp"}, {}, {}]' in text[manual:].split("\n")[0]


# -- nn.SparseIndexer end to end --------------------------------------------

def _indexer(seed=0):
    net = nn.SparseIndexer(32, num_heads=4, head_dim=16, topk=6)
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mx.random.seed(seed)
    return net


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_indexer_selects_and_counts_the_same_either_way(monkeypatch,
                                                               seed):
    """``nn.SparseIndexer`` on a seeded input, the scores the
    composition's either way: the TPU's route for the top-k picks the
    same keys and reports the same ``selected_pairs`` and
    ``select_grid``."""
    from mxnet_tpu import functional
    net = _indexer(seed)
    x = mx.np.array(onp.random.RandomState(seed).randn(2, 48, 32).astype(
        "float32"))
    net.infer_shape(x[:1, :1])

    def run():
        (_, sel), counts = jax.jit(lambda p, x_: functional.functional_call(
            net, p, x_, train=True))(functional.param_arrays(net), x._data)
        return (onp.asarray(sel), onp.asarray(counts["selected_pairs"]),
                onp.asarray(counts["select_grid"]))

    want = run()
    _on_the_kernel(monkeypatch, 16)
    # the scores stay the composition's: 48 is no multiple of their block
    names = _names(lambda x: net(mx.np.array(x))[1]._data, x._data)
    assert names == ["mx_dsa_select"]
    got = run()
    for a, r in zip(got, want):
        onp.testing.assert_array_equal(a, r)
    assert int(got[1][0]) == 2 * sum(min(t + 1, 6) for t in range(48)) \
        == int(got[2].sum())
