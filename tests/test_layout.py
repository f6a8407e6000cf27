"""parallel/layout.py — the state-layout plan, held without a device.

No step is built and no array is placed: a ``StateLayout`` is planned from
shapes, specs and axis sizes, and its checkpoint transforms run on host
numpy.  What the plan decides per leaf (the form and spec of its optimizer
state, the pp family it is stacked into) and the layout-free form of a
checkpoint are asserted here once, for every step that asks the layout.
"""
import itertools

import numpy as onp
import pytest

from jax.sharding import PartitionSpec as P

from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.layout import (DP, FLAT, PARAM, StateLayout,
                                       megatron_specs)

# a two-layer transformer-shaped block, per-layer names as a Block has them
TRAINABLE = {
    "embed.weight": (10, 8),                       # 80 elements
    "head.bias": (10,),
    "dec.layer0.ffn_1.weight": (16, 8),            # column-parallel
    "dec.layer1.ffn_1.weight": (16, 8),
    "dec.layer0.ffn_1.bias": (16,),                # its only dim takes tp
    "dec.layer1.ffn_1.bias": (16,),
    "dec.layer0.ln.gamma": (8,),
    "dec.layer1.ln.gamma": (8,),
    "dec.layer0.odd.weight": (3, 5),               # no dim 2 or 4 divides
    "dec.layer1.odd.weight": (3, 5),
}
AUX = {"dec.layer0.bn.running_mean": (8,), "dec.layer1.bn.running_mean": (8,),
       "counter": ()}


def _plan(zero=0, dp=1, tp=1, pp=1, **kw):
    axes = {"dp": dp, "pp": pp, "sp": 1, "tp": tp}   # MeshConfig's: all there
    specs = megatron_specs({**TRAINABLE, **AUX})
    return StateLayout(TRAINABLE, AUX, specs, axes, zero=zero, **kw)


def _pad(size, dp):
    return -(-size // dp) * dp


def _expected(zero, dp, tp, pp):
    """{leaf: (form, state_shape, state_spec)}, written out by hand."""
    out = {}

    def flat(size):   # a replicated leaf under zero>0; None at zero 0
        return (FLAT, (_pad(size, dp),), P("dp")) if zero else None

    # 10 rows: a quarter of the ravel would cut them, 8 columns divide
    out["embed.weight"] = (
        (DP, (10, 8), P(None, "dp")) if zero and dp == 4
        else flat(80) or (PARAM, (10, 8), P()))
    out["head.bias"] = flat(10) or (PARAM, (10,), P())
    if pp == 1:
        for i in (0, 1):
            # tensor-sharded: dp goes into the free dimension (8)
            out[f"dec.layer{i}.ffn_1.weight"] = (
                (DP, (16, 8), P("tp", "dp")) if zero
                else (PARAM, (16, 8), P("tp", None)))
            # no free dimension: state shards like the weight
            out[f"dec.layer{i}.ffn_1.bias"] = (PARAM, (16,), P("tp"))
            out[f"dec.layer{i}.ln.gamma"] = flat(8) or (PARAM, (8,), P())
            out[f"dec.layer{i}.odd.weight"] = \
                flat(15) or (PARAM, (3, 5), P())
        return out
    # pp 2: each family is one (2, ...) leaf over "pp", hence tensor-sharded
    out["dec.layer*.ffn_1.weight"] = (
        (DP, (2, 16, 8), P("pp", "tp", "dp")) if zero
        else (PARAM, (2, 16, 8), P("pp", "tp", None)))
    out["dec.layer*.ffn_1.bias"] = (PARAM, (2, 16), P("pp", "tp"))
    out["dec.layer*.ln.gamma"] = (
        (DP, (2, 8), P("pp", "dp")) if zero else (PARAM, (2, 8), P("pp")))
    # (2, 3, 5): dp 2 and 4 divide neither free dimension -> falls back;
    # dp 1 divides everything and takes the largest free one
    out["dec.layer*.odd.weight"] = (
        (DP, (2, 3, 5), P("pp", None, "dp")) if zero and dp == 1
        else (PARAM, (2, 3, 5), P("pp")))
    return out


@pytest.mark.parametrize(
    "zero,dp,tp,pp",
    list(itertools.product((0, 1, 2), (1, 2, 4), (1, 2), (1, 2))))
def test_plan_names_form_and_spec_per_leaf(zero, dp, tp, pp):
    lay = _plan(zero, dp, tp, pp)
    want = _expected(zero, dp, tp, pp)
    assert set(lay.leaves) == set(want)
    for n, (form, sshape, sspec) in want.items():
        leaf = lay.leaves[n]
        assert (leaf.form, leaf.state_shape, leaf.state_spec) == \
            (form, sshape, sspec), n
    for form in (PARAM, FLAT, DP):
        assert lay.names(form) == [n for n in lay.leaves
                                   if want[n][0] == form]
    assert lay.replicated_dp == (
        ["embed.weight"] if zero and dp == 4 else [])
    if pp == 2:
        assert lay.families["dec.layer*.ln.gamma"] == (
            "dec.layer0.ln.gamma", "dec.layer1.ln.gamma")
        assert lay.families["dec.layer*.bn.running_mean"] == (
            "dec.layer0.bn.running_mean", "dec.layer1.bn.running_mean")
        assert lay.param_spec("dec.layer*.bn.running_mean") == P("pp")
    else:
        assert lay.families == {}


@pytest.mark.parametrize("shape,zero,dp,want", [
    # GPT-2's tied table: a quarter of its ravel is 12564.25 rows
    ((50257, 1280), 1, 4, (DP, (50257, 1280), P(None, "dp"))),
    ((50257, 1280), 2, 4, (DP, (50257, 1280), P(None, "dp"))),
    ((50257, 1280), 1, 2, (DP, (50257, 1280), P(None, "dp"))),
    # whole rows to every rank: flat, as before
    ((1024, 1280), 1, 4, (FLAT, (1310720,), P("dp"))),
    ((1280,), 1, 4, (FLAT, (1280,), P("dp"))),
    ((50257, 1280), 1, 1, (FLAT, (64328960,), P("dp"))),
    # no dimension divides: flat, padded
    ((7, 5), 1, 4, (FLAT, (36,), P("dp"))),
    ((7,), 1, 4, (FLAT, (8,), P("dp"))),
    ((), 1, 4, (FLAT, (4,), P("dp"))),
    # the largest dimension that divides, not the first
    ((7, 8, 64), 1, 4, (DP, (7, 8, 64), P(None, None, "dp"))),
    ((50257, 1280), 0, 4, (PARAM, (50257, 1280), P())),
])
def test_plan_of_a_replicated_leaf(shape, zero, dp, want):
    """A replicated leaf takes the DP form only where 1/dp flat shards would
    cut its rows and some dimension divides; the plan needs no array."""
    lay = StateLayout({"w": shape}, {}, {}, {"dp": dp}, zero=zero)
    leaf = lay.leaves["w"]
    assert (leaf.form, leaf.state_shape, leaf.state_spec) == want
    assert lay.replicated_dp == (["w"] if want[0] == DP else [])
    census = lay.census({"w": 4})
    size = 4 * int(onp.prod(shape, dtype=onp.int64))
    assert census[f"{want[0]}_leaves"] == 1
    assert census[f"{want[0]}_bytes"] == size
    assert census["replicated_dp_bytes"] == (size if want[0] == DP else 0)
    assert sum(census[f"{f}_leaves"] for f in (PARAM, FLAT, DP)) == 1


def test_flat_padding_is_what_the_old_counter_summed():
    """Dense 8 -> 10 at dp 4: bias 10 -> 12 flat and padded; the weight's
    10 rows do not divide and its 8 columns do, so it keeps its shape."""
    lay = StateLayout({"weight": (10, 8), "bias": (10,)}, {}, {},
                      {"dp": 4}, zero=2)
    assert lay.leaves["weight"].state_shape == (10, 8)
    assert lay.leaves["weight"].state_spec == P(None, "dp")
    assert lay.leaves["bias"].state_shape == (12,)
    b = onp.arange(10, dtype="float32")
    flat = lay.to_state_form("bias", b)
    assert isinstance(flat, onp.ndarray) and flat.shape == (12,)
    assert (flat[:10] == b).all() and (flat[10:] == 0).all()
    onp.testing.assert_array_equal(lay.from_state_form("bias", flat), b)
    w = onp.ones((10, 8), "float32")
    assert lay.to_state_form("weight", w) is w
    assert StateLayout({"weight": (10, 8)}, {}, {}, {"dp": 2}, zero=2
                       ).to_state_form("weight", w).shape == (80,)


@pytest.mark.parametrize("kw,match", [
    (dict(zero=1, axes={"tp": 2}), "requires a 'dp' mesh axis"),
    (dict(axes={"dp": 1, "pp": 4}), "not divisible into 4 pipeline"),
    (dict(axes={"dp": 1, "pp": 2}, trainable={"w": (4,)}), "needs repeated"),
    (dict(axes={"dp": 4}, fp8=True, trainable={"w": (4,)}),
     "no eligible sites"),
])
def test_plan_refuses(kw, match):
    with pytest.raises(MXNetError, match=match):
        StateLayout(kw.get("trainable", TRAINABLE), {}, {}, kw["axes"],
                    zero=kw.get("zero", 0), fp8=kw.get("fp8", False))


def test_stack_unstack_and_partial_family():
    lay = _plan(pp=2)
    d = {n: onp.full(s, i, "float32")
         for i, (n, s) in enumerate(TRAINABLE.items())}
    stacked = lay.stack(d)
    assert list(stacked) == list(lay.leaves)        # the step's dict order
    assert stacked["dec.layer*.odd.weight"].shape == (2, 3, 5)
    back = lay.unstack(stacked)
    assert set(back) == set(d)
    for n in d:
        onp.testing.assert_array_equal(back[n], d[n])
    with pytest.raises(MXNetError, match="only 1/2 member layers"):
        lay.stack({"dec.layer0.bn.running_mean": onp.zeros(8)})
    assert lay.stack({}) == {} and _plan().stack(d) is d


def test_buckets_and_residual_shapes_are_planned_from_names():
    lay = _plan(dp=4, bucket_elems=100)
    sizes = [[s for _, _, s in b] for b in lay.buckets]
    names = [n for b in lay.buckets for n, _, _ in b]
    assert names == sorted(TRAINABLE)               # dp-size invariant
    assert all(sum(b) <= 100 or len(b) == 1 for b in sizes)
    assert lay.resid_shapes == {
        f"bucket{i}": (4, sum(b)) for i, b in enumerate(sizes)}
    assert _plan(dp=2, bucket_elems=100).buckets == lay.buckets
    assert _plan(dp=4).buckets == [] and _plan(dp=4).resid_shapes == {}


# -- the checkpoint's layout-free form --------------------------------------

PLANS = {
    "plain": dict(),
    "dp4-zero1": dict(zero=1, dp=4),
    "dp2-zero1": dict(zero=1, dp=2),
    "dp2-tp2-zero2": dict(zero=2, dp=2, tp=2),
    "dp2-tp2-pp2-zero1": dict(zero=1, dp=2, tp=2, pp=2),
    "pp2": dict(pp=2),
    "dp4-zero1-ef": dict(zero=1, dp=4, bucket_elems=100),
    "dp2-ef": dict(dp=2, bucket_elems=100),
}


def _state(lay, seed, fp8_sites=()):
    """A step's state under ``lay`` as host numpy: every leaf distinct,
    two optimizer-state leaves a parameter (one None between, as Adam with
    an unused slot would have), histories and residuals where planned."""
    rs = onp.random.RandomState(seed)

    def rand(shape):
        return onp.asarray(rs.standard_normal(shape), "float32")

    tr = lay.stack({n: rand(s) for n, s in TRAINABLE.items()})
    aux = lay.stack({n: rand(s) for n, s in AUX.items()})
    states = {n: (lay.to_state_form(n, rand(v.shape)), None,
                  lay.to_state_form(n, rand(v.shape)))
              for n, v in tr.items()}
    fp8 = {s: {k: rs.rand(4).astype("float32") for k in ("x", "w", "g")}
           for s in fp8_sites}
    resid = {b: rand(shape) for b, shape in lay.resid_shapes.items()}
    return tr, aux, states, {"fp8": fp8, "resid": resid}


def _assert_same(a, b):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
        assert a[k].tobytes() == b[k].tobytes(), k


@pytest.mark.parametrize("name", sorted(PLANS))
def test_canonical_round_trip_is_bit_equal(name):
    lay = _plan(**PLANS[name])
    state = _state(lay, 3, fp8_sites=("dec.layer0.ffn_1.weight",))
    canon = lay.to_canonical(*state)
    # layout-free: per-layer names, parameter shapes, whatever the plan
    for n, s in TRAINABLE.items():
        assert canon[f"trainable/{n}"].shape == s
        assert canon[f"state/{n}/0"].shape == s
        assert canon[f"state/{n}/1"].shape == s     # None leaves are skipped
    assert set(k.split("/")[0] for k in canon) >= {"trainable", "aux",
                                                   "state", "fp8"}
    back = lay.from_canonical(
        canon, _state(lay, 99, fp8_sites=("dec.layer0.ffn_1.weight",)))
    for n, s in back[2].items():                    # state form restored
        assert s[1] is None
        assert s[0].shape == lay.leaves[n].state_shape
    if lay.resid_shapes:
        # residuals come back as the canonical SUM in rank 0, zero elsewhere
        for b, v in back[3]["resid"].items():
            assert v.shape == lay.resid_shapes[b] and not v[1:].any()
    _assert_same(lay.to_canonical(*back), canon)


@pytest.mark.parametrize("src,dst", [
    ("dp4-zero1", "dp2-tp2-zero2"), ("dp2-tp2-pp2-zero1", "plain"),
    ("plain", "dp2-tp2-pp2-zero1"), ("pp2", "dp4-zero1"),
    ("dp4-zero1-ef", "dp2-ef"),
    # embed.weight FLAT where it was saved (as every plan before PR 31 had
    # it), DP where it is loaded -- and back
    ("dp2-zero1", "dp4-zero1"), ("dp4-zero1", "dp2-zero1")])
def test_canonical_restores_across_plans(src, dst):
    """The cross-layout restore, without devices: what one plan wrote,
    another reads into ITS forms and writes back unchanged."""
    a, b = _plan(**PLANS[src]), _plan(**PLANS[dst])
    if {src, dst} == {"dp2-zero1", "dp4-zero1"}:
        assert {p.leaves["embed.weight"].form for p in (a, b)} == {FLAT, DP}
    canon = a.to_canonical(*_state(a, 5))
    there = b.from_canonical(canon, _state(b, 77))
    for n, s in there[2].items():
        assert s[0].shape == b.leaves[n].state_shape
    _assert_same(b.to_canonical(*there), canon)


def test_from_canonical_tolerates_missing_extras_and_history_length():
    lay = _plan(dp=4, bucket_elems=100)
    site = "dec.layer0.ffn_1.weight"
    like = _state(lay, 1, fp8_sites=(site,))
    canon = lay.to_canonical(*_state(lay, 2))        # a pre-fp8, pre-EF run?
    canon = {k: v for k, v in canon.items() if not k.startswith("efresid/")}
    back = lay.from_canonical(canon, like)
    assert back[3]["fp8"][site]["x"] is like[3]["fp8"][site]["x"]
    assert back[3]["resid"]["bucket0"] is like[3]["resid"]["bucket0"]
    canon[f"fp8/{site}/x"] = onp.arange(6, dtype="float32")   # longer
    canon[f"fp8/{site}/w"] = onp.arange(2, dtype="float32")   # shorter
    back = lay.from_canonical(canon, like)
    onp.testing.assert_array_equal(back[3]["fp8"][site]["x"], [0, 1, 2, 3])
    onp.testing.assert_array_equal(back[3]["fp8"][site]["w"], [0, 1, 0, 0])
