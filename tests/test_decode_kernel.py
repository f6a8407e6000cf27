"""The decode step's cached attention read as a Pallas kernel
(ops/pallas/decode_attention.py) through the interpreter on the CPU, at
small shapes: the kernel against the XLA composition — the output and the
cache after the write — at ragged positions with idle slots,
``decode_attention``'s dispatch between the two with its counter, a tiny
GPT through ``ServeEngine`` by either, the engine's
``decode_rows_read_share``, and the decode program compiled for a described
v5e at the serve cells' cache shape: nothing there copies a cache leaf.
"""
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
from family_harness import one_v5e  # noqa: F401  (a fixture)
import mxnet_tpu as mx
from mxnet_tpu import runtime, telemetry
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
from mxnet_tpu.ops import attention
from mxnet_tpu.ops.pallas import decode_attention as kernel

SLOTS, MAX_SEQ, HEADS, WIDTH, BLOCK = 8, 64, 2, 128, 16
#: a slot's position: row 0, a block's last row, a block's first row, the
#: middle of a block, the cache's last row — and two slots that are idle
POSITIONS = (0, BLOCK - 1, BLOCK, 37, MAX_SEQ - 1, 5, 2 * BLOCK, 50)
LIVE = (True, True, True, True, True, False, True, False)


def _take_the_kernel(monkeypatch):
    """From here on a CPU takes the TPU's route, the kernel interpreted, in
    blocks of ``BLOCK`` rows (the shapes would give one block a slot)."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    monkeypatch.setattr(kernel, "_block", lambda *shape: BLOCK)


def _operands(dtype, seed=0):
    rs = onp.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32).astype(dtype)  # noqa: E731,E501
    return (f(SLOTS, 1, WIDTH), f(SLOTS, 1, WIDTH), f(SLOTS, 1, WIDTH),
            f(SLOTS, MAX_SEQ, WIDTH), f(SLOTS, MAX_SEQ, WIDTH))


def _step(q, k, v, kc, vc, live=None):
    out = attention.decode_attention(
        q, k, v, kc, vc, jnp.asarray(POSITIONS, jnp.int32), HEADS,
        None if live is None else jnp.asarray(live))
    return [jnp.asarray(getattr(a, "_data", a)) for a in out]


@pytest.mark.parametrize("live", [None, LIVE], ids=["all", "idle"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_reads_what_the_composition_reads(dtype, live,
                                                     monkeypatch):
    """Every live slot's output at its own position, whichever block it
    ends in, and the cache after the write, kernel against composition;
    an idle slot's output is zeros.  float32 to rounding; bfloat16 to a
    unit of the output's last place (the kernel keeps float32 scores, the
    composition rounds them)."""
    args = _operands(dtype)
    want, kc_want, vc_want = _step(*args)
    _take_the_kernel(monkeypatch)
    got, kc_got, vc_got = _step(*args, live=live)
    assert got.dtype == want.dtype and got.shape == (SLOTS, 1, WIDTH)
    onp.testing.assert_array_equal(kc_got, kc_want)
    onp.testing.assert_array_equal(vc_got, vc_want)
    rows = onp.asarray(POSITIONS)
    for slot in range(SLOTS):
        assert onp.array_equal(kc_got[slot, rows[slot]], args[1][slot, 0])
    read = onp.asarray(LIVE if live else (True,) * SLOTS)
    tol = 1e-5 if dtype == "float32" else 2e-2
    onp.testing.assert_allclose(
        onp.asarray(got, onp.float32)[read],
        onp.asarray(want, onp.float32)[read], atol=tol, rtol=tol)
    assert not onp.asarray(got, onp.float32)[~read].any()


@pytest.mark.parametrize("heads,width", [(3, 384), (16, 1024), (1, 128)])
def test_the_kernel_at_other_head_counts(heads, width):
    """Heads that do not fill a sublane tile, GPT-2 medium's sixteen, one:
    each head reads its own ``dim`` columns of the lane-dense row."""
    rs = onp.random.RandomState(3)
    q = jnp.asarray(rs.randn(3, 1, width), jnp.float32)
    k = jnp.asarray(rs.randn(3, 32, width), jnp.float32)
    v = jnp.asarray(rs.randn(3, 32, width), jnp.float32)
    rows = jnp.asarray([32, 0, 9], jnp.int32)
    got = kernel.decode_read(q, k, v, rows, heads, block=8, interpret=True)
    want = attention._step_attend(
        q, attention._heads_apart(k, heads), attention._heads_apart(v, heads),
        rows - 1, heads)
    onp.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)
    onp.testing.assert_allclose(got[2], want[2], atol=1e-5, rtol=1e-5)
    assert not onp.asarray(got[1]).any()


@pytest.mark.parametrize("shape,itemsize,takes", [
    ((1024, 1024), 2, True),        # the serve cells' cache
    ((1024, 768), 4, True),         # GPT-2 small, float32
    ((32, 32), 4, False),           # a row that fills no register
    ((1000, 1024), 2, False),       # no whole sublane tiles of rows
    ((1024, 1024), 1, False),       # int8 values: the composition's
], ids=["cell", "small", "narrow", "ragged", "int8"])
def test_fits(shape, itemsize, takes):
    assert kernel.fits(*shape, itemsize) is takes
    if takes:
        block = kernel._block(*shape, itemsize)
        assert shape[0] % block == 0
        assert block * shape[1] * itemsize <= kernel._BLOCK_BYTES


def test_the_dispatch_and_its_counter(monkeypatch):
    """``decode_attention`` takes the kernel where the route and the shapes
    allow, one counted a traced call; a narrow cache, an int8 cache and a
    CPU take the composition and count nothing."""
    assert telemetry.CATALOG["serve.decode_kernel_calls_total"][0] \
        == "counter"
    _take_the_kernel(monkeypatch)
    args = _operands("float32")
    _, counts = H.counters("serve.decode", _step, *args)
    assert counts == {"serve.decode_kernel_calls_total": 1}
    assert H.pallas_names(lambda *a: _step(*a), *args) == ["mx_decode_attn"]
    assert attention.decode_read_block(args[3]) == BLOCK
    narrow = jnp.zeros((SLOTS, MAX_SEQ, 32))
    assert attention.decode_read_block(narrow) is None
    assert attention.decode_read_block(
        (narrow.astype(jnp.int8), narrow[..., :2])) is None
    small = [a[..., :32] for a in args]
    _, counts = H.counters("serve.decode", _step, *small)
    assert counts == {}
    assert H.pallas_names(lambda *a: _step(*a), *small) == []


def test_a_cpu_takes_the_composition():
    args = _operands("float32")
    assert attention.decode_read_block(args[3]) is None
    _, counts = H.counters("serve.decode", _step, *args)
    assert counts == {}
    assert H.pallas_names(lambda *a: _step(*a), *args) == []


def _tiny():
    mx.random.seed(11)
    net = GPTForCausalLM(vocab_size=97, units=WIDTH, hidden_size=256,
                         num_layers=2, num_heads=HEADS, max_length=MAX_SEQ,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    return net


PROMPTS = ([5, 9, 2], [7] * 15, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13,
                                  14, 15, 16, 17], [3, 1])


def _serve(net, **kw):
    eng = mx.serve.load(net, max_slots=4, buckets="4,32", **kw)
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(PROMPTS, (20, 6, 12, 3))]
    eng.run()
    return eng, [r.generated for r in reqs]


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["plain", "prefix"])
def test_an_engine_by_the_kernel_emits_the_compositions_tokens(
        prefix_cache, monkeypatch):
    """A tiny GPT served through ``ServeEngine``, its decode step's read
    by the kernel (interpreted) and by the composition: the same greedy
    tokens a request, slots idling as requests finish; the kernel's
    engine reads a share of the cache's rows, the composition's all."""
    net = _tiny()
    plain, want = _serve(net, prefix_cache=prefix_cache)
    assert plain.stats()["decode_rows_read_share"] == 1.0
    _take_the_kernel(monkeypatch)
    eng, got = _serve(net, prefix_cache=prefix_cache)
    assert got == want
    assert eng._read_block == BLOCK
    share = eng.stats()["decode_rows_read_share"]
    # four slots of 64 rows; no request passes 34 rows: at most three
    # blocks of 16 a live slot, and slots idle at the end
    assert 0.0 < share <= 3 * BLOCK / MAX_SEQ


def test_the_share_is_counted_from_the_slot_table():
    """``decode_rows_read_share`` by hand: whole blocks up to each live
    slot's position, over ``max_slots x max_seq`` a step; None before a
    step."""
    eng = mx.serve.load(_tiny(), max_slots=4, buckets="4,32")
    assert eng.stats()["decode_rows_read_share"] is None
    eng._read_block = BLOCK
    a = mx.serve.Request(0, [1] * 15, 8)
    a.generated = [4]               # 16 rows: one block
    b = mx.serve.Request(1, [1] * 15, 8)
    b.generated = [4, 4]            # 17 rows: two blocks
    c = mx.serve.Request(2, [1] * 70, 8)    # past the cache: all of it
    assert eng._rows_covered({0: a}) == BLOCK
    assert eng._rows_covered({0: a, 2: b}) == 3 * BLOCK
    assert eng._rows_covered({1: c}) == MAX_SEQ
    eng._read_block = None
    assert eng._rows_covered({0: a}) == 4 * MAX_SEQ


def test_cache_rows_reads_what_prefill_and_decode_wrote():
    """``ServeEngine.cache_rows(slot, size)``: ``(k, v)``, each ``(layers,
    size, n_embd)`` in the cache's type, the rows a request's programs
    wrote; an int8 cache is refused."""
    net = _tiny()
    eng = mx.serve.load(net, max_slots=2, buckets="4,32")
    req = eng.submit([5, 9, 2, 4], max_new_tokens=3)
    eng.run()
    k, v = eng.cache_rows(0, 8)
    assert k.shape == v.shape == (2, 8, WIDTH) and k.dtype == jnp.float32
    leaf_k, leaf_v = eng._cache[1]
    assert leaf_k.shape == (2, MAX_SEQ, WIDTH)
    onp.testing.assert_array_equal(k[1], leaf_k[0, :8])
    onp.testing.assert_array_equal(v[1], leaf_v[0, :8])
    written = len(req.prompt) + len(req.generated) - 1
    assert onp.asarray(k[:, :written]).any(axis=-1).all()
    q8 = mx.serve.load(net, max_slots=2, buckets="4,32", quantize="int8_kv")
    with pytest.raises(mx.MXNetError, match="int8"):
        q8.cache_rows(0, 4)


def test_the_decode_program_compiled_for_a_v5e_copies_no_cache_leaf(
        one_v5e, monkeypatch):
    """``ServeEngine._decode_fn`` of a two-layer model as wide as GPT-2
    medium, lowered from shapes at the serve cells' cache (96 slots x 1024
    rows x 16 heads x 64, bf16; nothing that size is made here) with the
    cache donated, compiled for a described v5e.  Each of the four leaves
    is a parameter, written by one scatter in place and read by
    ``mx_decode_attn``: no other operation's result is a whole leaf, every
    leaf is aliased to its output, and the program's temporaries stay far
    under one leaf (201 MB; the composition's two copies a leaf held 404
    MB).  The guard against a reshape or a layout bringing the copies
    back."""
    from jax.experimental.compilation_cache import compilation_cache
    slots, max_seq, layers, units = 96, 1024, 2, 1024
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    net = GPTForCausalLM(vocab_size=512, units=units, hidden_size=4 * units,
                         num_layers=layers, num_heads=16, max_length=max_seq,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    eng = mx.serve.ServeEngine(net, max_slots=2, max_seq=16, buckets="8",
                               cache_dtype="bfloat16")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, jnp.bfloat16), eng._params)
    leaf = spec((slots, max_seq, units), jnp.bfloat16)
    state = {k: spec(v.shape if k == "key" else (slots,), v.dtype)
             for k, v in eng._state.items()}
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.default_matmul_precision("default"):
            exe = jax.jit(eng._decode_fn, donate_argnums=(1, 2)).lower(
                params, [(leaf, leaf)] * layers, state).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = exe.as_text()
    assert text.count("mx_decode_attn") >= layers
    whole = re.findall(
        r"= bf16\[96,1024,1024\]\S* ([\w-]+)\(", text[text.index("\nENTRY "):])
    assert sorted(set(whole)) == ["fusion", "parameter"], whole
    assert whole.count("parameter") == whole.count("fusion") == 2 * layers
    # each of those fusions is the step's scatter, on the parameter itself
    assert len(re.findall(r"ROOT %\S+ = bf16\[96,1024,1024\]\S* scatter\(",
                          text)) == 2 * layers
    memory = exe.memory_analysis()
    leaf_bytes = slots * max_seq * units * 2
    assert memory.alias_size_in_bytes >= 2 * layers * leaf_bytes
    assert memory.temp_size_in_bytes < leaf_bytes // 4
