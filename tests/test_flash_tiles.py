"""The flash attention kernels move and visit only what a step needs
(ops/pallas/flash_attention.py): parity with the XLA composition at the
chip's blocks and over the tuner's block grid, the causal tile schedule
against a brute-force count over the mask, the ``kernel.flash_tiles_total``
counter, and the layouts — no statistic with a minor dimension of 1, no
fp32 gradient leaving a kernel.  Interpret mode on the CPU; the last test
compiles for a described v5e and skips where none can be described.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
from family_harness import one_v5e, pallas_calls as _pallas_calls  # noqa: F401
from mxnet_tpu import telemetry
from mxnet_tpu.autotune import kernels as K
from mxnet_tpu.ops.attention import _reference_attention
from mxnet_tpu.ops.pallas import flash_attention as F

V5E = K._STATIC_DEFAULTS["v5e"]
KERNEL_NAMES = ("mx_flash_fwd", "mx_flash_bwd_dkv", "mx_flash_bwd_dq")


def _qkv(sq, sk, head, dtype, heads=2, seed=0):
    rs = onp.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(1, heads, s, head), dtype)
                 for s in (sq, sk, sk)) + (
        jnp.asarray(rs.randn(1, heads, sq, head), jnp.float32),)


def _reference(q, k, v, causal):
    """``_reference_attention`` on (batch, heads, seq, dim) operands, in
    fp32 whatever the operands' dtype: it is the truth both precisions
    are held to."""
    b, h, sq, d = q.shape

    def merge(t):
        return t.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            b, t.shape[2], h * d)
    out = _reference_attention(merge(q), merge(k), merge(v), h,
                               causal=causal)
    return out.reshape(b, sq, h, d).transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def _oracle(sq, sk, head, dtype, causal):
    """The reference's output and gradients, once a shape for the block
    pairs compared with it."""
    q, k, v, w = _qkv(sq, sk, head, dtype)
    return H.out_and_vjp(lambda *a: _reference(*a, causal), w, q, k, v)


def _check(sq, sk, head, dtype, causal, fwd, bwd):
    """Output and dq, dk, dv of the kernels against the reference's."""
    q, k, v, w = _qkv(sq, sk, head, dtype)

    def flash(q, k, v):
        return F.flash_attention(
            q, k, v, causal=causal, interpret=True,
            block_q=fwd["block_q"], block_k=fwd["block_k"],
            bwd_block_q=bwd["block_q"], bwd_block_k=bwd["block_k"])

    out, grads = H.out_and_vjp(flash, w, q, k, v)
    ref, ref_grads = _oracle(sq, sk, head, str(jnp.dtype(dtype)), causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == dtype
    onp.testing.assert_allclose(onp.asarray(out, "float32"),
                                onp.asarray(ref), atol=tol, rtol=tol)
    for g, r, x in zip(grads, ref_grads, (q, k, v)):
        assert g.dtype == x.dtype and g.shape == x.shape
        r = onp.asarray(r, "float32")
        onp.testing.assert_allclose(onp.asarray(g, "float32"), r,
                                    atol=tol * max(1.0, onp.abs(r).max()),
                                    rtol=tol)


# -- parity -----------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("head", [64, 128])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_parity_at_the_v5e_blocks(causal, head, dtype):
    _check(1024, 1024, head, dtype, causal, V5E["flash_attention"],
           V5E["flash_attention_bwd"])


def _space_pairs():
    bucket = K.shape_bucket("flash_attention", (1024, 1024, 64))
    for kern in ("flash_attention", "flash_attention_bwd"):
        for blocks in K.kernel_candidates(kern, bucket):
            yield pytest.param(
                kern, blocks,
                id=f"{kern[6:]}-{blocks['block_q']}x{blocks['block_k']}")


@pytest.mark.parametrize("kernel,blocks", _space_pairs())
def test_parity_at_every_block_pair_of_the_space(kernel, blocks):
    """Every candidate the tuner may pick computes the same function at
    the cells' shape (the other pass keeps the v5e's blocks)."""
    fwd, bwd = V5E["flash_attention"], V5E["flash_attention_bwd"]
    if kernel == "flash_attention":
        fwd = blocks
    else:
        bwd = blocks
    _check(1024, 1024, 64, jnp.float32, True, fwd, bwd)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("sq,sk", [(200, 200), (300, 520)])
def test_parity_ragged(sq, sk, causal, dtype):
    """Lengths that are no block multiple, and sq != sk: the padded end
    of K is masked, the padded queries are sliced off."""
    blocks = {"block_q": 128, "block_k": 128}
    _check(sq, sk, 64, dtype, causal, blocks, blocks)


# -- the tile schedule ------------------------------------------------------

def _brute_force(seq_q, seq_k, bq, bk, causal):
    """Kinds of tile from the mask itself, over the padded grid: a tile
    with no attended pair is skipped, one with all pairs attended is
    computed plain, the rest need the mask."""
    bq, bk = min(bq, seq_q), min(bk, seq_k)
    nq, nk = -(-seq_q // bq), -(-seq_k // bk)
    i = onp.arange(nq * bq)[:, None]
    j = onp.arange(nk * bk)[None, :]
    valid = (j < seq_k) & ((i >= j) if causal else True)
    valid = onp.broadcast_to(valid, (nq * bq, nk * bk))
    tiles = valid.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    full = tiles.all(axis=(2, 3))
    some = tiles.any(axis=(2, 3))
    return {"computed": int(full.sum()), "masked": int((some & ~full).sum()),
            "skipped": int((~some).sum())}, some


BLOCKS = [(128, 128), (128, 256), (256, 128), (256, 256), (256, 512),
          (512, 256), (512, 512), (1024, 512), (64, 192)]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("seq_q,seq_k", [
    (1024, 1024), (2048, 1024), (1024, 2048), (200, 200), (300, 520),
    (520, 300), (1000, 1000), (64, 64)])
def test_tile_schedule_equals_a_brute_force_count(seq_q, seq_k, causal):
    for bq, bk in BLOCKS:
        want, some = _brute_force(seq_q, seq_k, bq, bk, causal)
        assert F.tile_counts(seq_q, seq_k, bq, bk, causal) == want, (bq, bk)
        # the forward visits exactly the k-blocks that hold an attended
        # key: ceil((qi+1)*block_q/block_k) of them under the diagonal
        cbq, cbk = min(bq, seq_q), min(bk, seq_k)
        nk = -(-seq_k // cbk)
        visited = 0
        for qi in range(some.shape[0]):
            n_visit = F._fwd_visits(qi, nk, cbq, cbk, causal)
            assert n_visit == int(some[qi].sum())
            if causal:
                assert n_visit == min(nk, -(-(qi + 1) * cbq // cbk))
            visited += n_visit
        assert visited == want["computed"] + want["masked"], (bq, bk)


def test_tile_counts_at_the_cells_shape():
    """Seq 1024 at 256/256: 16 tiles a head, 6 above the diagonal, 4 on
    it; at the v5e's 512/512: 4 tiles, 1 above, 2 on."""
    assert F.tile_counts(1024, 1024, 256, 256, True) == {
        "computed": 6, "masked": 4, "skipped": 6}
    assert F.tile_counts(1024, 1024, 512, 512, True) == {
        "computed": 1, "masked": 2, "skipped": 1}
    assert F.tile_counts(1024, 1024, 512, 512, False) == {
        "computed": 4, "masked": 0, "skipped": 0}


def _grad_jaxpr(blocks, causal=True, shape=(8, 16, 1024, 64)):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return F.flash_attention(
            q, k, v, causal=causal, interpret=True, block_q=blocks[0],
            block_k=blocks[1], bwd_block_q=blocks[0],
            bwd_block_k=blocks[1]).astype(jnp.float32).sum()
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)


@pytest.mark.parametrize("bq,bk", [(256, 256), (512, 256), (256, 512),
                                   (512, 512)])
def test_skipped_tiles_are_not_fetched(bq, bk):
    """A step above the diagonal names the block the nearest step with
    work names, so the pipeline issues no copy for it: along the grid,
    the streamed operands' block index changes no more often than there
    are tiles with work (and a head's first step fetches once)."""
    calls = {e.params["name"]: e for e in _pallas_calls(_grad_jaxpr((bq, bk)).jaxpr)}
    _, some = _brute_force(1024, 1024, bq, bk, True)
    nq, nk = some.shape
    # (grid order over (q-block a, k-block b), operands streamed)
    cases = {"mx_flash_bwd_dkv": ([(a, b) for b in range(nk)
                                   for a in range(nq)], (0, 1, 2, 3)),
             "mx_flash_bwd_dq": ([(a, b) for a in range(nq)
                                  for b in range(nk)], (4, 5))}
    for name, (order, streamed) in cases.items():
        gm = calls[name].params["grid_mapping"]
        assert gm.grid[1:] == ((nk, nq) if name.endswith("dkv")
                               else (nq, nk))
        for idx in streamed:
            im = gm.block_mappings[idx].index_map_jaxpr
            seen, fetches = None, 0
            for a, b in order:
                step = (0, b, a) if name.endswith("dkv") else (0, a, b)
                at = tuple(int(x) for x in jax.core.eval_jaxpr(
                    im.jaxpr, im.consts, *map(jnp.int32, step)))
                fetches += at != seen
                seen = at
                if some[a, b]:      # a tile with work sees its own blocks
                    want = a if idx < 4 else b
                    assert want in at[1:], (name, idx, step, at)
            assert fetches <= int(some.sum()), (name, idx)


# -- the counter ------------------------------------------------------------

def test_flash_tiles_counter_counts_every_traced_call():
    """Tiles are counted where they are decided, when a call is traced:
    once a call, by tiles x ``bh``, whatever was traced before."""
    bh = 8 * 16
    want = {"computed": 6 * bh, "masked": 4 * bh, "skipped": 6 * bh}
    _grad_jaxpr((256, 256))         # telemetry off: nothing is counted
    for calls in (1, 2):
        telemetry.reset()
        telemetry.enable()
        try:
            for _ in range(calls):
                _grad_jaxpr((256, 256))
            got = telemetry.counters("kernel.flash_tiles_total")
        finally:
            telemetry.disable()
            telemetry.reset()
        assert got == {
            f'kernel.flash_tiles_total{{kernel="{kern}",kind="{kind}"}}':
            n * calls
            for kern in ("fwd", "bwd_dkv", "bwd_dq")
            for kind, n in want.items()}
    assert telemetry.CATALOG["kernel.flash_tiles_total"][0] == "counter"


def test_every_layer_lowers_its_own_three_kernels():
    """The bring-up smoke and the compile rehearsal count the Mosaic
    calls of a step in its lowered text, three a layer: a stack of
    layers holds that many ``pallas_call``s, each with one tile body
    (the dK/dV kernel three branches: init, tile, store)."""
    x = jax.ShapeDtypeStruct((2, 4, 1024, 64), jnp.bfloat16)

    def loss(q, k, v):
        for _ in range(3):
            q = F.flash_attention(q, k, v, causal=True, interpret=True)
        return q.astype(jnp.float32).sum()
    calls = _pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr)
    assert len(calls) == 9
    for eqn in calls:
        conds = [e for e in eqn.params["jaxpr"].eqns
                 if e.primitive.name == "cond"]
        assert len(conds) == (0 if eqn.params["name"] == KERNEL_NAMES[0]
                              else 3)


# -- layouts ----------------------------------------------------------------

def test_no_minor_dimension_of_one_and_gradients_in_the_operands_dtype():
    """On the jaxpr (no TPU library): the three kernels keep their names,
    none has an operand or result whose last dimension is 1, and dq, dk,
    dv leave their kernels as bf16."""
    blocks = (V5E["flash_attention_bwd"]["block_q"],
              V5E["flash_attention_bwd"]["block_k"])
    closed = _grad_jaxpr(blocks)
    calls = {e.params["name"]: e for e in _pallas_calls(closed.jaxpr)}
    assert sorted(calls) == sorted(KERNEL_NAMES)
    for name, eqn in calls.items():
        for var in list(eqn.invars) + list(eqn.outvars):
            assert var.aval.shape[-1] != 1, (name, var.aval)
    for name in KERNEL_NAMES[1:]:
        for var in calls[name].outvars:
            assert var.aval.dtype == jnp.bfloat16, (name, var.aval)
            assert var.aval.shape == (128, 1024, 128)
    assert all(v.aval.dtype == jnp.bfloat16 for v in closed.jaxpr.outvars)


def test_compiled_for_a_v5e_no_sparse_statistic_and_no_fp32_gradient(
        one_v5e):
    """The same function compiled for a described chip: Mosaic takes the
    kernels, and the HLO holds neither an ``f32[..., 1]`` buffer (a
    statistic on one lane in 128) nor an ``f32[..., 128]`` gradient."""
    from jax.experimental.compilation_cache import compilation_cache
    x = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16,
                             sharding=one_v5e)
    fwd, bwd = V5E["flash_attention"], V5E["flash_attention_bwd"]

    def loss(q, k, v):
        return F.flash_attention(
            q, k, v, causal=True, block_q=fwd["block_q"],
            block_k=fwd["block_k"], bwd_block_q=bwd["block_q"],
            bwd_block_k=bwd["block_k"]).astype(jnp.float32).sum()

    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip; conftest's fp32 matmul
    # precision is for the numpy oracles, the chip runs the default
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.default_matmul_precision("default"):
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in KERNEL_NAMES:
        assert name in text
    # buffers are the entry computation's values; what a fusion computes
    # inside itself (delta's fp32 product) never reaches memory
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r"f32\[[\d,]*,1\]", entry)
    assert not re.search(r"f32\[128,1024,128\]", entry)
    assert "f32[128,1,1024]" in entry


def test_the_alignment_kernel_compiles_for_a_v5e_at_the_cells_size(one_v5e):
    """``mx_dsa_align`` (ops/pallas/dsa_align.py) at ``keye-train-8k``'s
    shapes — 32 heads over 4 KV heads x 128, 8192 tokens, bf16 — is taken
    by Mosaic as written, and its program holds the ``(seq, seq)`` arrays
    it is given and returns and no ``heads x seq x seq`` one."""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops.pallas import dsa_align
    b, h, hk, s, d = 1, 32, 4, 8192, 128

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.default_matmul_precision("default"):
            text = jax.jit(lambda *a: dsa_align.align_pass(*a, b * s)).lower(
                spec((b, s, s), jnp.float32), spec((b, s, s), jnp.int8),
                spec((b, h, s, d), jnp.bfloat16),
                spec((b, hk, s, d), jnp.bfloat16),
                spec((b, h, s), jnp.float32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mx_dsa_align" in text
    sizes = [onp.prod([int(n) for n in dims.split(",")]) for dims in
             re.findall(r"\b[a-z]+[0-9]+\[([0-9,]+)\]", text)]
    assert max(sizes) == b * s * s


def test_the_top_k_kernel_compiles_for_a_v5e_at_the_cells_size(one_v5e):
    """``mx_dsa_select`` (ops/pallas/dsa_select.py) at ``keye-train-8k``'s
    shape — 8192 tokens, float32 scores, ``topk`` 2048 — is taken by Mosaic
    as written (a 16 MB panel twice, the keys' scratch, signed compares,
    a dynamic trip count, conditions with vector results, int8 stores),
    and its program holds the scores it is given and the int8 mask."""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops.pallas import dsa_select
    b, s, topk = 1, 8192, 2048
    assert dsa_select.panel_block(s) == 512
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(lambda i: dsa_select.select_pass(i, topk)).lower(
            jax.ShapeDtypeStruct((b, s, s), jnp.float32,
                                 sharding=one_v5e)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mx_dsa_select" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == b * s * s * 4
    assert memory.temp_size_in_bytes == 0
    assert memory.output_size_in_bytes == b * s * s
