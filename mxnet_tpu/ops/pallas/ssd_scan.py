"""The state-space scan ``ops/ssm.py::ssd_scan`` as Pallas passes (TPU):
a chunk's decays and masked products live in VMEM only, and the state is
carried from chunk to chunk in a VMEM scratch.

``_ssd_chunked`` in XLA writes to HBM, a mixer, a float32 copy of ``x``,
the ``(heads, seq, chunk)`` float32 decays and masked ``C . B``, the
stacked chunk states and entering states, carries the state through a
``lax.scan``, makes all of it again for the gradient and then the
cotangents of each.  Here every pass reads its operands once.

What the kernels move and visit:

* **Channel-major operands**: ``x`` and ``y`` ``(batch, heads x dim,
  seq)``, ``B`` / ``C`` ``(batch, groups x state, seq)``, the step sizes
  and the log-decays ``dt A`` float32 ``(batch, heads, seq)`` — tokens
  along the lanes.  It is the layout XLA gives the mixer's arrays between
  its two projections when left to itself (the step compiled for a v5e:
  ``{1,2,0}`` on ``(batch, seq, channels)``), so the ``swapaxes`` round
  the kernels are layouts and not copies, and what is a number a head and
  token (a step size, a running sum, a decay) is a ``(1, chunk)`` row
  that broadcasts along sublanes.  A head's channels are a block of
  sublanes: no head shares a register with its neighbour, and a sum over
  a head's channels is a sum of registers.
* ``mx_ssd_fwd``.  Grid (batch, group, chunk), the chunks innermost and
  in order.  The state of the group's ``per = heads / groups`` heads,
  ``(per x dim, state)`` float32, is a VMEM scratch zeroed at chunk 0.
  A step makes the running sums ``a`` of ``dt A`` over the chunk (a
  product with a triangle of ones, exact: the float32 operand goes as
  three bfloat16 parts), ``C . B`` once, transposed (keys on sublanes,
  queries on lanes, as the flash kernels hold their tiles); the entering
  state's output ``S @ C`` and the state's update ``S <- exp(a_Q) S +
  (exp(a_Q - a_k) dt_k x_k) @ B.T`` once for all the heads (products
  ``per x dim`` rows tall); and a head at a time the masked decay
  ``exp(where(k <= q, a_q - a_k, -inf))`` (the mask before the
  exponential; ``a_k`` down the sublanes from one transpose a step of
  the ``(per, chunk)`` sums), ``(C . B) * decay`` and the product of
  ``dt x`` with it.  ``y``'s tile is written once.  Where a gradient
  will be taken the state **entering** each chunk is written too,
  float32 ``(batch, chunks, heads x dim, state)``: all the backward pass
  keeps beside the operands.
* ``mx_ssd_bwd``.  The same grid with the chunks **in reverse**; the
  cotangent of the state is the scratch.  The sums, the decays and
  ``C . B`` are made again in VMEM.  A head's two products are ``dM.T =
  (dt x).T @ dy`` and ``d(dt x) = dy @ M``; ``dC . B`` is summed over the
  heads in VMEM and leaves through two products a step, the state's four
  products are made once for all the heads.  Every gradient of a number
  a head and token is a sum over the head's channels, sublane on
  sublane, and leaves as a ``(per, chunk)`` row block: ``d dt`` and — back
  through the running sum by the transposed triangle — ``d (dt A)``; XLA
  takes them to ``dt`` and ``A``, and a row of ``sum dy x`` a head to
  ``D``.  The masked product's share of ``d (dt A)`` does not go through
  the sums: token ``j`` takes ``dM . M`` of the pairs ``k < j <= q``
  directly (one more product a head with a triangle of ones), because as
  ``d a_q - d a_k`` the two sides cancel down to their products' rounding,
  which ``d A`` would sum over the sequence.  ``dx``, ``dB``, ``dC`` leave
  in the operands' types.
* Every accumulation, exponential and the carried state are float32;
  the products' operands take ``x``'s type (the entering state is cast
  to it where it meets ``C``, the cotangents where they meet an operand).
* No reduction over the batch: the calls can sit in a ``shard_map`` over
  ``dp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dsa_scores import _VMEM_MAX, _VMEM_ROOM
from .flash_attention import _NN, _NT, _TN

_F32 = jnp.float32
_LANES = 128


def _resident(per, dim, state, chunk, itemsize):
    """Bytes a backward step holds (it holds more than a forward one):
    the pipeline's two copies of every tile, the scratch, and room for a
    step's float32 temporaries."""
    wide = per * dim * chunk
    tiles = (3 * wide + 4 * state * chunk) * itemsize \
        + per * dim * state * 4 + 5 * max(per, 8) * chunk * 4
    return 2 * tiles + per * dim * state * 4 + 2 * wide * itemsize \
        + 6 * wide * 4 + 10 * chunk * chunk * 4


def fits(seq, heads, dim, groups, state, chunk, itemsize):
    """Can the kernels take these shapes: a sequence of a chunk or more,
    tiles that fill whole registers (tokens and the state along 128
    lanes, a head's channels and a group's heads down whole sublane
    tiles) and a step inside what VMEM gives."""
    per = heads // groups
    return (seq >= chunk and chunk % _LANES == 0 and state % _LANES == 0
            and dim % 16 == 0 and (per % 8 == 0 or groups == 1)
            and _resident(per, dim, state, chunk, itemsize) + _VMEM_ROOM
            <= _VMEM_MAX)


def _dot(a, b, dims):
    """A product on the MXU, summed in float32.  bfloat16 operands go at
    the default precision whatever ``jax.default_matmul_precision`` says
    (Mosaic takes no other for them); float32 operands follow it."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=None if a.dtype == _F32 else jax.lax.Precision.DEFAULT)


def _triangle(chunk, strict=False):
    """(chunk, chunk) bool, row <= column (``strict``: <): the ones a
    running sum along the lanes is a product with, and the pairs a query
    (on the lanes) may read of the keys (on the sublanes)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows < cols if strict else rows <= cols


def _ones_dot(v, ones, dims):
    """``v`` float32 times a matrix of ones and zeros (bool), exactly:
    ``v`` as three bfloat16 parts (8 + 8 + 8 of a float32's 24 bits), each
    product exact, summed in float32."""
    bf = jnp.bfloat16
    rows = v.shape[0]
    high = v.astype(bf)
    rest = v - high.astype(_F32)
    mid = rest.astype(bf)
    low = (rest - mid.astype(_F32)).astype(bf)
    out = _dot(jnp.concatenate([high, mid, low], axis=0), ones.astype(bf),
               dims)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _scalars(dt_ref, la_ref, causal):
    """A chunk's numbers a head and token, ``(per, chunk)`` rows: the
    step sizes, the running sums ``a`` of the log-decays (and ``a``
    transposed, ``(chunk, per)``: a key's down the sublanes), ``exp(a)``
    and ``exp(a_Q - a)``, the decay to the chunk's end."""
    dt = dt_ref[0]
    a = _ones_dot(la_ref[0], causal, _NN)
    chunk = a.shape[1]
    return dt, a, a.T, jnp.exp(a), jnp.exp(a[:, chunk - 1:] - a)


def _whole(grow, width):
    """``exp(a_Q)``, the decay over the whole chunk: a head's along
    ``width`` lanes of its row (the last lane of ``grow`` spread by a
    product with ones: Mosaic broadcasts no single element both ways, and
    that is what a broadcast of the column and a head's row of it
    become)."""
    chunk = grow.shape[1]
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    return _ones_dot(jnp.where(last, grow, 0.0),
                     jnp.ones((chunk, width), bool), _NN)


@jax.jit
def _head_fwd(x, causal, cb_t, from_state, a, a_keys, dt, grow, to_end, d):
    """One head of a forward step, values in and out (jitted: a step
    traces it once, not once a head): its ``(dim, chunk)`` tiles of
    ``x`` and of the entering state's output, its rows of the numbers a
    token (``a_keys`` a column) -> ``y``'s tile and ``exp(a_Q - a_k) dt_k
    x_k``, the state's share."""
    dtype = x.dtype
    xf = x.astype(_F32)
    decay_t = jnp.exp(jnp.where(causal, a - a_keys, -jnp.inf))
    y = _dot((xf * dt).astype(dtype), (cb_t * decay_t).astype(dtype), _NN)
    return ((y + grow * from_state + d * xf).astype(dtype),
            (xf * to_end).astype(dtype))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, d_ref, y_ref, *rest,
                dim, keep):
    """One chunk of one group's heads: ``y``'s tile, the state moved on."""
    state, xw_ref = rest[-2:]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    if keep:
        rest[0][0, 0] = state[...]
    bt, ct = b_ref[0], c_ref[0]
    causal = _triangle(bt.shape[1])
    dt, a, a_keys, grow, decay_end = _scalars(dt_ref, la_ref, causal)
    to_end = dt * decay_end                         # dt_k exp(a_Q - a_k)
    whole = _whole(grow, state.shape[1])
    # keys on sublanes, queries on lanes
    cb_t = _dot(bt, ct, _TN)
    from_state = _dot(state[...].astype(bt.dtype), ct, _NN)
    for h in range(dt.shape[0]):
        rows, row = slice(h * dim, (h + 1) * dim), slice(h, h + 1)
        y_ref[0, rows, :], xw_ref[rows, :] = _head_fwd(
            x_ref[0, rows, :], causal, cb_t, from_state[rows, :], a[row],
            a_keys[:, row], dt[row], grow[row], to_end[row], d_ref[0, row, :])
        state[rows, :] = whole[row] * state[rows, :]
    state[...] += _dot(xw_ref[...], bt, _NT)


@jax.jit
def _head_bwd(x, dy, causal, before, later, cb_t, from_state, d_xw, ds,
              entering, a, a_keys, dt, grow, to_end, decay_end, whole, d):
    """One head of a backward step, values in and out (jitted, as
    ``_head_fwd``): its tiles of ``x``, ``dy``, the entering state
    (``entering`` and its output ``from_state``) and the state's cotangent
    (``ds`` and ``d_xw``, what it hands ``exp(a_Q - a_k) dt_k x_k``), its
    rows of the numbers a token, the triangles ``before`` (k < j) and
    ``later`` (j <= q, a product's operand) -> ``dx``; ``exp(a_q) dy`` and
    ``exp(a_Q - a_k) dt_k x_k``, the state's products' operands; its share
    of ``d (C . B)`` transposed; the rows ``d a`` (of the running sums, all
    but the masked product's share), ``d (dt A)`` (that share), ``d dt``
    and ``sum dy x``; the cotangent ``exp(a_Q) ds``."""
    dtype = x.dtype
    chunk = x.shape[1]

    def over(t):        # a head's channels summed: a row a token
        return jnp.sum(t, axis=0, keepdims=True)

    xf, dyf = x.astype(_F32), dy.astype(_F32)
    decay_t = jnp.exp(jnp.where(causal, a - a_keys, -jnp.inf))
    masked_t = cb_t * decay_t
    xdt = (xf * dt).astype(dtype)
    d_masked_t = _dot(xdt, dy, _TN)
    d_xdt = _dot(dy, masked_t.astype(dtype), _NT)
    dx = d_xdt * dt + d_xw * to_end + d * dyf
    via_end = over(d_xw * xf)                       # d(dt_k exp(a_Q - a_k))
    # a_Q, the chunk's last sum, also decays the entering state and
    # every key's share of the chunk's state
    at_end = jnp.sum(over(ds * entering) * whole, axis=1, keepdims=True) \
        + jnp.sum(via_end * to_end, axis=1, keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    d_a = (over(dyf * from_state) * grow - via_end * to_end
           + jnp.where(last, jnp.broadcast_to(at_end, (1, chunk)), 0.0))
    # the masked product: ``a_q - a_k`` is the log-decays of the tokens
    # k < j <= q, so token j takes ``dM . M`` of the pairs round it, all
    # with one sign.  (As a query's sum less a key's, then back through
    # the running sum, the two sides cancel down to their products'
    # rounding, which ``d A`` then sums over the sequence.)
    after = _dot((d_masked_t * masked_t).astype(dtype), later, _NT)
    d_la = over(jnp.where(before, after, 0.0))
    return (dx.astype(dtype), (grow * dyf).astype(dtype),
            (xf * to_end).astype(dtype), d_masked_t * decay_t, d_a, d_la,
            over(xf * d_xdt) + via_end * decay_end, over(dyf * xf),
            whole * ds)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, d_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dla_ref, dd_ref, dstate,
                xw_ref, dz_ref, *, dim):
    """One chunk of one group's heads, the chunks walked backwards: the
    operands' gradients, the state's cotangent moved on."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bt, ct = b_ref[0], c_ref[0]
    dtype = bt.dtype
    chunk = bt.shape[1]
    causal, before = _triangle(chunk), _triangle(chunk, strict=True)
    later = causal.astype(dtype)
    dt, a, a_keys, grow, decay_end = _scalars(dt_ref, la_ref, causal)
    to_end = dt * decay_end
    whole = _whole(grow, dstate.shape[1])
    per = dt.shape[0]
    entering = st_ref[0, 0]                         # (per x dim, state)
    entering_lo = entering.astype(dtype)
    ds = dstate[...]
    ds_lo = ds.astype(dtype)
    cb_t = _dot(bt, ct, _TN)
    from_state = _dot(entering_lo, ct, _NN)
    d_xw = _dot(ds_lo, bt, _NN)
    head = jax.lax.broadcasted_iota(jnp.int32, (per, 1), 0)
    d_cb_t = jnp.zeros((chunk, chunk), _F32)
    d_a, d_la, d_dt, d_d = (jnp.zeros((per, chunk), _F32),) * 4
    for h in range(per):
        rows, row = slice(h * dim, (h + 1) * dim), slice(h, h + 1)
        (dx_ref[0, rows, :], dz_ref[rows, :], xw_ref[rows, :], d_cb_h, d_a_h,
         d_la_h, d_dt_h, d_d_h, dstate[rows, :]) = _head_bwd(
            x_ref[0, rows, :], dy_ref[0, rows, :], causal, before, later,
            cb_t, from_state[rows, :], d_xw[rows, :], ds[rows, :],
            entering[rows, :], a[row], a_keys[:, row], dt[row], grow[row],
            to_end[row], decay_end[row], whole[row], d_ref[0, row, :])
        d_cb_t = d_cb_t + d_cb_h
        mine = head == h
        d_a = jnp.where(mine, d_a_h, d_a)
        d_la = jnp.where(mine, d_la_h, d_la)
        d_dt = jnp.where(mine, d_dt_h, d_dt)
        d_d = jnp.where(mine, d_d_h, d_d)
    # the state's products, all the heads at once
    dz, xw = dz_ref[...], xw_ref[...]
    dstate[...] += _dot(dz, ct, _NT)
    d_cb_lo = d_cb_t.astype(dtype)
    db_ref[0] = (_dot(ds_lo, xw, _TN)
                 + _dot(ct, d_cb_lo, _NT)).astype(db_ref.dtype)
    dc_ref[0] = (_dot(entering_lo, dz, _TN)
                 + _dot(bt, d_cb_lo, _NN)).astype(dc_ref.dtype)
    ddt_ref[0] = d_dt
    # back through the running sum: a key's log-decay reaches every later a
    dla_ref[0] = _ones_dot(d_a, causal, _NT) + d_la
    dd_ref[0] += d_d


def _specs(chunk, rows, state, per, n, reverse):
    """Block specs of a ``(rows, chunk)`` tile of ``x``, a ``(state,
    chunk)`` tile of ``B`` / ``C``, the ``(per, chunk)`` numbers a head
    and token, ``D``'s rows and a chunk's ``(rows, state)`` state; grid
    (batch, group, chunk), the chunks backwards where ``reverse``."""
    def at(c):
        return n - 1 - c if reverse else c

    return (pl.BlockSpec((1, rows, chunk), lambda i, g, c: (i, g, at(c))),
            pl.BlockSpec((1, state, chunk), lambda i, g, c: (i, g, at(c))),
            pl.BlockSpec((1, per, chunk), lambda i, g, c: (i, g, at(c))),
            pl.BlockSpec((1, per, chunk), lambda i, g, c: (i, g, 0)),
            pl.BlockSpec((1, 1, rows, state),
                         lambda i, g, c: (i, at(c), g, 0)))


def _limit(per, dim, state, chunk, itemsize):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_resident(per, dim, state, chunk, itemsize)
        + _VMEM_ROOM)


def scan_pass(x, b_mat, c_mat, dt, log_decay, d_rows, groups, chunk,
              keep=False, interpret=False):
    """Channel-major: x (b, heads x dim, s), b_mat / c_mat (b, groups x
    state, s) with ``s`` whole chunks; float32 dt and log_decay (b,
    heads, s): the step sizes and ``dt A``; d_rows (b, heads, chunk)
    float32, ``D`` along the lanes -> y like x; with ``keep`` also the
    state entering each chunk, float32 (b, s / chunk, heads x dim,
    state)."""
    batch, width, seq = x.shape
    heads = dt.shape[1]
    per, dim, state = heads // groups, width // heads, b_mat.shape[1] // groups
    rows, n = per * dim, seq // chunk
    x_spec, bc_spec, row_spec, d_spec, st_spec = _specs(
        chunk, rows, state, per, n, False)
    out_specs, out_shape = [x_spec], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep:
        out_specs.append(st_spec)
        out_shape.append(jax.ShapeDtypeStruct((batch, n, width, state), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, dim=dim, keep=keep),
        grid=(batch, groups, n),
        in_specs=[x_spec, bc_spec, bc_spec, row_spec, row_spec, d_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, state), _F32),
                        pltpu.VMEM((rows, chunk), x.dtype)],
        compiler_params=_limit(per, dim, state, chunk, x.dtype.itemsize),
        interpret=interpret,
        name="mx_ssd_fwd",
    )(x, b_mat, c_mat, dt, log_decay, d_rows)
    return tuple(out) if keep else out[0]


def scan_bwd_pass(x, b_mat, c_mat, dt, log_decay, d_rows, entering, dy,
                  groups, chunk, interpret=False):
    """The operands of ``scan_pass``, the entering states it kept and
    ``y``'s cotangent -> the gradients of the six operands, in their
    shapes: ``dx``, ``dB``, ``dC`` in their types, the rest float32
    (``D``'s a batch row, head and lane: (b, heads, chunk))."""
    batch, width, seq = x.shape
    heads = dt.shape[1]
    per, dim, state = heads // groups, width // heads, b_mat.shape[1] // groups
    rows, n = per * dim, seq // chunk
    x_spec, bc_spec, row_spec, d_spec, st_spec = _specs(
        chunk, rows, state, per, n, True)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)  # noqa: E731
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, dim=dim),
        grid=(batch, groups, n),
        in_specs=[x_spec, bc_spec, bc_spec, row_spec, row_spec, d_spec,
                  st_spec, x_spec],
        out_specs=[x_spec, bc_spec, bc_spec, row_spec, row_spec, d_spec],
        out_shape=[like(x), like(b_mat), like(c_mat), like(dt),
                   like(log_decay), like(d_rows)],
        scratch_shapes=[pltpu.VMEM((rows, state), _F32),
                        pltpu.VMEM((rows, chunk), x.dtype),
                        pltpu.VMEM((rows, chunk), x.dtype)],
        compiler_params=_limit(per, dim, state, chunk, x.dtype.itemsize),
        interpret=interpret,
        name="mx_ssd_bwd",
    )(x, b_mat, c_mat, dt, log_decay, d_rows, entering, dy))
