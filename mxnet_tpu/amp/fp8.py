"""fp8 training with per-tensor delayed scaling.

Extends the PR 8 inference-only fp8 path (ops/pallas/quant_matmul.py,
ops/quantization.py) to training: Dense matmuls run e4m3 forward /
e5m2 backward with fp32 master weights and fp32 MXU accumulation, and
every quantization scale is DELAYED — derived from an amax history
carried in the training step's state (next to the AMP LossScaler in
spirit: state that rides the optimizer bundle), not measured in-line.
In-line (just-in-time) scaling would serialize a full-tensor reduction
before every matmul; delayed scaling reads a ready scalar and folds the
amax reduction into the backward pass XLA already runs.

Wiring (docs/PRECISION.md):

- ``ShardedTrainStep(precision="fp8")`` selects the eligible sites
  (2-D ``*.weight`` parameters >= ``amp.fp8_min_elems``), allocates one
  ``{x, w, g}`` amax history per site and threads it through the jitted
  step as donated state.
- Inside the step, :func:`scales_from_state` turns histories into
  scalar scales; the loss closure runs under :func:`scope`, which the
  ``gluon.nn.Dense`` forward consults — matching sites route through
  :func:`dense_fp8` instead of ``npx.fully_connected``.
- Forward amaxes (max |x|, max |w|) are recorded into the scope and
  returned through the loss aux. The GRADIENT amax cannot be observed
  that way — dy only exists inside the backward trace — so
  :func:`fp8_linear`'s custom_vjp returns the measured ``max |dy|`` as
  the "cotangent" of its (otherwise unused) ``g_scale`` input, and the
  step harvests it with ``argnums=(0, 1)``.
- :func:`roll_state` shifts each history one step and inserts the new
  amax; scales for step N+1 come from steps <= N only, so the whole
  update stays one fixed executable (zero post-warmup recompiles).

The forward matmul routes through the Pallas fp8 kernel on fp8-capable
TPUs (v5+, ``fp8_capable``); everywhere else the operands are cast
through the fp8 grid and the dot runs in fp32 — bit-identical value
snapping, so CPU CI exercises the exact training numerics the TPU path
ships (same fallback contract as ``ops.quantization.fp8_dense_fused``).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from .. import config as _config
from ..ops.pallas.quant_matmul import FP8_FORMATS, fp8_capable

__all__ = ["FWD_FORMAT", "BWD_FORMAT", "fp8_linear", "dense_fp8",
           "select_sites", "init_state", "scales_from_state", "roll_state",
           "merge_amax", "scope", "current", "record"]

#: training formats per the standard recipe: e4m3 (more mantissa) for
#: activations/weights in the forward, e5m2 (more range) for gradients
FWD_FORMAT = "e4m3"
BWD_FORMAT = "e5m2"

_tls = threading.local()


class _Scope:
    """Per-trace fp8 context: site -> (x_scale, w_scale, g_scale) traced
    scalars, plus the forward-amax collector the loss aux returns."""

    __slots__ = ("scales", "amax")

    def __init__(self, scales):
        self.scales = scales
        self.amax = {}


class scope:
    """Context manager installing a :class:`_Scope` for the enclosed
    (traced) forward; ``Dense.forward`` reads it via :func:`current`."""

    def __init__(self, scales):
        self._scope = _Scope(scales)

    def __enter__(self):
        prev = getattr(_tls, "ctx", None)
        self._prev = prev
        _tls.ctx = self._scope
        return self._scope

    def __exit__(self, *exc):
        _tls.ctx = self._prev
        return False


def current():
    """The active fp8 scope, or None — the one-attr-read gate the Dense
    fast path checks."""
    return getattr(_tls, "ctx", None)


def record(site, x_amax, w_amax):
    """A site's forward amaxes into the active scope; a site that runs
    more than once in a trace (layers looped on shared weights) keeps
    the largest over its uses."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        seen = ctx.amax.get(site)
        if seen is not None:
            x_amax = jnp.maximum(seen[0], x_amax)
        ctx.amax[site] = (x_amax, w_amax)


# -- quantize / dequantize ---------------------------------------------------

def _qcast(v, scale, fmt):
    """Saturating cast through the fp8 grid: scale maps the delayed amax
    onto the format's absmax, clip guards inter-step amax growth."""
    dt, fmax = FP8_FORMATS[fmt]
    return jnp.clip(v.astype(jnp.float32) * scale, -fmax, fmax).astype(dt)


def _dot(a, b, dims):
    """fp8 x fp8 dot with fp32 accumulation.  On fp8-capable devices the
    operands stay fp8 (the compiler picks the MXU mode); elsewhere they
    upcast first — numerically identical (the information loss happened
    at the cast), and it keeps CPU CI on dtypes XLA:CPU always lowers."""
    if not fp8_capable():
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# -- the fp8 linear primitive ------------------------------------------------

@jax.custom_vjp
def fp8_linear(x, w, b, x_scale, w_scale, g_scale):
    """``x @ w.T + b`` through the fp8 grid with delayed scales.

    x: (..., K); w: (N, K) fp32 master; b: (N,) or None; scales: fp32
    scalars (fmt_absmax / delayed_amax).  ``g_scale`` does not affect
    the value — it is consumed by the backward rule (e5m2 gradient
    quantization), and its custom_vjp cotangent carries the measured
    ``max |dy|`` back to the caller (the delayed-scaling history roll).
    """
    y, _ = _fp8_linear_fwd(x, w, b, x_scale, w_scale, g_scale)
    return y


def _fwd_value(x, w, b, x_scale, w_scale):
    qx = _qcast(x, x_scale, FWD_FORMAT)
    qw = _qcast(w, w_scale, FWD_FORMAT)
    if fp8_capable():
        # Pallas fused kernel (PR 8): per-row scale vector is the
        # broadcast per-tensor scale; kernel dequant is acc*(xs*ws)
        # with the DIVIDE convention, so pass the reciprocals
        from ..ops.pallas.quant_matmul import fp8_matmul
        lead = x.shape[:-1]
        h2 = qx.reshape(-1, x.shape[-1]).astype(jnp.float32) / x_scale
        inv_ws = jnp.full((w.shape[0],), 1.0, jnp.float32) / w_scale
        out = fp8_matmul(h2, qw, inv_ws, 1.0 / x_scale, bias=None,
                         fmt=FWD_FORMAT)
        y = out.reshape(lead + (w.shape[0],))
    else:
        y = _dot(qx, qw, ((x.ndim - 1,), (1,))) / (x_scale * w_scale)
    if b is not None:
        y = y + b
    return y, (qx, qw)


def _fp8_linear_fwd(x, w, b, x_scale, w_scale, g_scale):
    y, (qx, qw) = _fwd_value(x, w, b, x_scale, w_scale)
    # b rides the residuals only for its None-ness: the cotangent
    # structure must mirror the input (None stays None through pytrees)
    return y, (qx, qw, x_scale, w_scale, g_scale, b)


def _fp8_linear_bwd(res, dy):
    qx, qw, x_scale, w_scale, g_scale, b = res
    has_b = b is not None
    g_amax = jnp.max(jnp.abs(dy)).astype(jnp.float32)
    qdy = _qcast(dy, g_scale, BWD_FORMAT)
    # dx = dy @ w: contract dy's N with qw's leading N
    dx = _dot(qdy, qw, ((dy.ndim - 1,), (0,))) / (g_scale * w_scale)
    # dw = dy^T @ x over the flattened lead dims
    m = 1
    for s in dy.shape[:-1]:
        m *= s
    qdy2 = qdy.reshape(m, dy.shape[-1])
    qx2 = qx.reshape(m, qx.shape[-1])
    dw = _dot(qdy2, qx2, ((0,), (0,))) / (g_scale * x_scale)
    db = jnp.sum(dy.astype(jnp.float32),
                 axis=tuple(range(dy.ndim - 1))) if has_b else None
    # zero cotangents for the forward scales; g_scale's slot carries the
    # measured gradient amax out of the backward trace
    zero = jnp.zeros((), jnp.float32)
    return (dx, dw, db, zero, zero, g_amax)


fp8_linear.defvjp(_fp8_linear_fwd, _fp8_linear_bwd)


def dense_fp8(x, w, b, site, flatten=False):
    """The Dense-forward entry: record forward amaxes into the active
    scope and run :func:`fp8_linear` with the site's delayed scales.
    Raw jax arrays in and out (the caller wraps)."""
    ctx = current()
    xs, ws, gs = ctx.scales[site]
    h = x.reshape(x.shape[0], -1) if flatten and x.ndim > 2 else x
    h = h.astype(jnp.float32)       # the grid's arithmetic is fp32
    record(site, jnp.max(jnp.abs(h)).astype(jnp.float32),
           jnp.max(jnp.abs(w)).astype(jnp.float32))
    return fp8_linear(h, w, b, xs, ws, gs)


# -- delayed-scaling state ---------------------------------------------------

def select_sites(shapes):
    """Site names eligible for fp8: 2-D ``*.weight`` parameters of at
    least ``amp.fp8_min_elems`` elements, sorted for a deterministic
    state layout.  Name-based so the state is constructible without a
    discovery trace (``Parameter._structure_name`` is the key Dense
    uses at dispatch)."""
    floor = int(_config.get("amp.fp8_min_elems"))
    out = []
    for name, shape in shapes.items():
        if not name.endswith(".weight") and name != "weight":
            continue
        if len(shape) != 2:
            continue
        if int(shape[0]) * int(shape[1]) < floor:
            continue
        out.append(name)
    return sorted(out)


def init_state(sites, history=None):
    """Fresh amax histories: {site: {"x"|"w"|"g": zeros(H,)}}.  All-zero
    means "no observation yet"; :func:`scales_from_state` maps that to
    scale 1.0 (the first step quantizes un-scaled, then the history
    takes over)."""
    if history is None:
        history = int(_config.get("amp.fp8_history"))
    h = max(1, int(history))
    return {site: {k: jnp.zeros((h,), jnp.float32) for k in ("x", "w", "g")}
            for site in sites}


def _scale(hist, fmax, margin):
    amax = jnp.max(hist) * margin
    return jnp.where(amax > 0.0, fmax / jnp.maximum(amax, 1e-30),
                     jnp.float32(1.0)).astype(jnp.float32)


def scales_from_state(state, margin=None):
    """{site: (x_scale, w_scale, g_scale)} from the carried histories —
    scale = fmt_absmax / (margin * max(history))."""
    if margin is None:
        margin = float(_config.get("amp.fp8_margin"))
    _, fwd_max = FP8_FORMATS[FWD_FORMAT]
    _, bwd_max = FP8_FORMATS[BWD_FORMAT]
    return {site: (_scale(h["x"], fwd_max, margin),
                   _scale(h["w"], fwd_max, margin),
                   _scale(h["g"], bwd_max, margin))
            for site, h in state.items()}


def roll_state(state, fwd_amax, g_amax):
    """Shift every history one step and insert the step's measured amax
    at slot 0.  Sites the forward never reached this step (conditional
    branches) keep their history unchanged."""
    new = {}
    for site, h in state.items():
        upd = dict(h)
        if site in fwd_amax:
            xa, wa = fwd_amax[site]
            upd["x"] = jnp.concatenate([xa[None], h["x"][:-1]])
            upd["w"] = jnp.concatenate([wa[None], h["w"][:-1]])
        if site in g_amax:
            upd["g"] = jnp.concatenate([g_amax[site][None], h["g"][:-1]])
        new[site] = upd
    return new


def merge_amax(a, b):
    """Elementwise max-merge of two amax observations (grad_accum
    microbatches roll the history ONCE with the max over the scan)."""
    out = dict(a)
    for k, v in b.items():
        if k in out:
            out[k] = jax.tree_util.tree_map(jnp.maximum, out[k], v)
        else:
            out[k] = v
    return out
