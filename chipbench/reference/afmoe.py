"""The afmoe family (Trinity) in plain ``jax.numpy``: forward, loss,
gradients and MXNet's Adam, for one chip's share of the experts.

The reference the benchmark's ``correct`` is decided against.  float32
everywhere, ``jax.default_matmul_precision("highest")``, no kernel, no
grouped product, no bound on rows: one sequence at a time, every held
expert applied to every token and weighted by the router (weight 0 where
the token did not select it).  It imports nothing of the program and
takes nothing the program made; its parameters come from
``chipbench/families/afmoe.py`` (the benchmark's own generator).

The equations, per layer with ``x`` the residual stream (the family's
public modelling code; each item the released ``config.json`` does not
fix is listed in the configuration file under ``assumed``):

* ``h = x + RMS_post_attn(Attn(RMS_in(x)))``,
  ``x' = h + RMS_post_mlp(FF(RMS_pre_mlp(h)))``; RMSNorm with a learned
  scale, eps from the file.
* ``Attn(u)``: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``, ``g = u Wg``, no
  biases; RMSNorm over the head dimension on q and k (one scale vector
  each, shared by the heads); on ``sliding_attention`` layers only,
  rotary embedding (rotate-half, whole head) on q and k and a causal
  window ``0 <= i - j < sliding_window``; ``full_attention`` layers carry
  no position and are causal; query head ``i`` reads KV head
  ``i // (heads / kv_heads)``; output ``(o * sigmoid(g)) Wo``.
* ``FF`` on the leading dense layers: ``(silu(u W1) * (u W3)) W2``.  On
  expert layers: ``s = sigmoid(u Wr)``, selection = top-k of
  ``s + expert_bias``, ``w_e = route_scale * s_e / (sum of the selected
  s + 1e-20)``, result ``Shared(u) + sum over selected HELD e of
  w_e Expert_e(u)``: what the absent experts would add is left out, as
  in the program.
* Embedding rows times sqrt(hidden), final RMSNorm, untied head, mean
  token cross-entropy.

Weights of a linear layer are (out, in): ``y = x @ W.T``; the stacked
expert matrices are (expert, in, out): ``y = x @ W[e]``.

Memory at the cell's size (504 M parameters: 8 GB for parameters,
gradient and Adam's two moments): every layer is recomputed in the
backward pass (``jax.checkpoint``) and attention runs in query blocks,
each recomputed too, so an 8192-token sequence adds about 2 GB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: stacked over all layers / the dense layers / the expert layers
LAYER_LEAVES = (
    "ln_in.g", "ln_post_attn.g", "ln_pre_mlp.g", "ln_post_mlp.g",
    "attn.q.w", "attn.k.w", "attn.v.w", "attn.g.w", "attn.o.w",
    "attn.q_norm.g", "attn.k_norm.g")
DENSE_LEAVES = ("mlp.gate.w", "mlp.up.w", "mlp.down.w")
MOE_LEAVES = (
    "moe.router.w", "moe.shared.gate.w", "moe.shared.up.w",
    "moe.shared.down.w", "moe.gate.w", "moe.up.w", "moe.down.w")
#: per expert layer, not trained
BIAS = "moe.bias"
#: the counts that ride beside the change norms: assignments per
#: published expert over the followed updates, and assignments dropped
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"
STACKED = LAYER_LEAVES + DENSE_LEAVES + MOE_LEAVES

#: queries a block of the attention (scores are heads x block x seq)
ATTN_BLOCK = 256


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x (s, heads, d): rotate-half convention, positions 0..s-1."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(u, gate, up, down):
    """(out, in) matrices."""
    return (jax.nn.silu(u @ gate.T) * (u @ up.T)) @ down.T


def _attention_core(q, k, v, window):
    """q (s, kv, group, d), k/v (s, kv, d) -> (s, kv*group*d).  Query
    blocks against every key, masked; each block recomputed in the
    backward pass."""
    s, kv, group, d = q.shape
    block = math.gcd(s, ATTN_BLOCK)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(qb, q0):
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        rows = q0 + jnp.arange(block)[:, None]
        mask = keys <= rows
        if window is not None:
            mask &= rows - keys < window
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", att, v)

    out = jax.lax.map(lambda a: one(*a), (
        q.reshape(s // block, block, kv, group, d),
        jnp.arange(s // block) * block))
    return out.reshape(s, kv * group * d)


def _attention(u, p, cfg, sliding):
    s = u.shape[0]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _rms((u @ p["attn.q.w"].T).reshape(s, heads, d),
             p["attn.q_norm.g"], eps)
    k = _rms((u @ p["attn.k.w"].T).reshape(s, kv, d), p["attn.k_norm.g"],
             eps)
    v = (u @ p["attn.v.w"].T).reshape(s, kv, d)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = _attention_core(q.reshape(s, kv, heads // kv, d), k, v,
                        cfg["sliding_window"] if sliding else None)
    return (o * jax.nn.sigmoid(u @ p["attn.g.w"].T)) @ p["attn.o.w"].T


def route(u, router, bias, cfg):
    """(selected experts (s, k), their weights (s, k), assignments per
    published expert (n,)) of one sequence."""
    scores = jax.nn.sigmoid(u @ router.T)
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = cfg["route_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(idx[..., None] == jnp.arange(cfg["num_experts"]),
                   axis=(0, 1))
    return idx, w, load


def _experts(u, p, bias, cfg):
    """Shared(u) + the held experts' part, every held expert on every
    token, weighted by the router; and the layer's assignment counts."""
    idx, w, load = route(u, p["moe.router.w"], bias, cfg)
    lo = cfg["experts_held_from"]

    def one(acc, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
        y = (jax.nn.silu(u @ gate) * (u @ up)) @ down
        return acc + w_e[:, None] * y, None

    shared = _swiglu(u, p["moe.shared.gate.w"], p["moe.shared.up.w"],
                     p["moe.shared.down.w"])
    out, _ = jax.lax.scan(one, shared, (
        jnp.arange(cfg["num_experts_held"]), p["moe.gate.w"],
        p["moe.up.w"], p["moe.down.w"]))
    return out, load


def _layer(x, p, bias, cfg, sliding, dense):
    eps = cfg["rms_norm_eps"]
    h = x + _rms(_attention(_rms(x, p["ln_in.g"], eps), p, cfg, sliding),
                 p["ln_post_attn.g"], eps)
    u = _rms(h, p["ln_pre_mlp.g"], eps)
    if dense:
        ff, load = _swiglu(u, p["mlp.gate.w"], p["mlp.up.w"],
                           p["mlp.down.w"]), None
    else:
        ff, load = _experts(u, p, bias, cfg)
    return h + _rms(ff, p["ln_post_mlp.g"], eps), load


def layer_params(params, i, cfg):
    """Layer ``i``'s own leaves out of the stacks."""
    n_dense = cfg["num_dense_layers"]
    p = {n: params[n][i] for n in LAYER_LEAVES}
    if i < n_dense:
        p.update({n: params[n][i] for n in DENSE_LEAVES})
    else:
        p.update({n: params[n][i - n_dense] for n in MOE_LEAVES})
    return p


def forward(params, bias, tokens, cfg):
    """tokens (s,) int32 -> (logits (s, vocab) float32, assignments per
    expert layer and published expert (layers - dense, n)), one
    sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens] * math.sqrt(cfg["hidden_size"])
        loads = []
        for i, kind in enumerate(cfg["layer_types"]):
            dense = i < cfg["num_dense_layers"]
            layer = jax.checkpoint(functools.partial(
                _layer, cfg=cfg, sliding=kind == "sliding_attention",
                dense=dense))
            x, load = layer(x, layer_params(params, i, cfg),
                            None if dense
                            else bias[i - cfg["num_dense_layers"]])
            if load is not None:
                loads.append(load)
        x = _rms(x, params["ln_f.g"], cfg["rms_norm_eps"])
        return x @ params["head.w"].T, jnp.stack(loads)


def sequence_loss_sum(params, bias, tokens, labels, cfg):
    """(sum over one sequence's positions of -log softmax(logits)[label],
    the sequence's assignment counts)."""
    logits, loads = forward(params, bias, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1)), \
        loads


def adam_update(params, grads, m, v, t, opt):
    """MXNet's Adam (``optimizer/adam.py``): bias correction folded into
    the rate, epsilon added to the uncorrected sqrt(v), no weight decay."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for n in params:
        g = grads[n]
        new_m[n] = b1 * m[n] + (1.0 - b1) * g
        new_v[n] = b2 * v[n] + (1.0 - b2) * g * g
        new_p[n] = params[n] - lr_t * new_m[n] / (jnp.sqrt(new_v[n]) + eps)
    return new_p, new_m, new_v


def leaf_norms(tree):
    """{name: L2 norms, one per parameter of the program}: (layers,) for
    a stacked leaf (an expert layer's eight matrices of a kind are one
    parameter there), (1,) for the rest."""
    out = {}
    for n, a in tree.items():
        a = a.astype(jnp.float32)
        if n in STACKED:
            out[n] = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        else:
            out[n] = jnp.sqrt(jnp.sum(a * a)).reshape(1)
    return out


def train_reference(make_params, batches, cfg, opt, devices=None):
    """Follow the program's first ``len(batches)`` updates on the first
    of ``devices``.

    ``make_params()`` makes the starting parameters and the selection
    bias (it is called again at the end rather than a copy kept);
    ``batches`` is a list of (tokens (B, S), labels (B, S)) int32 host
    arrays.  Each update takes its batch one sequence at a time (a scan)
    and sums the gradients; the loss is the mean over all B*S positions.
    Returns the losses, the per-leaf norms of the first gradient, and the
    per-leaf norms of the parameters' change after the last update with,
    beside them, the assignments per expert layer and published expert
    over all the updates (``moe.load``) and the assignments left out
    (``moe.rows_over``: none, there is no bound here).
    """
    first = list(devices or jax.devices()[:1])[0]

    def split(tree):
        tree = dict(tree)
        return tree, tree.pop(BIAS)

    def batch_loss(p, bias, xs, ys):
        one = jax.checkpoint(
            lambda x, y: sequence_loss_sum(p, bias, x, y, cfg))

        def body(carry, xy):
            loss, loads = one(*xy)
            return (carry[0] + loss, carry[1] + loads), None

        n_moe = len(cfg["layer_types"]) - cfg["num_dense_layers"]
        zero = (jnp.zeros((), jnp.float32),
                jnp.zeros((n_moe, cfg["num_experts"]), jnp.int32))
        return jax.lax.scan(body, zero, (xs, ys))[0]

    grad_fn = jax.jit(jax.value_and_grad(batch_loss, has_aux=True))
    step = jax.jit(lambda p, g, m, v, t, scale: adam_update(
        p, jax.tree_util.tree_map(lambda a: a * scale, g), m, v, t, opt),
        static_argnums=4, donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda g, scale: leaf_norms(
        jax.tree_util.tree_map(lambda a: a * scale, g)))
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    with jax.default_device(first):
        p, bias = split(make_params())
        m = v = None
        losses, first_grad, load = [], None, 0
        for t, (xs, ys) in enumerate(batches, start=1):
            (total, loads), grads = grad_fn(p, bias, jnp.asarray(xs),
                                            jnp.asarray(ys))
            n_tok = xs.shape[0] * xs.shape[1]
            losses.append(float(total) / n_tok)
            load = load + jax.device_get(loads)
            if first_grad is None:
                first_grad = jax.device_get(norms(grads, 1.0 / n_tok))
            if m is None:
                m = jax.tree_util.tree_map(jnp.zeros_like, p)
                v = jax.tree_util.tree_map(jnp.zeros_like, p)
            p, m, v = step(p, grads, m, v, t, 1.0 / n_tok)
            del grads
        del m, v
        change = jax.device_get(delta(p, split(make_params())[0]))
    change[LOAD] = load
    change[ROWS_OVER] = 0 * load[:, 0]
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}


def leaf_gaps(program, reference):
    """{leaf (stacked leaves as ``name[i]``): |program's norm -
    reference's norm| over max(the reference's norm of that leaf, its
    median leaf norm)}, as host floats.  The two counts that ride beside
    the change norms are compared as counts: ``moe.load[i]`` is the
    summed |difference| over the published experts as a share of the
    layer's assignments, ``moe.rows_over[i]`` the assignments the program
    left out (any is past every limit); both are printed."""
    import json
    import numpy as onp
    names, ref, prog, counts = [], [], [], {}
    for n in sorted(reference):
        if n == LOAD:
            a, b = onp.asarray(program[n]), onp.asarray(reference[n])
            for i in range(b.shape[0]):
                counts[f"{n}[{i}]"] = float(
                    onp.abs(a[i] - b[i]).sum() / max(b[i].sum(), 1))
            continue
        if n == ROWS_OVER:
            for i, over in enumerate(onp.ravel(program[n])):
                counts[f"{n}[{i}]"] = float(over)
            continue
        r = onp.ravel(reference[n])
        names += [n if r.size == 1 else f"{n}[{i}]" for i in range(r.size)]
        ref.append(r)
        prog.append(onp.ravel(program[n]))
    ref, prog = onp.concatenate(ref), onp.concatenate(prog)
    gap = onp.abs(prog - ref) / onp.maximum(ref, onp.median(ref))
    if counts:
        print("# counts " + json.dumps(counts), flush=True)
    return dict(zip(names, gap.tolist()), **counts)


#: a leaf is dead where the reference's first gradient is under this
#: share of its median leaf's
DEAD_SHARE = 1e-3


def dead_leaves(grad_norms):
    """The leaves (named as ``leaf_gaps`` names them) whose reference
    gradient is zero but for rounding."""
    import numpy as onp
    flat = {}
    for n in sorted(grad_norms):
        r = onp.ravel(grad_norms[n])
        flat.update({(n if r.size == 1 else f"{n}[{i}]"): float(r[i])
                     for i in range(r.size)})
    floor = DEAD_SHARE * onp.median(list(flat.values()))
    return {n for n, v in flat.items() if v < floor}


def worst_leaf(gaps, skip=()):
    """(the largest gap, its leaf) over the leaves not in ``skip``."""
    leaf = max((n for n in gaps if n not in skip), key=gaps.get)
    return gaps[leaf], leaf
