"""The nemotron_h family (Nemotron-H) in plain ``jax.numpy``: forward,
loss, gradients and MXNet's Adam, for one chip's share of the experts.

The reference the benchmark's ``correct`` is decided against.  float32
everywhere, ``jax.default_matmul_precision("highest")``, no kernel, no
chunked scan, no grouped product, no bound on rows: one sequence at a
time; the state-space recurrence **itself, one token after another**
(``lax.scan`` over the sequence, a ``(heads, head_dim, state)`` state
carried), the convolution as four shifted adds, every held expert
applied to every token and weighted by the router (weight 0 where the
token did not select it).  It imports nothing of the program and takes
nothing the program made; its parameters come from
``chipbench/families/nemotron_h.py`` (the benchmark's own generator).

The equations, with ``x`` the residual stream (the family's public
modelling code; each item the released ``config.json`` does not fix is
listed in the configuration file under ``assumed``):

* Layer ``i``: ``x <- x + F_i(RMS_i(x))``, ``F_i`` by
  ``hybrid_override_pattern[i]``; RMSNorm with a learned scale, eps from
  the file.  After the last layer ``RMS_f``, the untied head, mean token
  cross-entropy.  Embedding rows not scaled.
* ``M``, Mamba-2 mixer: ``H`` heads of ``P`` channels (``d_in = H P``),
  ``G`` groups of B / C, state ``N``.  ``[z | xBC | dt] = u W_in``
  (``d_in | d_in + 2 G N | H``); ``xBC <- silu(conv(xBC))``, causal,
  depthwise, ``conv_kernel`` taps, left-padded, with bias; split
  ``x (H, P)``, ``B (G, N)``, ``C (G, N)``, head ``h`` reads group
  ``h // (H / G)``; ``D_t = softplus(dt_t + dt_bias)``,
  ``A = -exp(A_log)``; ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``,
  ``S_0 = 0``; ``y_t = S_t C_t + D x_t``; ``y <- RMS_grouped(y *
  silu(z)) * w`` — the gate first, the mean square over each group of
  ``d_in / G`` channels — and ``out = y W_out``.
* ``*``, attention: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``, causal
  softmax at ``1 / sqrt(head_dim)``, query head ``i`` reads KV head
  ``i // (heads / kv_heads)``, ``o Wo``.  No norm, no gate, no position.
* ``E``, experts: ``s = sigmoid(u Wr)``, selection = top-k of ``s +
  bias``, ``w_e = routed_scaling_factor * s_e / (sum of the selected s +
  1e-20)``, expert ``e(u) = relu(u W_up)^2 W_down``, the shared expert
  the same form at its own width; result ``Shared(u) + sum over selected
  HELD e of w_e e(u)``: what the absent experts would add is left out,
  as in the program.

Departure (one, and it changes no value): for the gradient the
token-by-token scan is taken in stretches of ``SCAN_BLOCK`` tokens, each
made again in the backward pass (``jax.checkpoint``) — keeping all 8192
states of ``(64, 64, 128)`` floats would be 17 GB.  Every layer is
recomputed in the backward pass too, and attention runs in query blocks,
as in ``reference/afmoe.py``.

Weights of a linear layer are (out, in): ``y = x @ W.T``; the stacked
expert matrices are (expert, in, out): ``y = x @ W[e]``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: stacked over all layers / the layers of one kind of the pattern
LAYER_LEAVES = ("ln.g",)
KIND_LEAVES = {
    "M": ("ssm.in.w", "ssm.conv.w", "ssm.conv.b", "ssm.dt_bias",
          "ssm.A_log", "ssm.D", "ssm.norm.g", "ssm.out.w"),
    "*": ("attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w"),
    "E": ("moe.router.w", "moe.shared.up.w", "moe.shared.down.w",
          "moe.up.w", "moe.down.w"),
}
#: per expert layer, not trained
BIAS = "moe.bias"
#: the counts that ride beside the change norms: assignments per
#: published expert over the followed updates, and assignments dropped
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"
STACKED = LAYER_LEAVES + sum(KIND_LEAVES.values(), ())

#: queries a block of the attention (scores are heads x block x seq)
ATTN_BLOCK = 256
#: tokens a stretch of the recurrence that the backward pass makes again
SCAN_BLOCK = 128


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _conv(x, w, b):
    """x (s, ch), w (ch, taps), b (ch,): ``y_t = b + sum_k w[:, k]
    x[t - (taps - 1) + k]``, zeros before the sequence."""
    s, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    y = b
    for k in range(taps):
        y = y + padded[k:k + s] * w[:, k]
    return y


def recurrence(x, dt, a, b_mat, c_mat):
    """The state-space recurrence of one sequence, one token after
    another: x (s, H, P), dt (s, H), a (H,), b_mat / c_mat (s, H, N)
    (each head's own group already chosen) -> y (s, H, P) without the
    skip."""
    s, heads, dim = x.shape
    block = math.gcd(s, SCAN_BLOCK)

    def token(state, t):
        xt, dtt, bt, ct = t
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def stretch(state, ts):
        return jax.lax.scan(token, state, ts)

    def split(t):
        return t.reshape((s // block, block) + t.shape[1:])

    _, y = jax.lax.scan(
        stretch, jnp.zeros((heads, dim, b_mat.shape[-1])),
        tuple(split(t) for t in (x, dt, b_mat, c_mat)))
    return y.reshape(s, heads, dim)


def _mixer(u, p, cfg):
    s = u.shape[0]
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    d_in, gn = heads * dim, groups * state
    zxbcdt = u @ p["ssm.in.w"].T
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * gn],
                  zxbcdt[:, 2 * d_in + 2 * gn:])
    xbc = jax.nn.silu(_conv(xbc, p["ssm.conv.w"], p["ssm.conv.b"]))
    x = xbc[:, :d_in].reshape(s, heads, dim)
    per = heads // groups
    b_mat = jnp.repeat(xbc[:, d_in:d_in + gn].reshape(s, groups, state),
                       per, axis=1)
    c_mat = jnp.repeat(xbc[:, d_in + gn:].reshape(s, groups, state),
                       per, axis=1)
    y = recurrence(x, jax.nn.softplus(dt + p["ssm.dt_bias"]),
                   -jnp.exp(p["ssm.A_log"]), b_mat, c_mat) \
        + p["ssm.D"][:, None] * x
    gated = (y.reshape(s, d_in) * jax.nn.silu(z)).reshape(s, groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    return (normed.reshape(s, d_in) * p["ssm.norm.g"]) @ p["ssm.out.w"].T


def _attention_core(q, k, v):
    """q (s, kv, group, d), k/v (s, kv, d) -> (s, kv*group*d).  Query
    blocks against every key, masked; each block recomputed in the
    backward pass."""
    s, kv, group, d = q.shape
    block = math.gcd(s, ATTN_BLOCK)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(qb, q0):
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        mask = keys <= q0 + jnp.arange(block)[:, None]
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", att, v)

    out = jax.lax.map(lambda a: one(*a), (
        q.reshape(s // block, block, kv, group, d),
        jnp.arange(s // block) * block))
    return out.reshape(s, kv * group * d)


def _attention(u, p, cfg):
    s = u.shape[0]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    q = (u @ p["attn.q.w"].T).reshape(s, kv, heads // kv, d)
    k = (u @ p["attn.k.w"].T).reshape(s, kv, d)
    v = (u @ p["attn.v.w"].T).reshape(s, kv, d)
    return _attention_core(q, k, v) @ p["attn.o.w"].T


def route(u, router, bias, cfg):
    """(selected experts (s, k), their weights (s, k), assignments per
    published expert (n,)) of one sequence."""
    scores = jax.nn.sigmoid(u @ router.T)
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = cfg["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(idx[..., None] == jnp.arange(cfg["n_routed_experts"]),
                   axis=(0, 1))
    return idx, w, load


def _relu2(u, up, down):
    """(in, out) matrices."""
    return jnp.square(jax.nn.relu(u @ up)) @ down


def _experts(u, p, bias, cfg):
    """Shared(u) + the held experts' part, every held expert on every
    token, weighted by the router; and the layer's assignment counts."""
    idx, w, load = route(u, p["moe.router.w"], bias, cfg)
    lo = cfg["experts_held_from"]

    def one(acc, ew):
        e, up, down = ew
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _relu2(u, up, down), None

    shared = _relu2(u, p["moe.shared.up.w"].T, p["moe.shared.down.w"].T)
    out, _ = jax.lax.scan(one, shared, (
        jnp.arange(cfg["num_experts_held"]), p["moe.up.w"],
        p["moe.down.w"]))
    return out, load


def _layer(x, p, bias, cfg, kind):
    u = _rms(x, p["ln.g"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + _mixer(u, p, cfg), None
    if kind == "*":
        return x + _attention(u, p, cfg), None
    out, load = _experts(u, p, bias, cfg)
    return x + out, load


def layer_params(params, i, cfg):
    """Layer ``i``'s own leaves out of the stacks."""
    pattern = cfg["hybrid_override_pattern"]
    kind = pattern[i]
    k = pattern[:i].count(kind)
    p = {n: params[n][i] for n in LAYER_LEAVES}
    p.update({n: params[n][k] for n in KIND_LEAVES[kind]})
    return p


def forward(params, bias, tokens, cfg):
    """tokens (s,) int32 -> (logits (s, vocab) float32, assignments per
    expert layer and published expert (expert layers, n)), one
    sequence."""
    pattern = cfg["hybrid_override_pattern"]
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens]
        loads = []
        for i, kind in enumerate(pattern):
            layer = jax.checkpoint(functools.partial(
                _layer, cfg=cfg, kind=kind))
            x, load = layer(x, layer_params(params, i, cfg),
                            bias[pattern[:i].count("E")] if kind == "E"
                            else None)
            if load is not None:
                loads.append(load)
        x = _rms(x, params["ln_f.g"], cfg["layer_norm_epsilon"])
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
    """{name: L2 norms, one per parameter of the program}: (layers of
    its kind,) for a stacked leaf (an expert layer's eight matrices of a
    kind are one parameter there), (1,) for the rest."""
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

        n_moe = cfg["hybrid_override_pattern"].count("E")
        zero = (jnp.zeros((), jnp.float32),
                jnp.zeros((n_moe, cfg["n_routed_experts"]), jnp.int32))
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
