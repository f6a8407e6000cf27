"""The keye family (Keye-VL-2.0's language model) in plain ``jax.numpy``:
forward, loss, gradients and MXNet's Adam, for one chip's share of the
experts.

The reference the benchmark's ``correct`` is decided against.  float32
everywhere, ``jax.default_matmul_precision("highest")``, no kernel, no
grouped product, no bound on rows, the top-k by ``lax.top_k``: one
sequence at a time.  It imports nothing of the program and takes nothing
the program made; its parameters come from ``chipbench/families/keye.py``
(the benchmark's own generator).

The equations, per layer with ``x`` the residual stream and
``u = RMS_in(x)`` (each item the released ``config.json`` does not fix is
listed in the configuration file under ``assumed``):

* Attention: ``q = RMS_h(u Wq)``, ``k = RMS_h(u Wk)``, ``v = u Wv``, no
  biases; ``RMS_h`` over the head dimension, one scale vector each shared
  by the heads; rotary embedding (rotate-half, whole head) on q and k;
  query head ``i`` reads KV head ``i // (heads / kv_heads)``;
  ``o_t = sum_{s in S_t} softmax_{s in S_t}(q_t k_s / sqrt(d)) v_s``, then
  ``Wo``; ``h = x + o``.  No gate, no window.
* Indexer, on ``u' = stop_gradient(u)``: ``qI = u' WqI`` (heads x d_I),
  ``kI = LayerNorm(u' WkI)`` (one head), rotary on both,
  ``w = u' Ww / sqrt(heads_I)``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(d_I)``.
  ``S_t``: the ``topk`` positions ``s <= t`` with the largest ``I[t, s]``
  (all while ``t < topk``), ties to the lower ``s``.
* The indexer's loss, ``L_I = sum over layers of mean_t KL(p_t ||
  softmax_{S_t}(I[t, :]))`` with ``p_t`` the mean over the query heads of
  the attention's probabilities on ``S_t``, detached.  The step's loss is
  ``L_lm + L_I``; the two detachments and the selection (no gradient)
  make each part train only its own leaves.
* Experts: ``g = RMS_post(h)``, ``P = softmax(g Wr)`` over all published
  experts, the ``k`` largest selected, ``w_e = P_e / sum of the selected
  P``, result ``h + sum over selected HELD e of w_e Expert_e(g)``, each
  expert ``(silu(g W1) * (g W3)) W2``: what the absent experts would add
  is left out, as in the program.  No shared expert.
* Final RMSNorm, untied head, mean token cross-entropy.

Weights of a linear layer are (out, in): ``y = x @ W.T``; the stacked
expert matrices are (expert, in, out): ``y = x @ W[e]``.

Memory at the cell's size (465 M parameters: 7.4 GB for parameters,
gradient and Adam's two moments): every layer is recomputed in the
backward pass (``jax.checkpoint``); the indexer's scores, the selection,
the attention and the alignment run in query blocks of 256 against every
key, each block recomputed too, so the largest temporaries are
``(32, 256, 8192)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: stacked over the layers
LAYER_LEAVES = (
    "ln_in.g", "ln_post.g", "attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w",
    "attn.q_norm.g", "attn.k_norm.g", "idx.q.w", "idx.k.w", "idx.w.w",
    "idx.k_norm.g", "idx.k_norm.b", "moe.router.w", "moe.gate.w",
    "moe.up.w", "moe.down.w")
STACKED = LAYER_LEAVES
#: the leaves only ``L_I`` trains
INDEX_LEAVES = tuple(n for n in LAYER_LEAVES if n.startswith("idx."))
#: the counts that ride beside the change norms: assignments per
#: published expert over the followed updates, assignments dropped, and —
#: of the LAST followed update — the selected (query, key) pairs of a
#: layer and their grid by sixteenth of the sequence
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"
PAIRS, GRID = "dsa.pairs", "dsa.grid"
COUNTS = (LOAD, ROWS_OVER, PAIRS, GRID)

#: queries a block (scores are heads x block x seq)
ATTN_BLOCK = 256
SIXTEENTHS = 16


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _rope(x, theta):
    """x (s, heads, d): rotate-half convention, positions 0..s-1."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def selected_pairs(seq, topk):
    """What every selection must count: ``sum_t min(t + 1, topk)``."""
    k = min(seq, topk)
    return k * (k + 1) // 2 + (seq - k) * k


def select(scores, q0, topk):
    """scores (block, s) of the queries ``q0 ...`` -> bool (block, s):
    each row's ``topk`` largest entries among ``s <= t``.  ``lax.top_k``
    puts the lower index first among equals; entries it returns from
    above the diagonal (rows with fewer than ``topk`` positions) are cut
    by the causal mask."""
    block, s = scores.shape
    rows = q0 + jnp.arange(block)[:, None]
    causal = jnp.arange(s)[None, :] <= rows
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, s))
    hit = jnp.zeros((block, s), bool).at[
        jnp.arange(block)[:, None], idx].set(True)
    return hit & causal


def _sparse_attention(q, k, v, q_idx, k_idx, w_idx, cfg):
    """q (s, kv, group, d), k/v (s, kv, d); the indexer's q_idx (s, hI,
    dI), k_idx (s, dI), w_idx (s, hI).  Query blocks against every key,
    each block recomputed in the backward pass.  Returns (attention
    output (s, kv*group*d), sum over queries of KL(p_t || softmax_S(I)),
    selected pairs by sixteenth of query and key (16, 16))."""
    s, kv, group, d = q.shape
    d_idx = q_idx.shape[-1]
    topk = cfg["sa_config"]["topk"]
    block = math.gcd(s, ATTN_BLOCK)
    sixteenth = (jnp.arange(s) * SIXTEENTHS // s)[:, None] \
        == jnp.arange(SIXTEENTHS)

    @jax.checkpoint
    def one(qb, qib, wb, q0):
        per_head = jnp.einsum("qjd,kd->jqk", qib, k_idx)
        index = jnp.einsum("jqk,qj->qk", jax.nn.relu(per_head), wb) \
            / math.sqrt(d_idx)
        chosen = select(jax.lax.stop_gradient(index), q0, topk)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        att = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hgqk,khd->qhgd", att, v)
        p = jax.lax.stop_gradient(jnp.mean(att, axis=(0, 1)))
        logq = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf),
                                  axis=-1)
        kl = jnp.sum(jnp.where(chosen & (p > 0),
                               p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                    - jnp.where(chosen, logq, 0.0)), 0.0))
        rows = jax.lax.dynamic_slice_in_dim(sixteenth, q0, block, 0)
        grid = jnp.einsum("qa,qk,kb->ab", rows.astype(jnp.float32),
                          chosen.astype(jnp.float32),
                          sixteenth.astype(jnp.float32))
        return out, kl, grid

    def blocks(t):
        return t.reshape(s // block, block, *t.shape[1:])

    out, kl, grid = jax.lax.map(lambda a: one(*a), (
        blocks(q), blocks(q_idx), blocks(w_idx),
        jnp.arange(s // block) * block))
    return (out.reshape(s, kv * group * d), jnp.sum(kl),
            jnp.round(jnp.sum(grid, axis=0)).astype(jnp.int32))


def _attention(u, p, cfg):
    s = u.shape[0]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    sa = cfg["sa_config"]
    h_idx, d_idx = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _rope(_rms((u @ p["attn.q.w"].T).reshape(s, heads, d),
                   p["attn.q_norm.g"], eps), theta)
    k = _rope(_rms((u @ p["attn.k.w"].T).reshape(s, kv, d),
                   p["attn.k_norm.g"], eps), theta)
    v = (u @ p["attn.v.w"].T).reshape(s, kv, d)
    ud = jax.lax.stop_gradient(u)
    q_idx = _rope((ud @ p["idx.q.w"].T).reshape(s, h_idx, d_idx), theta)
    k_idx = _rope(_layer_norm(ud @ p["idx.k.w"].T, p["idx.k_norm.g"],
                              p["idx.k_norm.b"], eps)[:, None, :],
                  theta)[:, 0]
    w_idx = (ud @ p["idx.w.w"].T) / math.sqrt(h_idx)
    o, kl, grid = _sparse_attention(q.reshape(s, kv, heads // kv, d), k, v,
                                    q_idx, k_idx, w_idx, cfg)
    return o @ p["attn.o.w"].T, kl, grid


def route(g, router, cfg):
    """(selected experts (s, k), their weights (s, k), assignments per
    published expert (n,)) of one sequence."""
    probs = jax.nn.softmax(g @ router.T, axis=-1)
    chosen, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    load = jnp.sum(idx[..., None] == jnp.arange(cfg["num_experts"]),
                   axis=(0, 1))
    return idx, w, load


def _experts(g, p, cfg):
    """The held experts' part, every held expert on every token, weighted
    by the router; and the layer's assignment counts."""
    idx, w, load = route(g, p["moe.router.w"], cfg)
    lo = cfg["experts_held_from"]

    def one(acc, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
        y = (jax.nn.silu(g @ gate) * (g @ up)) @ down
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(g), (
        jnp.arange(cfg["num_experts_held"]), p["moe.gate.w"],
        p["moe.up.w"], p["moe.down.w"]))
    return out, load


def _layer(x, p, cfg):
    eps = cfg["rms_norm_eps"]
    o, kl, grid = _attention(_rms(x, p["ln_in.g"], eps), p, cfg)
    h = x + o
    ff, load = _experts(_rms(h, p["ln_post.g"], eps), p, cfg)
    return h + ff, kl, (load, grid)


def layer_params(params, i):
    return {n: params[n][i] for n in LAYER_LEAVES}


def forward(params, tokens, cfg):
    """tokens (s,) int32 -> (logits (s, vocab) float32, sum over layers
    and queries of the indexer's KL, (assignments (layers, n), selection
    grids (layers, 16, 16))), one sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens]
        kls, loads, grids = 0.0, [], []
        for i in range(cfg["num_hidden_layers"]):
            layer = jax.checkpoint(functools.partial(_layer, cfg=cfg))
            x, kl, (load, grid) = layer(x, layer_params(params, i))
            kls = kls + kl
            loads.append(load)
            grids.append(grid)
        x = _rms(x, params["ln_f.g"], cfg["rms_norm_eps"])
        return x @ params["head.w"].T, kls, (jnp.stack(loads),
                                             jnp.stack(grids))


def sequence_loss_sum(params, tokens, labels, cfg):
    """(sum over one sequence's positions of -log softmax(logits)[label]
    plus the sum over its layers and queries of the indexer's KL, (the
    sequence's assignment counts, its selection grids))."""
    logits, kls, counts = forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lm = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return lm + kls, counts


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
    a stacked leaf (an expert layer's sixteen matrices of a kind are one
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

    ``make_params()`` makes the starting parameters (it is called again
    at the end rather than a copy kept); ``batches`` is a list of (tokens
    (B, S), labels (B, S)) int32 host arrays.  Each update takes its
    batch one sequence at a time (a scan) and sums the gradients; the
    loss is the mean over all B*S positions of the token loss plus the
    indexer's.  Returns the losses, the per-leaf norms of the first
    gradient, and the per-leaf norms of the parameters' change after the
    last update with, beside them, the counts: assignments per layer and
    published expert over all the updates (``moe.load``), assignments
    left out (``moe.rows_over``: none, there is no bound here), and the
    LAST update's selected pairs a layer (``dsa.pairs``) with their grid
    (``dsa.grid``) — what the program's counters hold after that update.
    """
    first = list(devices or jax.devices()[:1])[0]
    n_layer = cfg["num_hidden_layers"]

    def batch_loss(p, xs, ys):
        one = jax.checkpoint(lambda x, y: sequence_loss_sum(p, x, y, cfg))

        def body(carry, xy):
            loss, (loads, grids) = one(*xy)
            return (carry[0] + loss, (carry[1][0] + loads,
                                      carry[1][1] + grids)), None

        zero = (jnp.zeros((), jnp.float32),
                (jnp.zeros((n_layer, cfg["num_experts"]), jnp.int32),
                 jnp.zeros((n_layer, SIXTEENTHS, SIXTEENTHS), jnp.int32)))
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
        p = dict(make_params())
        m = v = None
        losses, first_grad, load, grid = [], None, 0, None
        for t, (xs, ys) in enumerate(batches, start=1):
            (total, (loads, grids)), grads = grad_fn(
                p, jnp.asarray(xs), jnp.asarray(ys))
            n_tok = xs.shape[0] * xs.shape[1]
            losses.append(float(total) / n_tok)
            load = load + jax.device_get(loads)
            grid = jax.device_get(grids)
            if first_grad is None:
                first_grad = jax.device_get(norms(grads, 1.0 / n_tok))
            if m is None:
                m = jax.tree_util.tree_map(jnp.zeros_like, p)
                v = jax.tree_util.tree_map(jnp.zeros_like, p)
            p, m, v = step(p, grads, m, v, t, 1.0 / n_tok)
            del grads
        del m, v
        change = jax.device_get(delta(p, dict(make_params())))
    change[LOAD] = load
    change[ROWS_OVER] = 0 * load[:, 0]
    change[GRID] = grid
    change[PAIRS] = grid.sum(axis=(1, 2))
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}


def leaf_gaps(program, reference):
    """{leaf (stacked leaves as ``name[i]``): |program's norm -
    reference's norm| over max(the reference's norm of that leaf, its
    median leaf norm)}, as host floats.  The counts that ride beside the
    change norms are compared as counts, and printed: ``moe.load[i]`` is
    the summed |difference| over the published experts as a share of the
    layer's assignments; ``moe.rows_over[i]`` the assignments the program
    left out; ``dsa.pairs[i]`` the |difference| of the selected pairs in
    whole pairs (the reference's are ``sum_t min(t + 1, topk)``: a
    selection that is dense, a window, or off by one key a row differs by
    thousands); ``dsa.grid[i]`` the summed |difference| over the grid as
    a share of the layer's pairs.  Any of ``rows_over`` or ``pairs`` is
    past every limit."""
    import json
    import numpy as onp
    names, ref, prog, counts = [], [], [], {}
    for n in sorted(reference):
        if n in (LOAD, GRID):
            a, b = onp.asarray(program[n]), onp.asarray(reference[n])
            for i in range(b.shape[0]):
                counts[f"{n}[{i}]"] = float(
                    onp.abs(a[i] - b[i]).sum() / max(b[i].sum(), 1))
            continue
        if n == ROWS_OVER:
            for i, over in enumerate(onp.ravel(program[n])):
                counts[f"{n}[{i}]"] = float(over)
            continue
        if n == PAIRS:
            a, b = onp.ravel(program[n]), onp.ravel(reference[n])
            for i in range(b.size):
                counts[f"{n}[{i}]"] = float(abs(int(a[i]) - int(b[i])))
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
