"""The ouro family (Ouro-2.6B: a stack whose layers run ``total_ut_steps``
times on shared weights, an exit gate, a loss over every exit) in plain
``jax.numpy``: forward, the loss with its gate and entropy term,
gradients and MXNet's Adam.

The reference the benchmark's ``correct`` is decided against.  float32
everywhere, ``jax.default_matmul_precision("highest")``, no kernel, no
chunked head, the passes written as a plain Python loop over the same
parameter dictionary (a shared leaf's gradient is summed over its uses by
autodiff of that loop and by nothing else; inside a pass the N layers are
a ``lax.scan`` over their stacked leaves).  It imports nothing of the
program and takes nothing the program made; its parameters come from
``chipbench/families/ouro.py`` (the benchmark's own generator).

The equations, with N layers held, T = ``total_ut_steps`` and ``h`` the
residual stream (the family's public modelling code and paper, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741; each item
the released ``config.json`` does not fix is listed in the
configuration's file under ``assumed``):

* Layer l: ``a = h + RMS2_l(Attn_l(RMS1_l(h)))``,
  ``h' = a + RMS4_l(FF_l(RMS3_l(a)))``; RMSNorm with a learned scale, eps
  from the file.
* ``Attn(u)``: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` as heads x
  ``head_dim``, no biases, no q/k norm, no gate, no window; rotary
  embedding (rotate-half, whole head, theta from the file) on q and k on
  every layer; causal ``softmax(q k^T / sqrt(head_dim)) v``; query head
  ``i`` reads KV head ``i // (heads / kv_heads)``; then ``Wo``.
* ``FF(u) = (silu(u Wg) * (u Wu)) Wd``.
* Stack: ``h = E[ids]`` (not scaled); for t = 1..T: for l = 1..N:
  ``h = Layer_l(h)`` — the same N layers' weights at every t; then
  ``h = z_t = RMS_f(h)``: the final norm's output is exit t's state and
  what pass t + 1 reads; ``g_t = sigmoid(w_g . z_t + b_g)``;
  ``logits_t = z_t W_head^T`` (untied).
* Exit distribution of a token: ``p_1 = g_1``,
  ``p_t = g_t prod_{j<t}(1 - g_j)`` for t < T,
  ``p_T = prod_{j<T}(1 - g_j)`` (``g_T`` is not read).
* Loss: ``mean over tokens of [sum_t p_t CE(logits_t, label) - beta
  H(p)]``, ``H(p) = -sum_t p_t log p_t``, labels = inputs shifted by one,
  gradients through everything.

Departures from the published description, none in value: the
distribution is computed from ``log sigmoid`` (``log p_t`` is a sum, and
``p log p`` needs no guard where a gate saturates) and not from products
of ``g``; the served form (exit by the cumulative ``p`` against
``early_exit_threshold``) is not here.

Weights of a linear layer are (out, in): ``y = x @ W.T``.

Memory at the cell's size (407 M parameters: 6.5 GB for parameters,
gradient and Adam's two moments): every layer application is recomputed
in the backward pass (``jax.checkpoint``: 16 saved inputs of 67 MB),
attention runs in query blocks and each exit's head in token blocks of
``HEAD_BLOCK`` (a block's (1024, 49152) logits are 0.2 GB), each
recomputed too; layer-wise recomputation is a way of fitting, not a
departure.  The gradient program is kept small on purpose (sixteen layer
bodies written out and a second copy of the forward behind a
whole-sequence ``jax.checkpoint`` made it 154 MB serialized, which beside
the step's 57 MB overflowed the machine's 192 MiB compile cache, so every
run compiled both again: PERF.md section 6, PR 42).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: stacked over the layers held
LAYER_LEAVES = (
    "ln_in.g", "ln_post_attn.g", "ln_pre_mlp.g", "ln_post_mlp.g",
    "attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w",
    "mlp.gate.w", "mlp.up.w", "mlp.down.w")
STACKED = LAYER_LEAVES
#: the count that rides beside the change norms: the mean exit
#: distribution (T,) of the last followed update's forward
PDF = "exit.pdf"

#: queries a block of the attention (scores are heads x block x seq);
#: tokens a block of an exit's head (logits are block x vocab)
ATTN_BLOCK = 256
HEAD_BLOCK = 1024


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


def _attention_core(q, k, v):
    """q (s, kv, group, d), k/v (s, kv, d) -> (s, kv*group*d), causal.
    Query blocks against every key, masked; each block recomputed in the
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
    q = _rope((u @ p["attn.q.w"].T).reshape(s, heads, d), cfg["rope_theta"])
    k = _rope((u @ p["attn.k.w"].T).reshape(s, kv, d), cfg["rope_theta"])
    v = (u @ p["attn.v.w"].T).reshape(s, kv, d)
    o = _attention_core(q.reshape(s, kv, heads // kv, d), k, v)
    return o @ p["attn.o.w"].T


def _layer(h, p, cfg):
    eps = cfg["rms_norm_eps"]
    a = h + _rms(_attention(_rms(h, p["ln_in.g"], eps), p, cfg),
                 p["ln_post_attn.g"], eps)
    u = _rms(a, p["ln_pre_mlp.g"], eps)
    ff = (jax.nn.silu(u @ p["mlp.gate.w"].T) * (u @ p["mlp.up.w"].T)) \
        @ p["mlp.down.w"].T
    return a + _rms(ff, p["ln_post_mlp.g"], eps)


def exit_states(params, tokens, cfg):
    """tokens (s,) int32 -> [z_1 .. z_T], each (s, hidden): one
    sequence."""
    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg))
    stack = {n: params[n] for n in LAYER_LEAVES}
    h = params["wte"][tokens]
    states = []
    for _ in range(cfg["total_ut_steps"]):
        # the N layers in order, over their stacked leaves (one body in
        # the program where a Python loop would write N)
        h, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), h, stack)
        h = _rms(h, params["ln_f.g"], cfg["rms_norm_eps"])
        states.append(h)
    return states


def exit_log_pdf(params, states):
    """[z_t (s, hidden)] -> log p (T, s)."""
    a = jnp.stack([z @ params["gate.w"][0] + params["gate.b"][0]
                   for z in states])
    log_g, log_stay = jax.nn.log_sigmoid(a), jax.nn.log_sigmoid(-a)
    rows, before = [], jnp.zeros_like(a[0])
    for t in range(len(states) - 1):
        rows.append(log_g[t] + before)
        before = before + log_stay[t]
    return jnp.stack(rows + [before])


def exit_xent(z, head, labels):
    """-log softmax(z head^T)[label] of one exit, (s,), in token blocks
    each recomputed in the backward pass."""
    s, d = z.shape
    block = math.gcd(s, HEAD_BLOCK)

    @jax.checkpoint
    def one(zb, lb):
        logp = jax.nn.log_softmax(zb @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    return jax.lax.map(lambda a: one(*a), (
        z.reshape(s // block, block, d),
        labels.reshape(s // block, block))).reshape(s)


def sequence_loss_sum(params, tokens, labels, cfg):
    """(sum over one sequence's positions of ``sum_t p_t CE_t - beta
    H(p)``, the sum over its positions of p (T,))."""
    with jax.default_matmul_precision("highest"):
        states = exit_states(params, tokens, cfg)
        log_p = exit_log_pdf(params, states)
        p = jnp.exp(log_p)
        ce = jnp.stack([exit_xent(z, params["head.w"], labels)
                        for z in states])
        per_token = jnp.sum(p * (ce + cfg["entropy_beta"] * log_p), axis=0)
        return jnp.sum(per_token), jnp.sum(p, axis=1)


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
    a stacked leaf, (1,) for the rest."""
    out = {}
    for n, a in tree.items():
        a = a.astype(jnp.float32)
        if n in STACKED:
            out[n] = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        else:
            out[n] = jnp.sqrt(jnp.sum(a * a)).reshape(1)
    return out


def batch_loss(params, xs, ys, cfg):
    """(summed loss, summed p (T,)) of a batch (B, S), one sequence at a
    time."""
    def body(carry, xy):
        loss, pdf = sequence_loss_sum(params, *xy, cfg)
        return (carry[0] + loss, carry[1] + pdf), None

    zero = (jnp.zeros((), jnp.float32),
            jnp.zeros((cfg["total_ut_steps"],), jnp.float32))
    return jax.lax.scan(body, zero, (xs, ys))[0]


def train_reference(make_params, batches, cfg, opt, devices=None):
    """Follow the program's first ``len(batches)`` updates on the first
    of ``devices``.

    ``make_params()`` makes the starting parameters (it is called again
    at the end rather than a copy kept); ``batches`` is a list of (tokens
    (B, S), labels (B, S)) int32 host arrays.  Each update takes its
    batch one sequence at a time (a scan) and sums the gradients; the
    loss is the mean over all B*S positions.  Returns the losses, the
    per-leaf norms of the first gradient, and the per-leaf norms of the
    parameters' change after the last update with, beside them, the mean
    exit distribution of the last update's forward (``exit.pdf``).
    """
    first = list(devices or jax.devices()[:1])[0]
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(batch_loss, cfg=cfg), has_aux=True))
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
        losses, first_grad, pdf = [], None, None
        for t, (xs, ys) in enumerate(batches, start=1):
            (total, pdf_sum), grads = grad_fn(p, jnp.asarray(xs),
                                              jnp.asarray(ys))
            n_tok = xs.shape[0] * xs.shape[1]
            losses.append(float(total) / n_tok)
            pdf = jax.device_get(pdf_sum) / n_tok
            if first_grad is None:
                first_grad = jax.device_get(norms(grads, 1.0 / n_tok))
            if m is None:
                m = jax.tree_util.tree_map(jnp.zeros_like, p)
                v = jax.tree_util.tree_map(jnp.zeros_like, p)
            p, m, v = step(p, grads, m, v, t, 1.0 / n_tok)
            del grads
        del m, v
        change = jax.device_get(delta(p, dict(make_params())))
    change[PDF] = pdf
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}


#: what ``exit.pdf``'s summed difference counts for in the change-norm
#: row it rides.  That row's limit is the parameters' (a leaf's update
#: off by half a percent); the distribution is what a gate moved by three
#: of Adam's full-size steps leaves of 8192 tokens' near-ties, and reads
#: ten times a parameter leaf's gap on sound runs (PERF.md section 2): at
#: a tenth, both sit under one limit with the same room, and a gate that
#: learns nothing (a summed difference near 1) is still 25 limits away
PDF_SHARE = 0.1


def leaf_gaps(program, reference):
    """{leaf (stacked leaves as ``name[i]``): |program's norm -
    reference's norm| over max(the reference's norm of that leaf, its
    median leaf norm)}, as host floats.  The mean exit distribution that
    rides beside the change norms is compared as a distribution:
    ``exit.pdf`` is ``PDF_SHARE`` x the summed |difference| over the T
    exits (both sum to 1); it is printed with the two distributions."""
    import json
    import numpy as onp
    names, ref, prog, counts = [], [], [], {}
    for n in sorted(reference):
        if n == PDF:
            a, b = onp.ravel(program[n]), onp.ravel(reference[n])
            counts[n] = PDF_SHARE * float(onp.abs(a - b).sum())
            print("# counts " + json.dumps(
                {n: counts[n], "program": a.tolist(),
                 "reference": b.tolist()}), flush=True)
            continue
        r = onp.ravel(reference[n])
        names += [n if r.size == 1 else f"{n}[{i}]" for i in range(r.size)]
        ref.append(r)
        prog.append(onp.ravel(program[n]))
    ref, prog = onp.concatenate(ref), onp.concatenate(prog)
    gap = onp.abs(prog - ref) / onp.maximum(ref, onp.median(ref))
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
