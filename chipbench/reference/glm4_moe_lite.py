"""The glm4_moe_lite family (GLM-4.7-Flash) in plain ``jax.numpy``:
forward, loss, gradients and MXNet's Adam, for one chip's share of the
experts.

The reference the benchmark's ``correct`` is decided against.  float32
everywhere, ``jax.default_matmul_precision("highest")``, no kernel, no
grouped product, no bound on rows: one sequence at a time, every held
expert applied to every token and weighted by the router (weight 0 where
the token did not select it).  It imports nothing of the program and
takes nothing the program made; its parameters come from
``chipbench/families/glm4_moe_lite.py`` (the benchmark's own generator).

The equations, with ``x`` the residual stream (each item the released
``config.json`` does not fix is listed in the configuration file under
``assumed``):

* Layer: ``h = x + Attn(RMS_in(x))``, ``x' = h + FF(RMS_post(h))``;
  RMSNorm with a learned scale, eps from the file.
* ``Attn(u)``, multi-head latent attention, ``H`` heads, no bias:
  ``c_q = RMS(u W_qa)`` (``q_lora_rank``), ``q = c_q W_qb``, a head
  ``(nope | rope)``; ``[c_kv | k_r] = u W_kva`` (``kv_lora_rank |
  rope``), ``[k_nope | v] = RMS(c_kv) W_kvb``, a head ``(nope |
  v_head_dim)``.  Each head's ``q_rope`` and the **one** ``k_r`` are
  rotated (rotate-half, all ``rope`` dimensions, positions 0..s-1);
  ``k_h = [k_nope_h | k_r]``, the same ``k_r`` for every head.  Scores
  ``q_h . k_h / sqrt(nope + rope)``, causal softmax,
  ``concat_h(P_h v_h) W_o``.
* ``FF`` on the leading ``first_k_dense_replace`` layers:
  ``(silu(u W1) * (u W3)) W2``.  On expert layers: ``s = sigmoid(u Wr)``,
  selection = top-k of ``s + e_score_correction_bias`` (``n_group`` 1: no
  group limit), ``w_e = routed_scaling_factor * s_e / (sum of the
  selected s + 1e-20)``, result ``Shared(u) + sum over selected HELD e of
  w_e Expert_e(u)``: what the absent experts would add is left out, as in
  the program.
* Embedding rows as they are, final RMSNorm, untied head, mean token
  cross-entropy.
* With ``num_nextn_predict_layers`` 1, DeepSeek-V3's multi-token
  prediction depth: with ``h`` the last layer's output **before** the
  final norm, for ``i < s - 1``:
  ``h'_i = W_eh [RMS_e(Emb(t_{i+1})) ; RMS_h(h_i)]``, one more expert
  layer over those ``s - 1`` positions (rotary positions 0..s-2), a final
  norm of its own, **the main embedding and the main head**; position
  ``i`` predicts ``t_{i+2}``.  Loss ``CE_main + mtp_loss_weight *
  CE_mtp``, ``CE_mtp`` a mean over the ``s - 1`` positions.  Sliced
  plainly: nothing is rolled or masked here.

Weights of a linear layer are (out, in): ``y = x @ W.T``; the stacked
expert matrices are (expert, in, out): ``y = x @ W[e]``.

Memory at the cell's size (591 M parameters: 9.5 GB for parameters,
gradient and Adam's two moments, which leaves the gradient program under
7 GB of the chip): every layer is recomputed in the backward pass
(``jax.checkpoint``); attention runs in query blocks, the dense
feed-forward and the loss in token blocks, each recomputed too; the
experts' loop keeps its running sum only; and the program is
differentiated with the stacked leaves taken apart by layer
(``by_layer``).  Compiled for a described v5e its scratch is 6.0 GB
(9.8 before those four measures, which the chip refused: PERF.md
section 6, PR 40).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: stacked over all layers / the dense layers / the expert layers
LAYER_LEAVES = (
    "ln_in.g", "ln_post_attn.g", "attn.q_a.w", "attn.q_a_norm.g",
    "attn.q_b.w", "attn.kv_a.w", "attn.kv_a_norm.g", "attn.kv_b.w",
    "attn.o.w")
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
#: the second prediction depth's own leaves, none stacked: one expert
#: layer's under ``mtp.`` and, joining it to the model, ``mtp.enorm.g``,
#: ``mtp.hnorm.g``, ``mtp.eh.w``, ``mtp.ln_f.g``
MTP = "mtp."
MTP_BIAS = MTP + BIAS

#: queries a block of the attention (scores are heads x block x seq)
ATTN_BLOCK = 256
#: tokens a block of the dense feed-forward and of the loss (the logits
#: are block x vocabulary)
TOKEN_BLOCK = 1024


def n_dense(cfg):
    return cfg["first_k_dense_replace"]


def has_mtp(cfg):
    return bool(cfg["num_nextn_predict_layers"])


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
    """(out, in) matrices.  In blocks of tokens, each made again in the
    backward pass: the hidden activation of the dense layer is
    (8192, 10240) three times over."""
    block = math.gcd(u.shape[0], TOKEN_BLOCK)
    one = jax.checkpoint(
        lambda ub: (jax.nn.silu(ub @ gate.T) * (ub @ up.T)) @ down.T)
    return jax.lax.map(one, u.reshape(-1, block, u.shape[1])).reshape(
        u.shape[0], -1)


def _attention_core(q, k, v):
    """q / k (s, heads, d), v (s, heads, dv) -> (s, heads * dv).  Query
    blocks against every key, masked; each block recomputed in the
    backward pass."""
    s, heads, d = q.shape
    block = math.gcd(s, ATTN_BLOCK)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(qb, q0):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        mask = keys <= q0 + jnp.arange(block)[:, None]
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", att, v)

    out = jax.lax.map(lambda a: one(*a), (
        q.reshape(s // block, block, heads, d),
        jnp.arange(s // block) * block))
    return out.reshape(s, -1)


def _attention(u, p, cfg):
    s = u.shape[0]
    heads, eps, theta = (cfg["num_attention_heads"], cfg["rms_norm_eps"],
                         cfg["rope_theta"])
    nope, rope, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["kv_lora_rank"])
    c_q = _rms(u @ p["attn.q_a.w"].T, p["attn.q_a_norm.g"], eps)
    q = (c_q @ p["attn.q_b.w"].T).reshape(s, heads, nope + rope)
    kv_a = u @ p["attn.kv_a.w"].T
    c_kv = _rms(kv_a[:, :rank], p["attn.kv_a_norm.g"], eps)
    k_r = _rope(kv_a[:, None, rank:], theta)                # (s, 1, rope)
    kv = (c_kv @ p["attn.kv_b.w"].T).reshape(s, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (s, heads, rope))], -1)
    return _attention_core(q, k, kv[..., nope:]) @ p["attn.o.w"].T


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


def _experts(u, p, bias, cfg):
    """Shared(u) + the held experts' part, every held expert on every
    token, weighted by the router; and the layer's assignment counts."""
    idx, w, load = route(u, p["moe.router.w"], bias, cfg)
    lo = cfg["experts_held_from"]

    @jax.checkpoint
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


def _layer(x, p, bias, cfg, dense):
    eps = cfg["rms_norm_eps"]
    h = x + _attention(_rms(x, p["ln_in.g"], eps), p, cfg)
    u = _rms(h, p["ln_post_attn.g"], eps)
    if dense:
        return h + _swiglu(u, p["mlp.gate.w"], p["mlp.up.w"],
                           p["mlp.down.w"]), None
    ff, load = _experts(u, p, bias, cfg)
    return h + ff, load


def layer_params(params, i, cfg):
    """Layer ``i``'s own leaves out of the stacks."""
    p = {n: params[n][i] for n in LAYER_LEAVES}
    if i < n_dense(cfg):
        p.update({n: params[n][i] for n in DENSE_LEAVES})
    else:
        p.update({n: params[n][i - n_dense(cfg)] for n in MOE_LEAVES})
    return p


def hidden_states(params, bias, tokens, cfg):
    """tokens (s,) int32 -> (the last layer's output before the final
    norm (s, hidden), assignments per expert layer and published expert
    (layers - dense, n))."""
    x = params["wte"][tokens]
    loads = []
    for i in range(cfg["num_hidden_layers"]):
        dense = i < n_dense(cfg)
        layer = jax.checkpoint(functools.partial(_layer, cfg=cfg,
                                                 dense=dense))
        x, load = layer(x, layer_params(params, i, cfg),
                        None if dense else bias[i - n_dense(cfg)])
        if load is not None:
            loads.append(load)
    return x, jnp.stack(loads)


def forward(params, bias, tokens, cfg):
    """tokens (s,) int32 -> (logits (s, vocab) float32, assignments per
    expert layer), one sequence."""
    with jax.default_matmul_precision("highest"):
        x, loads = hidden_states(params, bias, tokens, cfg)
        x = _rms(x, params["ln_f.g"], cfg["rms_norm_eps"])
        return x @ params["head.w"].T, loads


def mtp_hidden(params, mtp_bias, hidden, tokens, cfg):
    """The second depth's normed hidden states for the main head,
    (s - 1, hidden): position ``i`` from ``hidden[i]`` and token
    ``i + 1``; and its layer's counts."""
    eps = cfg["rms_norm_eps"]
    x = jnp.concatenate(
        [_rms(params["wte"][tokens[1:]], params["mtp.enorm.g"], eps),
         _rms(hidden[:-1], params["mtp.hnorm.g"], eps)], -1) \
        @ params["mtp.eh.w"].T
    p = {n: params[MTP + n] for n in LAYER_LEAVES + MOE_LEAVES}
    x, load = jax.checkpoint(functools.partial(_layer, cfg=cfg, dense=False))(
        x, p, mtp_bias)
    return _rms(x, params["mtp.ln_f.g"], eps), load


def _nll_sum(x, head, labels):
    """Sum over the positions of -log softmax(x head^T)[label], in blocks
    of tokens, each made again in the backward pass: the logits of 8192
    tokens are 634 MB, and their log-softmax and gradient as much
    again."""
    block = math.gcd(x.shape[0], TOKEN_BLOCK)

    @jax.checkpoint
    def one(xb, yb):
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=-1))

    return jnp.sum(jax.lax.map(lambda a: one(*a), (
        x.reshape(-1, block, x.shape[1]), labels.reshape(-1, block))))


def sequence_loss_sum(params, bias, tokens, labels, cfg, mtp_bias=None):
    """(what one sequence adds to the batch's loss times the batch's
    positions — sum over its ``s`` positions of -log softmax(logits)
    [label], plus with a second depth ``mtp_loss_weight * s / (s - 1)``
    times that depth's sum over its ``s - 1`` —, the sequence's assignment
    counts of the main layers)."""
    s = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        hidden, loads = hidden_states(params, bias, tokens, cfg)
        total = _nll_sum(_rms(hidden, params["ln_f.g"], cfg["rms_norm_eps"]),
                         params["head.w"], labels)
        if has_mtp(cfg):
            second, _ = mtp_hidden(params, mtp_bias, hidden, tokens, cfg)
            total = total + cfg["mtp_loss_weight"] * s / (s - 1) \
                * _nll_sum(second, params["head.w"], labels[1:])
    return total, loads


def adam_update(params, grads, m, v, t, opt):
    """MXNet's Adam (``optimizer/adam.py``): bias correction folded into
    the rate, epsilon added to the uncorrected sqrt(v), no weight decay.
    Any pytree of parameters (stacked leaves whole or ``by_layer``)."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    tmap = jax.tree_util.tree_map
    new_m = tmap(lambda m_, g: b1 * m_ + (1.0 - b1) * g, m, grads)
    new_v = tmap(lambda v_, g: b2 * v_ + (1.0 - b2) * g * g, v, grads)
    new_p = tmap(lambda p, m_, v_: p - lr_t * m_ / (jnp.sqrt(v_) + eps),
                 params, new_m, new_v)
    return new_p, new_m, new_v


def by_layer(tree):
    """The stacked leaves as tuples of their layers' arrays
    (``tree[name][i]`` reads the same either way).  The gradient program
    is differentiated in this form: with the stacks whole, every layer's
    gradient is staged a second time to be put into its stack, 2.4 GB at
    the cell's size that the chip does not have beside Adam's state."""
    return {n: tuple(a) if n in STACKED else a for n, a in tree.items()}


def leaf_norms(tree):
    """{name: L2 norms, one per parameter of the program}: (layers,) for
    a stacked leaf (an expert layer's eight matrices of a kind are one
    parameter there), whole or ``by_layer``; (1,) for the rest."""
    def norm(a):
        a = a.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(a * a))

    out = {}
    for n, a in tree.items():
        if n in STACKED:
            out[n] = jnp.stack([norm(layer) for layer in a])
        else:
            out[n] = norm(a).reshape(1)
    return out


def split_biases(tree):
    """(the trained leaves, the expert layers' selection biases, the
    second depth's or None)."""
    tree = dict(tree)
    bias = tree.pop(BIAS)
    return tree, bias, tree.pop(MTP_BIAS, None)


def train_reference(make_params, batches, cfg, opt, devices=None):
    """Follow the program's first ``len(batches)`` updates on the first
    of ``devices``.

    ``make_params()`` makes the starting parameters and the selection
    biases (it is called again at the end rather than a copy kept);
    ``batches`` is a list of (tokens (B, S), labels (B, S)) int32 host
    arrays.  Each update takes its batch one sequence at a time and sums
    the gradients; the loss is the mean over all B*S positions
    (the second depth's term weighted as ``sequence_loss_sum`` says).
    Returns the losses, the per-leaf norms of the first gradient, and the
    per-leaf norms of the parameters' change after the last update with,
    beside them, the assignments per expert layer and published expert
    over all the updates (``moe.load``) and the assignments left out
    (``moe.rows_over``: none, there is no bound here).
    """
    first = list(devices or jax.devices()[:1])[0]

    def batch_loss(p, bias, mtp_bias, xs, ys):
        # a loop in Python, not a scan: a scan's backward pass carries a
        # second copy of every gradient, 2.4 GB the chip does not have
        one = jax.checkpoint(lambda x, y: sequence_loss_sum(
            p, bias, x, y, cfg, mtp_bias))
        total, loads = 0.0, 0
        for x, y in zip(xs, ys):
            loss, load = one(x, y)
            total, loads = total + loss, loads + load
        return total, loads

    grad_fn = jax.jit(jax.value_and_grad(batch_loss, has_aux=True))
    step = jax.jit(lambda p, g, m, v, t, scale: adam_update(
        p, jax.tree_util.tree_map(lambda a: a * scale, g), m, v, t, opt),
        static_argnums=4, donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda g, scale: leaf_norms(
        jax.tree_util.tree_map(lambda a: a * scale, g)))
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    start = jax.jit(lambda tree: by_layer(split_biases(tree)[0]))

    def memory(when):
        """The cell runs within 0.7 GB of the chip: say how the reference
        stood (nothing where the back-end keeps no statistics)."""
        stats = first.memory_stats()
        if stats:
            print(f"# reference memory, {when}: " + ", ".join(
                f"{k} {v / 1e9:.2f} GB" for k, v in sorted(stats.items())
                if k.startswith("bytes") or k == "largest_free_block_bytes"),
                flush=True)

    with jax.default_device(first):
        # the driver has freed the program's arrays, not its executables:
        # with none of the device in use the chip could reserve 7.45 GB
        # for a program's scratch, and 12.18 once JAX's caches were
        # dropped (the update then ends with 1.25 GB in one piece, not
        # 0.57, and the reference takes 21 s, not 34: PERF.md section 6,
        # PR 40).  Nothing after the window needs them
        jax.clear_caches()
        memory("at its start, JAX's caches dropped")
        made = make_params()
        bias, mtp_bias = split_biases(made)[1:]
        p = start(made)
        del made
        m = v = None
        losses, first_grad, load = [], None, 0
        for t, (xs, ys) in enumerate(batches, start=1):
            (total, loads), grads = grad_fn(p, bias, mtp_bias,
                                            jnp.asarray(xs), jnp.asarray(ys))
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
        memory("after its last update (parameters, Adam's moments)")
        change = jax.device_get(delta(p, start(make_params())))
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
