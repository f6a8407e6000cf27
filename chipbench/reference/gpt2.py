"""GPT-2 in plain ``jax.numpy``: forward, loss, gradients and MXNet's Adam.

The reference the benchmark's ``correct`` is decided against.  float32
everywhere, ``jax.default_matmul_precision("highest")``, no kernel, no
cache, no batching: one sequence at a time.  It imports nothing of the
program and takes nothing the program made; its parameters come from
``chipbench/families/gpt2.py`` (the benchmark's own generator), stacked
over layers so that the stack can be scanned.

It follows the GPT-2 description (Radford et al. 2019; the released
``config.json``): learned positions, pre-norm blocks
``x + attn(ln_1(x))`` then ``x + mlp(ln_2(x))``, LayerNorm eps 1e-5,
scores scaled by 1/sqrt(head size), causal mask, final LayerNorm, head
tied to the token embedding.  One departure, stated in each
configuration file under ``activation_function``: the release computes
``gelu_new`` (the tanh approximation); the program's zoo computes the
exact erf GELU, and the configuration files state ``gelu`` so that the
limits below measure precision, not that known difference (at most
4.7e-4 per activation).  Both are implemented here.

Weights of a linear layer are (out, in), as the generator makes them:
``y = x @ W.T + b``.

Limits (how each was set is in PERF.md section 2; numbers are from chip
runs of PR 23) live in the cell files under ``check``; this file computes
the numbers that are held against them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the stacked per-layer leaves, in the generator's order
LAYER_LEAVES = (
    "ln_1.g", "ln_1.b", "attn.q.w", "attn.q.b", "attn.k.w", "attn.k.b",
    "attn.v.w", "attn.v.b", "attn.o.w", "attn.o.b", "ln_2.g", "ln_2.b",
    "mlp.fc.w", "mlp.fc.b", "mlp.proj.w", "mlp.proj.b")


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _act(x, kind):
    if kind == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    if kind == "gelu_new":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation_function {kind!r}")


def _block(x, p, cfg):
    s, e = x.shape
    heads = cfg["n_head"]
    d = e // heads
    eps = cfg["layer_norm_epsilon"]
    h = _ln(x, p["ln_1.g"], p["ln_1.b"], eps)
    q = (h @ p["attn.q.w"].T + p["attn.q.b"]).reshape(s, heads, d)
    k = (h @ p["attn.k.w"].T + p["attn.k.b"]).reshape(s, heads, d)
    v = (h @ p["attn.v.w"].T + p["attn.v.b"]).reshape(s, heads, d)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, e)
    x = x + out @ p["attn.o.w"].T + p["attn.o.b"]
    h = _ln(x, p["ln_2.g"], p["ln_2.b"], eps)
    h = _act(h @ p["mlp.fc.w"].T + p["mlp.fc.b"],
             cfg["activation_function"])
    return x + h @ p["mlp.proj.w"].T + p["mlp.proj.b"]


def forward(params, tokens, cfg, remat=False):
    """tokens (s,) int32 -> logits (s, vocab) float32, one sequence.
    ``remat`` recomputes each block in the backward pass (the same
    arithmetic; it only keeps the gradient's memory to one block)."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        x = params["wte"][tokens] + params["wpe"][:s]
        layers = {n: params[n] for n in LAYER_LEAVES}

        def body(x, p):
            return _block(x, p, cfg), None

        if remat:
            body = jax.checkpoint(body)

        x, _ = jax.lax.scan(body, x, layers)
        x = _ln(x, params["ln_f.g"], params["ln_f.b"],
                cfg["layer_norm_epsilon"])
        return x @ params["wte"].T


def sequence_loss_sum(params, tokens, labels, cfg):
    """Sum over one sequence's positions of -log softmax(logits)[label]."""
    logits = forward(params, tokens, cfg, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


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
    """{name: per-layer L2 norms (L,)} for stacked leaves, (1,) for the
    rest — one number per leaf of the program's tree."""
    out = {}
    for n, a in tree.items():
        a = a.astype(jnp.float32)
        if n in LAYER_LEAVES:
            out[n] = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        else:
            out[n] = jnp.sqrt(jnp.sum(a * a)).reshape(1)
    return out


def train_reference(make_params, batches, cfg, opt, devices=None):
    """Follow the program's first ``len(batches)`` updates.

    ``make_params()`` makes the starting parameters (it is called again
    at the end rather than a copy kept); ``batches`` is a list of
    (tokens (B, S), labels (B, S)) int32 host arrays.  Each update takes its batch one sequence at a time (a scan)
    and sums the gradients; the loss is the mean over all B*S positions.
    With several ``devices`` each takes every n-th sequence and the sums
    are added on the first, where Adam runs — the same arithmetic, four
    times sooner on a four-chip host, and no chip holds more than the
    parameters, one gradient and the two moments.  Returns the losses,
    the per-leaf norms of the first gradient and the per-leaf norms of
    the parameters' change after the last update.
    """
    devices = list(devices or jax.devices()[:1])
    first = devices[0]

    def share_loss(p, xs, ys):
        one = jax.checkpoint(lambda x, y: sequence_loss_sum(p, x, y, cfg))

        def body(total, xy):
            return total + one(*xy), None

        return jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ys))[0]

    grad_fn = jax.jit(jax.value_and_grad(share_loss))
    step = jax.jit(lambda p, g, m, v, t, scale: adam_update(
        p, jax.tree_util.tree_map(lambda a: a * scale, g), m, v, t, opt),
        static_argnums=4, donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda g, scale: leaf_norms(
        jax.tree_util.tree_map(lambda a: a * scale, g)))
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    p = jax.device_put(make_params(), first)
    m = v = None
    losses, first_grad = [], None
    for t, (xs, ys) in enumerate(batches, start=1):
        parts = []
        for k, dev in enumerate(devices):
            pk = p if dev is first else jax.device_put(p, dev)
            parts.append(grad_fn(pk, jax.device_put(xs[k::len(devices)], dev),
                                 jax.device_put(ys[k::len(devices)], dev)))
        total, grads = parts[0]
        total = float(total)
        for loss_k, g_k in parts[1:]:
            total += float(loss_k)
            for n in grads:     # leaf by leaf: one leaf in flight, not a tree
                grads[n] = grads[n] + jax.device_put(g_k.pop(n), first)
        del parts
        n_tok = xs.shape[0] * xs.shape[1]
        losses.append(total / n_tok)
        if first_grad is None:
            first_grad = jax.device_get(norms(grads, 1.0 / n_tok))
        if m is None:
            m = jax.tree_util.tree_map(jnp.zeros_like, p)
            v = jax.tree_util.tree_map(jnp.zeros_like, p)
        p, m, v = step(p, grads, m, v, t, 1.0 / n_tok)
        del grads
    del m, v
    change = jax.device_get(delta(p, jax.device_put(make_params(), first)))
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}


def leaf_gaps(program, reference):
    """{leaf (layer leaves as ``name[i]``): |program's norm - reference's
    norm| over max(the reference's norm of that leaf, its median leaf
    norm)}, as host floats."""
    import numpy as onp
    names, ref, prog = [], [], []
    for n in sorted(reference):
        r = onp.ravel(reference[n])
        names += [n if r.size == 1 else f"{n}[{i}]" for i in range(r.size)]
        ref.append(r)
        prog.append(onp.ravel(program[n]))
    ref, prog = onp.concatenate(ref), onp.concatenate(prog)
    gap = onp.abs(prog - ref) / onp.maximum(ref, onp.median(ref))
    return dict(zip(names, gap.tolist()))


#: a leaf is dead where the reference's first gradient is under this
#: share of its median leaf's (at the tiny size the key biases read 1e-8
#: of it and the smallest live leaf, a query bias, 6e-3)
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
