"""The GPT-2 family: seeded weights, and the zoo's net built from a
configuration file.

Two halves that must not mix.  ``leaf_shapes`` / ``make_weights`` are the
benchmark's own generator (pure JAX, nothing of the program): one jitted
call makes every leaf on the device from the seed, stacked over layers,
in the type asked for.  The reference is handed these; the program is
handed the same values under its own parameter names
(``program_leaves``).  ``build_net`` is the only function here that
imports the program: ``GPTForCausalLM(GPTModel(...))`` from the sizes of
the file, parameters set with ``Parameter.set_data`` (no host
initializer runs).

Initialisation follows GPT-2's: N(0, 0.02) weights, residual
projections scaled by 1/sqrt(2 * layers), and — so that no leaf is
trivially zero or one in the check — biases N(0, 0.02) and LayerNorm
scales 1 + N(0, 0.02).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = (
    "ln_1.g", "ln_1.b", "attn.q.w", "attn.q.b", "attn.k.w", "attn.k.b",
    "attn.v.w", "attn.v.b", "attn.o.w", "attn.o.b", "ln_2.g", "ln_2.b",
    "mlp.fc.w", "mlp.fc.b", "mlp.proj.w", "mlp.proj.b")

#: reference leaf -> the zoo's parameter name (layer leaves take {i})
PROGRAM_NAMES = {
    "wte": "backbone.word_embed.weight",
    "wpe": "backbone.position_embed.weight",
    "ln_f.g": "backbone.final_ln.gamma",
    "ln_f.b": "backbone.final_ln.beta",
    "ln_1.g": "backbone.decoder.layer{i}.attn_ln.gamma",
    "ln_1.b": "backbone.decoder.layer{i}.attn_ln.beta",
    "attn.q.w": "backbone.decoder.layer{i}.attention.query_proj.weight",
    "attn.q.b": "backbone.decoder.layer{i}.attention.query_proj.bias",
    "attn.k.w": "backbone.decoder.layer{i}.attention.key_proj.weight",
    "attn.k.b": "backbone.decoder.layer{i}.attention.key_proj.bias",
    "attn.v.w": "backbone.decoder.layer{i}.attention.value_proj.weight",
    "attn.v.b": "backbone.decoder.layer{i}.attention.value_proj.bias",
    "attn.o.w": "backbone.decoder.layer{i}.attention.out_proj.weight",
    "attn.o.b": "backbone.decoder.layer{i}.attention.out_proj.bias",
    "ln_2.g": "backbone.decoder.layer{i}.ffn_ln.gamma",
    "ln_2.b": "backbone.decoder.layer{i}.ffn_ln.beta",
    "mlp.fc.w": "backbone.decoder.layer{i}.ffn.ffn_1.weight",
    "mlp.fc.b": "backbone.decoder.layer{i}.ffn.ffn_1.bias",
    "mlp.proj.w": "backbone.decoder.layer{i}.ffn.ffn_2.weight",
    "mlp.proj.b": "backbone.decoder.layer{i}.ffn.ffn_2.bias",
}


def leaf_shapes(cfg):
    """{reference leaf: shape}; layer leaves carry a leading n_layer."""
    e, f, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    per = {"ln_1.g": (e,), "ln_1.b": (e,), "ln_2.g": (e,), "ln_2.b": (e,),
           "attn.q.w": (e, e), "attn.k.w": (e, e), "attn.v.w": (e, e),
           "attn.o.w": (e, e), "attn.q.b": (e,), "attn.k.b": (e,),
           "attn.v.b": (e,), "attn.o.b": (e,), "mlp.fc.w": (f, e),
           "mlp.fc.b": (f,), "mlp.proj.w": (e, f), "mlp.proj.b": (e,)}
    out = {"wte": (cfg["vocab_size"], e), "wpe": (cfg["n_positions"], e),
           "ln_f.g": (e,), "ln_f.b": (e,)}
    out.update({n: (L,) + per[n] for n in LAYER_LEAVES})
    return out


def n_params(cfg):
    return sum(math.prod(s) for s in leaf_shapes(cfg).values())


def seed_key(seed):
    """A key from any whole number: seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def _make_leaf(key, index, name, shape, cfg, dtype):
    k = jax.random.fold_in(key, index)
    w = jax.random.normal(k, shape, jnp.float32) * 0.02
    if name in ("attn.o.w", "mlp.proj.w"):
        w = w / math.sqrt(2.0 * cfg["n_layer"])
    if name.endswith(".g"):
        w = w + 1.0
    return w.astype(dtype)


def make_weights(cfg, seed, dtype="float32", only=None):
    """Every leaf (or the leaves named in ``only``), on the default
    device, from one jitted call.  The same seed gives the same values
    whatever ``only`` selects."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)
    wanted = tuple(names if only is None else only)

    @jax.jit
    def make(key):
        return {n: _make_leaf(key, names.index(n), n, shapes[n], cfg,
                              jnp.dtype(dtype))
                for n in wanted}

    return make(seed_key(seed))


def program_leaves(weights):
    """Reference tree -> {zoo parameter name: leaf}, layer stacks split."""
    out = {}
    for n, a in weights.items():
        if n in LAYER_LEAVES:
            for i in range(a.shape[0]):
                out[PROGRAM_NAMES[n].format(i=i)] = a[i]
        else:
            out[PROGRAM_NAMES[n]] = a
    return out


def stack_program_tree(tree, n_layer):
    """{zoo parameter name: array} -> {reference leaf: array}, layer
    leaves stacked; the inverse of ``program_leaves`` for any per-leaf
    tree of the program's, on the host (the check's per-leaf norms)."""
    import numpy as onp
    out = {}
    for n, pname in PROGRAM_NAMES.items():
        if n in LAYER_LEAVES:
            out[n] = onp.stack([onp.asarray(tree[pname.format(i=i)])
                                for i in range(n_layer)])
        else:
            out[n] = onp.asarray(tree[pname])
    return out


def change_norms(cfg, seed, trainable):
    """{zoo parameter name: norm of (parameter now - parameter as the
    seed made it)}, in one jitted call that makes the seed's values
    again rather than keeping a copy of them."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def norms(key, tree):
        out = {}
        for n in names:
            w0 = _make_leaf(key, names.index(n), n, shapes[n], cfg,
                            jnp.float32)
            if n in LAYER_LEAVES:
                for i in range(cfg["n_layer"]):
                    pn = PROGRAM_NAMES[n].format(i=i)
                    out[pn] = jnp.sqrt(jnp.sum(jnp.square(tree[pn] - w0[i])))
            else:
                pn = PROGRAM_NAMES[n]
                out[pn] = jnp.sqrt(jnp.sum(jnp.square(tree[pn] - w0)))
        return out

    return norms(seed_key(seed), trainable)


def build_net(cfg, weights):
    """The zoo's GPT-2 at the file's sizes, holding ``weights`` (in
    their type).  Dropout 0, as a throughput run sets it."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM, GPTModel

    net = GPTForCausalLM(backbone=GPTModel(
        vocab_size=cfg["vocab_size"], units=cfg["n_embd"],
        hidden_size=cfg["n_inner"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], max_length=cfg["n_positions"],
        dropout=0.0, embed_dropout=0.0))
    leaves = jax.jit(program_leaves)(weights)
    dtype = str(next(iter(leaves.values())).dtype)
    if dtype != "float32":
        net.cast(dtype)
    params = net.collect_params()
    if set(params) != set(leaves):
        raise RuntimeError(
            "the zoo's parameter names no longer match "
            f"chipbench/families/gpt2.py: {sorted(set(params) ^ set(leaves))[:6]}")
    for name, p in params.items():
        p.set_data(mx.np.array(leaves[name]))
    return net


def loss_fn(logits, labels):
    """Mean token cross-entropy through the program's own fused op (what
    gluon's SoftmaxCrossEntropyLoss calls): float32 inside, whatever
    type the logits arrive in."""
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    return jnp.mean(sparse_softmax_xent(logits, labels))
