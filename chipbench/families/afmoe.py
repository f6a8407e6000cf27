"""The afmoe family (Trinity): seeded weights, and the zoo's net built
from a configuration file.

Two halves that must not mix.  ``leaf_shapes`` / ``make_weights`` are the
benchmark's own generator (pure JAX, nothing of the program): one jitted
call makes every leaf on the device from the seed, stacked over the
layers that have it (all / the leading dense ones / the expert ones), in
the type asked for.  The reference is handed these; the program is handed
the same values under its own parameter names (``program_leaves``).
``build_net`` and ``loss_fn`` are the only functions here that import
the program.

Initialisation: N(0, 0.02) weights, residual projections scaled by
1/sqrt(2 * layers), and — so that no leaf is trivially zero or one in the
check — norm scales 1 + N(0, 0.02) and the selection bias
(``moe.bias``, no gradient, never updated) N(0, 0.01).  The two
post-norms of a layer start at 0.03 x (1 + N(0, 0.02)).

Why 0.03 and 0.01: the cell is one chip's share of a deployed model, and
a deployed model of this family moves its selection bias until every
batch's loads are even; that is what ``rows_bound`` (twice the
expectation) and the cell's 512 rows an expert assume.  So the
generator's target is a load statistic, not a timing: the most loaded
expert within about 1.5 x the mean, the rows held within a tenth or so of
the expected 4096.  Two things in a seeded model miss it.  (1) The bias
itself.  Eight of 128 are selected at the 1.53-sigma tail of the scores,
where an offset of b sigma multiplies an expert's load by about
exp(1.95 b) (the normal's hazard there); the scores' sigma is 0.25 x
0.905 = 0.23 (sigmoid's slope x the logits' spread), so a bias of
N(0, 0.05) is 0.22 sigma and the largest of 128 such offsets (2.6 s)
triples a load, where N(0, 0.01) leaves 1.25 x, 1.4 with the sampling
noise of 512 rows an expert.  (2) The post-norms.  Attention with random
weights over uniform random tokens returns nearly the mean of its
values, one vector for every query, and a post-norm of scale g adds g of
it (in RMS) to a stream whose token-specific part is the embedding row,
RMS 0.02 x sqrt(2048) = 0.905: the router's scores then share an offset
per expert across all tokens.  The plain reference's forward at the
cell's sizes (``chipbench/dev/afmoe_loads.py``: one batch of seed
2100003201, on the CPU, counts only) gives, most loaded of the 128 over
the mean by expert layer, and the rows held:

    post-norm 1,    bias 0.01:  4.4 4.4 6.1 5.8   1864-5714 rows
    post-norm 0.1,  bias 0.05:  3.0 4.3 5.0 3.8   3953-5400
    post-norm 0.03, bias 0.05:  3.1 3.6 4.2 3.1   4038-5516
    post-norm 0.1,  bias 0.01:  1.5 2.0 2.0 2.2   3796-4683
    post-norm 0.03, bias 0.01:  1.4 1.6 1.5 1.5   4053-4645

so the bias is the larger part and reckons as above, a post-norm of 1
collapses the routing whatever the bias, and 0.03 is at the floor that
the bias and the sampling leave while 0.1 is not yet.  On the v5e
(PR 27) the settings were reached in the order 1 / 0.05, 0.1 / 0.05,
0.03 / 0.01, the second left when ``train_mfu`` followed the held rows
by 0.2 % a thousand rows; the table was made afterwards, and it, not the
spread, is what holds the values now (0.1 / 0.01 was never run there).

The counts the expert layers keep in the step's ``aux`` (``expert_load``,
``rows_over``) ride beside the change norms.  The driver hands
``change_norms`` the step's parameters and not the step, and is not a
``model_config`` PR's to edit, so the workaround lives here and not in the
program: ``build_net`` keeps a weak reference to the net it built,
``change_norms`` (called after the check's updates, while the step is
alive) asks the garbage collector which train step holds that net as its
``block`` and reads the counts from its ``aux``.  The reference's counts
for the same updates ride beside its change norms, and
``reference.leaf_gaps`` compares them as counts.  PERF.md section 7 names
the edit to ``drivers/train_steps.py`` that retires this.
"""
from __future__ import annotations

import gc
import math
import weakref

import jax
import jax.numpy as jnp

LAYER_LEAVES = (
    "ln_in.g", "ln_post_attn.g", "ln_pre_mlp.g", "ln_post_mlp.g",
    "attn.q.w", "attn.k.w", "attn.v.w", "attn.g.w", "attn.o.w",
    "attn.q_norm.g", "attn.k_norm.g")
DENSE_LEAVES = ("mlp.gate.w", "mlp.up.w", "mlp.down.w")
MOE_LEAVES = (
    "moe.router.w", "moe.bias", "moe.shared.gate.w", "moe.shared.up.w",
    "moe.shared.down.w", "moe.gate.w", "moe.up.w", "moe.down.w")
#: not trained: the program keeps it in ``aux``
BIAS = "moe.bias"
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"

_LAYER = "backbone.layer{i}."
#: reference leaf -> the zoo's parameter name (stacked leaves take {i})
PROGRAM_NAMES = {
    "wte": "backbone.word_embed.weight",
    "head.w": "lm_head.weight",
    "ln_f.g": "backbone.final_norm.gamma",
    "ln_in.g": _LAYER + "input_norm.gamma",
    "ln_post_attn.g": _LAYER + "post_attn_norm.gamma",
    "ln_pre_mlp.g": _LAYER + "pre_mlp_norm.gamma",
    "ln_post_mlp.g": _LAYER + "post_mlp_norm.gamma",
    "attn.q.w": _LAYER + "attention.query_proj.weight",
    "attn.k.w": _LAYER + "attention.key_proj.weight",
    "attn.v.w": _LAYER + "attention.value_proj.weight",
    "attn.g.w": _LAYER + "attention.gate_proj.weight",
    "attn.o.w": _LAYER + "attention.out_proj.weight",
    "attn.q_norm.g": _LAYER + "attention.q_norm.gamma",
    "attn.k_norm.g": _LAYER + "attention.k_norm.gamma",
    "mlp.gate.w": _LAYER + "mlp.gate_proj.weight",
    "mlp.up.w": _LAYER + "mlp.up_proj.weight",
    "mlp.down.w": _LAYER + "mlp.down_proj.weight",
    "moe.router.w": _LAYER + "mlp.router",
    "moe.bias": _LAYER + "mlp.expert_bias",
    "moe.shared.gate.w": _LAYER + "mlp.shared_gate",
    "moe.shared.up.w": _LAYER + "mlp.shared_up",
    "moe.shared.down.w": _LAYER + "mlp.shared_down",
    "moe.gate.w": _LAYER + "mlp.w_gate",
    "moe.up.w": _LAYER + "mlp.w_up",
    "moe.down.w": _LAYER + "mlp.w_down",
}
#: the counts the program's expert layers keep in ``aux``
PROGRAM_COUNTS = {LOAD: _LAYER + "mlp.expert_load",
                  ROWS_OVER: _LAYER + "mlp.rows_over"}


def layers_of(name, cfg):
    """The model layers a stacked leaf has an entry for, in order (None
    for a leaf that is not stacked)."""
    n_layer, n_dense = len(cfg["layer_types"]), cfg["num_dense_layers"]
    if name in LAYER_LEAVES:
        return list(range(n_layer))
    if name in DENSE_LEAVES:
        return list(range(n_dense))
    if name in MOE_LEAVES or name in PROGRAM_COUNTS:
        return list(range(n_dense, n_layer))
    return None


def leaf_shapes(cfg):
    """{reference leaf: shape}; stacked leaves carry a leading count of
    the layers that have them."""
    e, f, fm = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    d = cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    n, held = cfg["num_experts"], cfg["num_experts_held"]
    fs = fm * cfg["num_shared_experts"]
    per = {"ln_in.g": (e,), "ln_post_attn.g": (e,), "ln_pre_mlp.g": (e,),
           "ln_post_mlp.g": (e,), "attn.q.w": (hq, e), "attn.k.w": (hk, e),
           "attn.v.w": (hk, e), "attn.g.w": (hq, e), "attn.o.w": (e, hq),
           "attn.q_norm.g": (d,), "attn.k_norm.g": (d,),
           "mlp.gate.w": (f, e), "mlp.up.w": (f, e), "mlp.down.w": (e, f),
           "moe.router.w": (n, e), "moe.bias": (n,),
           "moe.shared.gate.w": (fs, e), "moe.shared.up.w": (fs, e),
           "moe.shared.down.w": (e, fs), "moe.gate.w": (held, e, fm),
           "moe.up.w": (held, e, fm), "moe.down.w": (held, fm, e)}
    out = {"wte": (cfg["vocab_size"], e), "head.w": (cfg["vocab_size"], e),
           "ln_f.g": (e,)}
    out.update({name: (len(layers_of(name, cfg)),) + shape
                for name, shape in per.items()})
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
    w = jax.random.normal(k, shape, jnp.float32) * (
        0.01 if name == BIAS else 0.02)
    if name in ("attn.o.w", "mlp.down.w", "moe.down.w",
                "moe.shared.down.w"):
        w = w / math.sqrt(2.0 * len(cfg["layer_types"]))
    if name.endswith(".g"):
        w = w + 1.0
    if name in ("ln_post_attn.g", "ln_post_mlp.g"):
        w = 0.03 * w
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


def _program_names(cfg):
    """(reference leaf, index in its stack or None, zoo name) of every
    leaf of the program that the generator makes."""
    for n, pname in PROGRAM_NAMES.items():
        layers = layers_of(n, cfg)
        if layers is None:
            yield n, None, pname
        else:
            for k, i in enumerate(layers):
                yield n, k, pname.format(i=i)


def program_leaves(weights, cfg):
    """Reference tree -> {zoo parameter name: leaf}, stacks split."""
    return {pname: weights[n] if k is None else weights[n][k]
            for n, k, pname in _program_names(cfg) if n in weights}


def stack_program_tree(tree, n_layer):
    """{zoo parameter name: array} -> {reference leaf: array}, stacked
    leaves stacked again; the inverse of ``program_leaves`` for any
    per-leaf tree of the program's, on the host (the check's per-leaf
    norms).  The leaves the tree lacks are left out (the first gradient
    has none for the selection bias), and the expert layers' counts are
    taken along where the tree has them."""
    import numpy as onp
    out = {}
    for n, pname in {**PROGRAM_NAMES, **PROGRAM_COUNTS}.items():
        if "{i}" not in pname:
            if pname in tree:
                out[n] = onp.asarray(tree[pname])
            continue
        rows = [onp.asarray(tree[pname.format(i=i)])
                for i in range(n_layer) if pname.format(i=i) in tree]
        if rows:
            out[n] = onp.stack(rows)
    return out


#: the net ``build_net`` last built (a weak reference: the driver frees
#: the program before the reference runs)
_net = None

#: {``moe.load``: (expert layers, published experts), ``moe.rows_over``:
#: (expert layers,)} as ``change_norms`` last read them — after the
#: check's updates: the driver frees the step before a reader runs, so
#: ``moe_load_max_over_mean.train`` has nothing later to read
last_counts = {}


def step_counts():
    """{zoo name: array} of the counts that the expert layers of the
    train step round the net ``build_net`` last built keep in its
    ``aux``; empty where that net is gone or no step holds it."""
    net = _net() if _net is not None else None
    for holder in gc.get_referrers(net) if net is not None else ():
        # a step's attributes: its ``__dict__``, or the step itself
        # where Python keeps them inline
        attrs = holder if isinstance(holder, dict) \
            else getattr(holder, "__dict__", {})
        aux = attrs.get("aux")
        if attrs.get("block") is net and isinstance(aux, dict):
            return {n: a for n, a in aux.items()
                    if n.endswith((".expert_load", ".rows_over"))}
    return {}


def change_norms(cfg, seed, trainable):
    """{zoo parameter name: norm of (parameter now - parameter as the
    seed made it)}, in one jitted call that makes the seed's values
    again rather than keeping a copy of them; and, beside them, the
    counts of the step's expert layers (``step_counts``) as they stand
    now."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def norms(key, tree):
        out = {}
        made = {}
        for n, k, pname in _program_names(cfg):
            if pname not in tree:
                continue
            if n not in made:
                made[n] = _make_leaf(key, names.index(n), n, shapes[n], cfg,
                                     jnp.float32)
            w0 = made[n] if k is None else made[n][k]
            out[pname] = jnp.sqrt(jnp.sum(jnp.square(tree[pname] - w0)))
        return out

    out = norms(seed_key(seed), trainable)
    counts = jax.device_get(step_counts())
    out.update({n: a.reshape(-1) if n.endswith(".expert_load") else a[0]
                for n, a in counts.items()})
    last_counts.clear()
    last_counts.update(stack_program_tree(
        {n: out[n] for n in counts}, len(cfg["layer_types"])))
    return out


def build_net(cfg, weights):
    """The zoo's afmoe decoder at the file's sizes, holding ``weights``
    (in their type): this chip's share of the experts."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.afmoe import AfmoeForCausalLM

    lo = cfg["experts_held_from"]
    net = AfmoeForCausalLM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        hidden_size=cfg["intermediate_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        shared_hidden_size=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        held_experts=(lo, lo + cfg["num_experts_held"]),
        rows_bound=cfg["rows_bound"], sliding_window=cfg["sliding_window"],
        route_scale=cfg["route_scale"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"])
    leaves = jax.jit(lambda w: program_leaves(w, cfg))(weights)
    dtype = str(next(iter(leaves.values())).dtype)
    if dtype != "float32":
        net.cast(dtype)
    params = net.collect_params()
    counts = {n for n in params
              if n.endswith((".expert_load", ".rows_over"))}
    if set(params) - counts != set(leaves):
        raise RuntimeError(
            "the zoo's parameter names no longer match "
            "chipbench/families/afmoe.py: "
            f"{sorted((set(params) - counts) ^ set(leaves))[:6]}")
    for name, leaf in leaves.items():
        params[name].set_data(mx.np.array(leaf))
    net.initialize()        # the counts: zeros
    global _net
    _net = weakref.ref(net)
    return net


def loss_fn(logits, labels):
    """Mean token cross-entropy through the program's own fused op (what
    gluon's SoftmaxCrossEntropyLoss calls): float32 inside, whatever
    type the logits arrive in."""
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    return jnp.mean(sparse_softmax_xent(logits, labels))
