"""The keye family (Keye-VL-2.0's language model): seeded weights, and the
zoo's net built from a configuration file.

Two halves that must not mix.  ``leaf_shapes`` / ``make_weights`` are the
benchmark's own generator (pure JAX, nothing of the program): one jitted
call makes every leaf on the device from the seed, stacked over the
layers, in the type asked for.  The reference is handed these; the
program is handed the same values under its own parameter names
(``program_leaves``).  ``build_net`` and ``loss_fn`` are the only
functions here that import the program.

Initialisation: N(0, 0.02) weights, attention's output and the experts'
down projections scaled by 1/sqrt(2 * layers), norm scales 1 + N(0, 0.02)
and the one norm bias (the indexer's LayerNorm) N(0, 0.02), so that no
leaf is trivially zero or one in the check — and the **embedding rows at
``EMBED_SCALE`` x that, N(0, 0.02 x sqrt(hidden))**, which a load
statistic and the check's own readings set, as ``families/afmoe.py``'s
post-norms were set.

Why: the cell is one chip's share of a deployed model, and a deployed
model's router is trained until every batch's loads are even; that is
what ``rows_bound`` (twice the expectation) and the cell's 512 rows an
expert assume.  A seeded router is not trained.  Softmax routing selects
the 8 largest logits of 128, which no scale of the router changes; what
unbalances it is the part of the router's input that all tokens share,
and what a token owns is its embedding row.  This layout does not scale
its embeddings (Trinity's multiplies them by sqrt(hidden)), so under
N(0, 0.02) a row is an element RMS of 0.02 while one expert's output is
~0.08 x its weight and attention returns nearly the mean of its values,
one vector for every query: the stream after a layer is mostly what the
layers added.  Two things follow on the chip (PERF.md section 6, PR 32,
the first chip round): the most loaded held expert took 3.7 x the mean
and the last layer left 3,744–9,511 assignments past its bound; and the
stream has no exact part for bf16's rounding to be small beside, so the
routing and the selection of the program and of the float32 reference
part ways layer by layer (a fifth to a third of the last layer's
assignments) — a random network amplifying rounding, which a trained one
does not do.  With rows at 0.02 x sqrt(hidden) (element RMS 0.905, what
Trinity's stream holds after its scaling) the stream stays the token's
own, as a trained model's is.  The target: the most loaded held expert
within about 1.5 x the mean, ``rows_over`` 0.
``chipbench/dev/keye_loads.py`` prints the statistic from the plain
reference's forward at the cell's sizes (counts only, any back-end); the
readings by setting are in PERF.md section 6, PR 32.

The counts the program keeps in the step's ``aux`` — the expert layers'
``expert_load`` / ``rows_over`` and the indexers' ``selected_pairs`` /
``select_grid`` — ride beside the change norms, found as
``families/afmoe.py`` finds them (the driver hands ``change_norms`` the
step's parameters and not the step): ``build_net`` keeps a weak reference
to its net, ``change_norms`` asks the garbage collector which train step
holds that net as its ``block`` and reads its ``aux``.
"""
from __future__ import annotations

import gc
import importlib.util
import math
import weakref

import jax
import jax.numpy as jnp

# a program without the zoo's keye decoder cannot run this family: say so
# before a weight is made (located, not imported: ``build_net`` imports it)
if importlib.util.find_spec("mxnet_tpu.gluon.model_zoo.keye") is None:
    raise SystemExit("chipbench: this program has no "
                     "mxnet_tpu.gluon.model_zoo.keye: it cannot run the "
                     "keye family")

LAYER_LEAVES = (
    "ln_in.g", "ln_post.g", "attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w",
    "attn.q_norm.g", "attn.k_norm.g", "idx.q.w", "idx.k.w", "idx.w.w",
    "idx.k_norm.g", "idx.k_norm.b", "moe.router.w", "moe.gate.w",
    "moe.up.w", "moe.down.w")
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"
PAIRS, GRID = "dsa.pairs", "dsa.grid"

#: embedding rows, on top of N(0, 0.02), in units of sqrt(hidden) (see the
#: module's docstring)
EMBED_SCALE = 1.0

_LAYER = "backbone.layer{i}."
_ATTN, _IDX = _LAYER + "attention.", _LAYER + "attention.indexer."
#: reference leaf -> the zoo's parameter name (stacked leaves take {i})
PROGRAM_NAMES = {
    "wte": "backbone.word_embed.weight",
    "head.w": "lm_head.weight",
    "ln_f.g": "backbone.final_norm.gamma",
    "ln_in.g": _LAYER + "input_norm.gamma",
    "ln_post.g": _LAYER + "post_attn_norm.gamma",
    "attn.q.w": _ATTN + "query_proj.weight",
    "attn.k.w": _ATTN + "key_proj.weight",
    "attn.v.w": _ATTN + "value_proj.weight",
    "attn.o.w": _ATTN + "out_proj.weight",
    "attn.q_norm.g": _ATTN + "q_norm.gamma",
    "attn.k_norm.g": _ATTN + "k_norm.gamma",
    "idx.q.w": _IDX + "query_proj.weight",
    "idx.k.w": _IDX + "key_proj.weight",
    "idx.w.w": _IDX + "weight_proj.weight",
    "idx.k_norm.g": _IDX + "key_norm.gamma",
    "idx.k_norm.b": _IDX + "key_norm.beta",
    "moe.router.w": _LAYER + "mlp.router",
    "moe.gate.w": _LAYER + "mlp.w_gate",
    "moe.up.w": _LAYER + "mlp.w_up",
    "moe.down.w": _LAYER + "mlp.w_down",
}
#: the counts the program keeps in ``aux``
PROGRAM_COUNTS = {LOAD: _LAYER + "mlp.expert_load",
                  ROWS_OVER: _LAYER + "mlp.rows_over",
                  PAIRS: _IDX + "selected_pairs",
                  GRID: _IDX + "select_grid"}
_COUNT_ENDINGS = tuple(n.rsplit(".", 1)[1] for n in PROGRAM_COUNTS.values())
#: aux of the program's that the generator does not make: the counts and
#: the expert layers' selection bias (zeros: this family has none)
_NOT_MADE = _COUNT_ENDINGS + ("expert_bias",)


def leaf_shapes(cfg):
    """{reference leaf: shape}; stacked leaves carry a leading count of
    the layers."""
    e, fm, d = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                cfg["head_dim"])
    hq, hk = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    n, held = cfg["num_experts"], cfg["num_experts_held"]
    per = {"ln_in.g": (e,), "ln_post.g": (e,), "attn.q.w": (hq, e),
           "attn.k.w": (hk, e), "attn.v.w": (hk, e), "attn.o.w": (e, hq),
           "attn.q_norm.g": (d,), "attn.k_norm.g": (d,),
           "idx.q.w": (hi * di, e),
           "idx.k.w": (sa["indexer_num_kv_heads"] * di, e),
           "idx.w.w": (hi, e), "idx.k_norm.g": (di,), "idx.k_norm.b": (di,),
           "moe.router.w": (n, e), "moe.gate.w": (held, e, fm),
           "moe.up.w": (held, e, fm), "moe.down.w": (held, fm, e)}
    out = {"wte": (cfg["vocab_size"], e), "head.w": (cfg["vocab_size"], e),
           "ln_f.g": (e,)}
    out.update({name: (cfg["num_hidden_layers"],) + shape
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
    w = jax.random.normal(k, shape, jnp.float32) * 0.02
    if name in ("attn.o.w", "moe.down.w"):
        w = w / math.sqrt(2.0 * cfg["num_hidden_layers"])
    if name == "wte":
        w = w * (EMBED_SCALE * math.sqrt(cfg["hidden_size"]))
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


def _program_names(cfg):
    """(reference leaf, index in its stack or None, zoo name) of every
    leaf of the program that the generator makes."""
    for n, pname in PROGRAM_NAMES.items():
        if n in LAYER_LEAVES:
            for i in range(cfg["num_hidden_layers"]):
                yield n, i, pname.format(i=i)
        else:
            yield n, None, pname


def program_leaves(weights, cfg):
    """Reference tree -> {zoo parameter name: leaf}, stacks split."""
    return {pname: weights[n] if k is None else weights[n][k]
            for n, k, pname in _program_names(cfg) if n in weights}


def stack_program_tree(tree, n_layer):
    """{zoo parameter name: array} -> {reference leaf: array}, stacked
    leaves stacked again; the inverse of ``program_leaves`` for any
    per-leaf tree of the program's, on the host (the check's per-leaf
    norms), the counts taken along where the tree has them."""
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

#: {``moe.load``: (layers, published experts), ``moe.rows_over``:
#: (layers,), ``dsa.pairs``: (layers,), ``dsa.grid``: (layers, 16, 16)} as
#: ``change_norms`` last read them — after the check's updates: the
#: driver frees the step before a reader runs, so
#: ``moe_load_max_over_mean.train`` and ``dsa_selected_share.train`` have
#: nothing later to read
last_counts = {}


def step_counts():
    """{zoo name: array} of the counts that the train step round the net
    ``build_net`` last built keeps in its ``aux``; empty where that net
    is gone or no step holds it."""
    net = _net() if _net is not None else None
    for holder in gc.get_referrers(net) if net is not None else ():
        # a step's attributes: its ``__dict__``, or the step itself
        # where Python keeps them inline
        attrs = holder if isinstance(holder, dict) \
            else getattr(holder, "__dict__", {})
        aux = attrs.get("aux")
        if attrs.get("block") is net and isinstance(aux, dict):
            return {n: a for n, a in aux.items()
                    if n.endswith(_COUNT_ENDINGS)}
    return {}


def change_norms(cfg, seed, trainable):
    """{zoo parameter name: norm of (parameter now - parameter as the
    seed made it)}, in one jitted call that makes the seed's values
    again rather than keeping a copy of them; and, beside them, the
    step's counts (``step_counts``) as they stand now."""
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
    # (1,) counts as scalars, so that a stack of them is (layers,)
    out.update({n: a[0] if a.shape == (1,) else a
                for n, a in counts.items()})
    last_counts.clear()
    last_counts.update(stack_program_tree(
        {n: out[n] for n in counts}, cfg["num_hidden_layers"]))
    return out


def build_net(cfg, weights):
    """The zoo's keye decoder at the file's sizes, holding ``weights``
    (in their type): this chip's share of the experts."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.keye import KeyeForCausalLM

    lo = cfg["experts_held_from"]
    sa = cfg["sa_config"]
    net = KeyeForCausalLM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        held_experts=(lo, lo + cfg["num_experts_held"]),
        rows_bound=cfg["rows_bound"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], topk=sa["topk"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"])
    leaves = jax.jit(lambda w: program_leaves(w, cfg))(weights)
    dtype = str(next(iter(leaves.values())).dtype)
    if dtype != "float32":
        net.cast(dtype)
    params = net.collect_params()
    made = {n for n in params if not n.endswith(_NOT_MADE)}
    if made != set(leaves):
        raise RuntimeError(
            "the zoo's parameter names no longer match "
            f"chipbench/families/keye.py: {sorted(made ^ set(leaves))[:6]}")
    for name, leaf in leaves.items():
        params[name].set_data(mx.np.array(leaf))
    net.initialize()        # the counts and the selection bias: zeros
    global _net
    _net = weakref.ref(net)
    return net


def loss_fn(out, labels):
    """Mean token cross-entropy through the program's own fused op (float32
    inside, whatever type the logits arrive in) plus the indexers' loss
    the block returns beside its logits."""
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    logits, index_loss = out
    return jnp.mean(sparse_softmax_xent(logits, labels)) + index_loss
