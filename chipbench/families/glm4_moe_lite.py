"""The glm4_moe_lite family (GLM-4.7-Flash: latent attention,
sigmoid-routed experts with a shared one, a multi-token-prediction
depth): seeded weights, and the zoo's net built from a configuration
file.

Two halves that must not mix.  ``leaf_shapes`` / ``make_weights`` are the
benchmark's own generator (pure JAX, nothing of the program): one jitted
call makes every leaf on the device from the seed, stacked over the
layers that have it (all / the leading dense ones / the expert ones; the
second prediction depth's own leaves, where the configuration has one,
are single), in the type asked for.  The reference is handed these; the
program is handed the same values under its own parameter names
(``program_leaves``).  ``build_net`` and ``loss_fn`` are the only
functions here that import the program.

Initialisation (the configuration's ``assumed`` repeats it), as
``families/nemotron_h.py`` set it for a layout that, like this one, has
no post-norm and does not scale its embedding, and for the reasons
measured there: N(0, 0.02) matrices; the residual projections
(attention's output, every down projection) / sqrt(**published** layers,
47): the kept layers are the first five of a 47-layer model and are
scaled as that model's are; norm scales 1 + N(0, 0.02); the selection
bias (``moe.bias``, ``e_score_correction_bias``: no gradient, never
updated) N(0, 0.01) (``families/afmoe.py``: the largest of many offsets
of 0.05 sigma triples an expert's load); **embedding rows
N(0, 0.02 x 4 sqrt(hidden))** (``EMBED_SCALE``), so that the router's
input stays the token's own and the routing is even, as a trained
model's is: attention with random weights returns nearly the mean of its
values, one vector for every query, and Adam's first steps add more of
it whatever the gradient's size.  ``chipbench/dev/glm_loads.py`` prints
the load statistic this is held by from the plain reference's forward;
PERF.md section 6 (PR 40) has the readings.

The counts the expert layers keep in the step's ``aux``
(``expert_load``, ``rows_over``) ride beside the change norms as in
``families/afmoe.py``, by the same workaround (the driver hands
``change_norms`` the step's parameters and not the step): ``build_net``
keeps a weak reference to its net, ``change_norms`` asks the garbage
collector which train step holds that net as its ``block``.  The second
depth's layer keeps such counts too; they are not compared (its last
position is a pad the reference does not have).
"""
from __future__ import annotations

import gc
import importlib.util
import math
import weakref

import jax
import jax.numpy as jnp

# a program without the zoo's glm4_moe_lite decoder cannot run this
# family: say so before a weight is made (located, not imported)
if importlib.util.find_spec(
        "mxnet_tpu.gluon.model_zoo.glm4_moe_lite") is None:
    raise SystemExit("chipbench: this program has no "
                     "mxnet_tpu.gluon.model_zoo.glm4_moe_lite: it cannot "
                     "run the glm4_moe_lite family")

#: embedding rows, on top of N(0, 0.02), in units of sqrt(hidden) (see the
#: module's docstring)
EMBED_SCALE = 4.0

LAYER_LEAVES = (
    "ln_in.g", "ln_post_attn.g", "attn.q_a.w", "attn.q_a_norm.g",
    "attn.q_b.w", "attn.kv_a.w", "attn.kv_a_norm.g", "attn.kv_b.w",
    "attn.o.w")
DENSE_LEAVES = ("mlp.gate.w", "mlp.up.w", "mlp.down.w")
MOE_LEAVES = (
    "moe.router.w", "moe.bias", "moe.shared.gate.w", "moe.shared.up.w",
    "moe.shared.down.w", "moe.gate.w", "moe.up.w", "moe.down.w")
#: not trained: the program keeps it in ``aux``
BIAS = "moe.bias"
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"
#: the second prediction depth: one expert layer's leaves under ``mtp.``
#: and what joins it to the model (``mtp.enorm.g`` ... below)
MTP = "mtp."

_LAYER = "backbone.layer{i}."
_LAYER_NAMES = {
    "ln_in.g": "input_norm.gamma",
    "ln_post_attn.g": "post_attn_norm.gamma",
    "attn.q_a.w": "attention.q_a_proj.weight",
    "attn.q_a_norm.g": "attention.q_a_norm.gamma",
    "attn.q_b.w": "attention.q_b_proj.weight",
    "attn.kv_a.w": "attention.kv_a_proj.weight",
    "attn.kv_a_norm.g": "attention.kv_a_norm.gamma",
    "attn.kv_b.w": "attention.kv_b_proj.weight",
    "attn.o.w": "attention.out_proj.weight",
    "mlp.gate.w": "mlp.gate_proj.weight",
    "mlp.up.w": "mlp.up_proj.weight",
    "mlp.down.w": "mlp.down_proj.weight",
    "moe.router.w": "mlp.router",
    "moe.bias": "mlp.expert_bias",
    "moe.shared.gate.w": "mlp.shared_gate",
    "moe.shared.up.w": "mlp.shared_up",
    "moe.shared.down.w": "mlp.shared_down",
    "moe.gate.w": "mlp.w_gate",
    "moe.up.w": "mlp.w_up",
    "moe.down.w": "mlp.w_down",
}
#: reference leaf -> the zoo's parameter name (stacked leaves take {i})
PROGRAM_NAMES = {
    "wte": "backbone.word_embed.weight",
    "head.w": "lm_head.weight",
    "ln_f.g": "backbone.final_norm.gamma",
    **{n: _LAYER + p for n, p in _LAYER_NAMES.items()},
    "mtp.enorm.g": "mtp.embed_norm.gamma",
    "mtp.hnorm.g": "mtp.hidden_norm.gamma",
    "mtp.eh.w": "mtp.eh_proj.weight",
    "mtp.ln_f.g": "mtp.final_norm.gamma",
    **{MTP + n: "mtp.layer." + p for n, p in _LAYER_NAMES.items()
       if n not in DENSE_LEAVES},
}
#: the counts the program's expert layers keep in ``aux``
PROGRAM_COUNTS = {LOAD: _LAYER + "mlp.expert_load",
                  ROWS_OVER: _LAYER + "mlp.rows_over"}


def layers_of(name, cfg):
    """The model layers a stacked leaf has an entry for, in order (None
    for a leaf that is not stacked)."""
    n_layer, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
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
    heads, nope, rope, dv = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, held = cfg["n_routed_experts"], cfg["num_experts_held"]
    fs = fm * cfg["n_shared_experts"]
    per = {"ln_in.g": (e,), "ln_post_attn.g": (e,),
           "attn.q_a.w": (rq, e), "attn.q_a_norm.g": (rq,),
           "attn.q_b.w": (heads * (nope + rope), rq),
           "attn.kv_a.w": (rkv + rope, e), "attn.kv_a_norm.g": (rkv,),
           "attn.kv_b.w": (heads * (nope + dv), rkv),
           "attn.o.w": (e, heads * dv),
           "mlp.gate.w": (f, e), "mlp.up.w": (f, e), "mlp.down.w": (e, f),
           "moe.router.w": (n, e), "moe.bias": (n,),
           "moe.shared.gate.w": (fs, e), "moe.shared.up.w": (fs, e),
           "moe.shared.down.w": (e, fs), "moe.gate.w": (held, e, fm),
           "moe.up.w": (held, e, fm), "moe.down.w": (held, fm, e)}
    out = {"wte": (cfg["vocab_size"], e), "head.w": (cfg["vocab_size"], e),
           "ln_f.g": (e,)}
    out.update({name: (len(layers_of(name, cfg)),) + shape
                for name, shape in per.items()})
    if cfg["num_nextn_predict_layers"]:
        out.update({"mtp.enorm.g": (e,), "mtp.hnorm.g": (e,),
                    "mtp.eh.w": (e, 2 * e), "mtp.ln_f.g": (e,)})
        out.update({MTP + name: per[name]
                    for name in LAYER_LEAVES + MOE_LEAVES})
    return out


def n_params(cfg):
    """The trained parameters (the selection biases are none)."""
    return sum(math.prod(s) for n, s in leaf_shapes(cfg).items()
               if not n.endswith(BIAS))


def seed_key(seed):
    """A key from any whole number: seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def _make_leaf(key, index, name, shape, cfg, dtype):
    k = jax.random.fold_in(key, index)
    w = jax.random.normal(k, shape, jnp.float32) * (
        0.01 if name.endswith(BIAS) else 0.02)
    if name == "wte":
        w = w * EMBED_SCALE * math.sqrt(cfg["hidden_size"])
    if name.endswith(("attn.o.w", "mlp.down.w", "moe.down.w",
                      "moe.shared.down.w")):
        w = w / math.sqrt(cfg["published"]["num_hidden_layers"])
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
    shapes = leaf_shapes(cfg)
    for n, pname in PROGRAM_NAMES.items():
        if n not in shapes:
            continue            # the second depth, where there is none
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
        {n: out[n] for n in counts}, cfg["num_hidden_layers"]))
    return out


def build_net(cfg, weights):
    """The zoo's glm4_moe_lite decoder at the file's sizes, holding
    ``weights`` (in their type): this chip's share of the experts."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.glm4_moe_lite import \
        Glm4MoeLiteForCausalLM

    lo = cfg["experts_held_from"]
    net = Glm4MoeLiteForCausalLM(
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], hidden_size=cfg["intermediate_size"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        shared_hidden_size=cfg["moe_intermediate_size"]
        * cfg["n_shared_experts"],
        held_experts=(lo, lo + cfg["num_experts_held"]),
        rows_bound=cfg["rows_bound"],
        route_scale=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"])
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
            "chipbench/families/glm4_moe_lite.py: "
            f"{sorted((set(params) - counts) ^ set(leaves))[:6]}")
    for name, leaf in leaves.items():
        params[name].set_data(mx.np.array(leaf))
    net.initialize()        # the counts: zeros
    global _net
    _net = weakref.ref(net)
    return net


def loss_fn(out, labels):
    """Mean token cross-entropy through the program's own fused op
    (float32 inside, whatever type the logits arrive in); where the net
    trains its second prediction depth, plus ``mtp_loss_weight`` (0.3,
    ``assumed``) times that depth's: the zoo's ``next_token_loss``."""
    from mxnet_tpu.gluon.model_zoo.glm4_moe_lite import next_token_loss
    return next_token_loss(out, labels)
