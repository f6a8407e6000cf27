"""The ouro family (Ouro-2.6B: a stack whose layers run four times on
shared weights, an exit gate, a loss over every exit): seeded weights,
and the zoo's net built from a configuration file.

Two halves that must not mix.  ``leaf_shapes`` / ``make_weights`` are the
benchmark's own generator (pure JAX, nothing of the program): one jitted
call makes every leaf on the device from the seed, the layers' leaves
stacked over the layers held, in the type asked for.  The reference is
handed these; the program is handed the same values under its own
parameter names (``program_leaves``).  ``build_net`` and ``loss_fn`` are
the only functions here that import the program.

Initialisation (the configuration's ``assumed`` repeats it): N(0, 0.02)
matrices, the gate's row and bias among them; the residual projections
(attention's output, the down projection) / sqrt(2 x **published**
layers, 48): the kept layers are the first four of that model; norm
scales 1 + N(0, 0.02), the two post-norms of a layer 0.1 x that;
**embedding rows N(0, 0.02 x sqrt(hidden))**, RMS 0.9.  Why the last
two: pass 1 reads the embedding rows and passes 2 to 4 read the final
norm's output, RMS 1, through the same weights, so the rows have to be of
that size for the shared layers to see one scale of input, as a trained
model's do; and attention with random weights over uniform random tokens
returns nearly the mean of its values, one vector for every query, of
which a post-norm of scale g adds g (in RMS) to the stream — sixteen
applications add 32 such branches, and at g = 1 the four exits' states
would be one common vector and the gate would have nothing to tell apart
(``families/afmoe.py`` measured the same collapse on a router).

The mean exit distribution the gate keeps in the step's ``aux``
(``exit.pdf``) rides beside the change norms as the expert counts do in
``families/afmoe.py``, by the same workaround (the driver hands
``change_norms`` the step's parameters and not the step): ``build_net``
keeps a weak reference to its net, ``change_norms`` asks the garbage
collector which train step holds that net as its ``block``.
"""
from __future__ import annotations

import gc
import importlib.util
import math
import weakref

import jax
import jax.numpy as jnp

# a program without the zoo's looped decoder cannot run this family: say
# so before a weight is made (located, not imported)
if importlib.util.find_spec("mxnet_tpu.gluon.model_zoo.ouro") is None:
    raise SystemExit("chipbench: this program has no "
                     "mxnet_tpu.gluon.model_zoo.ouro: it cannot run the "
                     "ouro family")

#: a layer's two post-norms, on top of 1 + N(0, 0.02) (the module's
#: docstring)
POST_NORM_SCALE = 0.1

LAYER_LEAVES = (
    "ln_in.g", "ln_post_attn.g", "ln_pre_mlp.g", "ln_post_mlp.g",
    "attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w",
    "mlp.gate.w", "mlp.up.w", "mlp.down.w")
#: not trained: the program keeps it in ``aux``
PDF = "exit.pdf"

_LAYER = "backbone.layer{i}."
#: reference leaf -> the zoo's parameter name (stacked leaves take {i})
PROGRAM_NAMES = {
    "wte": "backbone.word_embed.weight",
    "head.w": "lm_head.weight",
    "ln_f.g": "backbone.final_norm.gamma",
    "gate.w": "exit.proj.weight",
    "gate.b": "exit.proj.bias",
    "ln_in.g": _LAYER + "input_norm.gamma",
    "ln_post_attn.g": _LAYER + "post_attn_norm.gamma",
    "ln_pre_mlp.g": _LAYER + "pre_mlp_norm.gamma",
    "ln_post_mlp.g": _LAYER + "post_mlp_norm.gamma",
    "attn.q.w": _LAYER + "attention.query_proj.weight",
    "attn.k.w": _LAYER + "attention.key_proj.weight",
    "attn.v.w": _LAYER + "attention.value_proj.weight",
    "attn.o.w": _LAYER + "attention.out_proj.weight",
    "mlp.gate.w": _LAYER + "mlp.gate_proj.weight",
    "mlp.up.w": _LAYER + "mlp.up_proj.weight",
    "mlp.down.w": _LAYER + "mlp.down_proj.weight",
}
#: what the program's exit gate keeps in ``aux``
PROGRAM_COUNTS = {PDF: "exit.pdf"}


def leaf_shapes(cfg):
    """{reference leaf: shape}; a layer's leaves carry a leading count of
    the layers held."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    per = {"ln_in.g": (e,), "ln_post_attn.g": (e,), "ln_pre_mlp.g": (e,),
           "ln_post_mlp.g": (e,), "attn.q.w": (hq, e), "attn.k.w": (hk, e),
           "attn.v.w": (hk, e), "attn.o.w": (e, hq), "mlp.gate.w": (f, e),
           "mlp.up.w": (f, e), "mlp.down.w": (e, f)}
    out = {"wte": (cfg["vocab_size"], e), "head.w": (cfg["vocab_size"], e),
           "ln_f.g": (e,), "gate.w": (1, e), "gate.b": (1,)}
    out.update({n: (cfg["num_hidden_layers"],) + s for n, s in per.items()})
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
    if name == "wte":
        w = w * math.sqrt(cfg["hidden_size"])
    if name in ("attn.o.w", "mlp.down.w"):
        w = w / math.sqrt(2.0 * cfg["published"]["num_hidden_layers"])
    if name.endswith(".g"):
        w = w + 1.0
    if name in ("ln_post_attn.g", "ln_post_mlp.g"):
        w = POST_NORM_SCALE * w
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
    """{zoo parameter name: array} -> {reference leaf: array}, a layer's
    leaves stacked again; the inverse of ``program_leaves`` for any
    per-leaf tree of the program's, on the host (the check's per-leaf
    norms).  The exit distribution is taken along where the tree has
    it."""
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
#: the program before the reference runs) and the configuration's
#: ``entropy_beta`` for ``loss_fn``
_net = None
_beta = None

#: the mean exit distribution (T,) as ``change_norms`` last read it —
#: after the check's updates: the driver frees the step before a reader
#: runs, so ``exit_mean_step.train`` has nothing later to read
last_pdf = []


def step_pdf():
    """The ``exit.pdf`` that the train step round the net ``build_net``
    last built keeps in its ``aux``; None where that net is gone or no
    step holds it."""
    net = _net() if _net is not None else None
    for holder in gc.get_referrers(net) if net is not None else ():
        # a step's attributes: its ``__dict__``, or the step itself
        # where Python keeps them inline
        attrs = holder if isinstance(holder, dict) \
            else getattr(holder, "__dict__", {})
        aux = attrs.get("aux")
        if attrs.get("block") is net and isinstance(aux, dict):
            return aux.get(PROGRAM_COUNTS[PDF])
    return None


def change_norms(cfg, seed, trainable):
    """{zoo parameter name: norm of (parameter now - parameter as the
    seed made it)}, in one jitted call that makes the seed's values
    again rather than keeping a copy of them; and, beside them, the
    step's mean exit distribution (``step_pdf``) as it stands now."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def norms(key, tree):
        out, made = {}, {}
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
    pdf = step_pdf()
    last_pdf.clear()
    if pdf is not None:
        out[PROGRAM_COUNTS[PDF]] = jax.device_get(pdf)
        last_pdf.extend(float(p) for p in out[PROGRAM_COUNTS[PDF]])
    return out


def build_net(cfg, weights):
    """The zoo's looped decoder at the file's sizes, holding ``weights``
    (in their type); each layer application a recomputation boundary
    with the file's ``layer_remat`` policy where it names one."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.ouro import OuroForCausalLM

    net = OuroForCausalLM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        hidden_size=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"])
    leaves = jax.jit(lambda w: program_leaves(w, cfg))(weights)
    dtype = str(next(iter(leaves.values())).dtype)
    if dtype != "float32":
        net.cast(dtype)
    params = net.collect_params()
    counts = set(PROGRAM_COUNTS.values())
    if set(params) - counts != set(leaves):
        raise RuntimeError(
            "the zoo's parameter names no longer match "
            "chipbench/families/ouro.py: "
            f"{sorted((set(params) - counts) ^ set(leaves))[:6]}")
    for name, leaf in leaves.items():
        params[name].set_data(mx.np.array(leaf))
    net.initialize()        # the exit distribution: zeros
    if cfg["layer_remat"] is not None:
        for layer in net.backbone.layers:
            layer.hybridize(remat=cfg["layer_remat"])
    global _net, _beta
    _net, _beta = weakref.ref(net), cfg["entropy_beta"]
    return net


def loss_fn(out, labels):
    """The looped model's objective, ``mean_tokens[sum_t p_t CE_t - beta
    H(p)]`` with the configuration's ``entropy_beta``: the zoo's
    ``looped_lm_loss`` (the four exits' cross-entropies through the
    program's chunked head, float32 inside)."""
    from mxnet_tpu.gluon.model_zoo.ouro import looped_lm_loss
    return looped_lm_loss(out, labels, beta=_beta)
