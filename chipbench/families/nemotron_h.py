"""The nemotron_h family (Nemotron-H: Mamba-2 mixers, attention and
relu^2 experts, one sublayer a layer): seeded weights, and the zoo's net
built from a configuration file.

Two halves that must not mix.  ``leaf_shapes`` / ``make_weights`` are the
benchmark's own generator (pure JAX, nothing of the program): one jitted
call makes every leaf on the device from the seed, stacked over the
layers of its kind (all / ``M`` / ``*`` / ``E`` of
``hybrid_override_pattern``), in the type asked for.  The reference is
handed these; the program is handed the same values under its own
parameter names (``program_leaves``).  ``build_net`` and ``loss_fn`` are
the only functions here that import the program.

Initialisation (the configuration's ``assumed`` repeats it): N(0, 0.02)
matrices; every sublayer's out-projection / sqrt(**published** layers, 52)
(``rescale_prenorm_residual``: the kept layers are the first seven of a
52-layer model, and are scaled as that model's are); norm scales
1 + N(0, 0.02); the selection
bias (``moe.bias``, no gradient, never updated) N(0, 0.01), for the
reason ``families/afmoe.py`` measured (the largest of 128 offsets of
0.05 sigma triples an expert's load); **embedding rows
N(0, 0.02 x 4 sqrt(hidden))** (``EMBED_SCALE``; why 4 is below).  The
state-space leaves
take Mamba-2's own start from the configuration's keys: ``dt_bias`` the
inverse softplus of a step log-uniform in ``[time_step_min,
time_step_max]`` held above ``time_step_floor``, ``A_log`` the log of a
rate uniform in [1, 16], ``D`` 1, the convolution's weights and bias
uniform in +-1/sqrt(``conv_kernel``).

Why the published depth and not the kept one: the cell is one chip's
share of a deployed model, whose routing is even, and a seeded model's
is not where the router's input shares a component across tokens.  A
mixer with random weights returns a running average of its inputs, and
attention nearly the mean of its values: one vector for many tokens,
added to a stream whose token-specific part is the embedding row
(RMS 1.04).  With out-projections / sqrt(7) a mixer adds ~0.48 to it in
RMS, with / sqrt(52) ~0.18.  The plain reference's forward at the cell's
sizes (``chipbench/dev/nemotron_loads.py``, on the CPU, counts only;
seeds 2100003601 and 2100003602) gives, most loaded of the 128 experts
over the mean by expert layer, the same over the 8 held, and the rows
held (3072 expected, ``rows_bound`` 6144):

    / sqrt(7):   1.93 2.50 3.47 | 1.16 1.58 1.79 | 3010 2668 3372
                 1.74 2.93 3.25 | 1.43 2.07 2.34 | 2788 2527 3608
    / sqrt(52):  1.52 1.67 2.07 | 1.19 1.36 1.25 | 3087 2701 3169
                 1.55 2.13 2.05 | 1.20 1.59 1.70 | 2799 2774 3416

so the imbalance grows with depth at the kept depth's scale and stays
near what the selection bias and the sampling of ~384 rows an expert
leave (``families/afmoe.py``: ~1.5) at the published one's.

Why the embedding rows are 4 x sqrt(hidden) and not 1 x (what
``families/keye.py`` ships for a family that does not scale its
embedding either): that held at the first batch and not on the chip.
Adam's first steps move every weight by its rate whatever the
gradient's size, and an out-projection of 4096 inputs whose gradient is
nearly of rank one (the shared component again) then adds one vector of
RMS ~0.3 to every token a step: the routing grew uneven within the
check's three updates (``moe_load_max_over_mean.train`` 1.87–2.51 on the
chip where the first batch read 1.36–1.70) and kept moving through the
window: not the even loads a deployed model's router hands its experts,
and on the way to rows past ``rows_bound``.  (What showed it was time:
``lax.ragged_dot``'s kernel takes as long as the rows it is handed, and an
update took 242.9–255.9 ms by seed, 268 -> 253 ms inside one window; my
chip runs, PR 36.)  This family has no post-norm to hold a sublayer's
share down (as
``families/afmoe.py`` does with 0.03), so the stream's own part is made
larger instead: the program against the reference on the CPU at every
published width, 1024 tokens, seed 2100003614 (the slowest), most loaded
of 128 over the mean by expert layer after the check's three updates,
and rows held over rows expected:

    rows x 1:  1.49 2.28 2.67 | 0.98 1.01 1.15
    rows x 4:  1.43 1.41 1.57 | 0.99 1.07 1.06
    rows x 8:  1.43 1.38 1.50 | 0.99 1.08 1.04

4 is at the floor the bias and the sampling leave; 8 buys nothing.  A
sublayer's share of the stream falls with it (a mixer adds ~0.04 of the
stream's RMS), which is what a post-norm of 0.03 does in the family
beside this one; every leaf's gradient and change is still compared by
its own norm.

The counts the expert layers keep in the step's ``aux``
(``expert_load``, ``rows_over``) ride beside the change norms as in
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

# a program without the zoo's nemotron_h stack cannot run this family:
# say so before a weight is made (located, not imported)
if importlib.util.find_spec("mxnet_tpu.gluon.model_zoo.nemotron_h") is None:
    raise SystemExit("chipbench: this program has no "
                     "mxnet_tpu.gluon.model_zoo.nemotron_h: it cannot run "
                     "the nemotron_h family")

#: embedding rows, on top of N(0, 0.02), in units of sqrt(hidden) (see the
#: module's docstring)
EMBED_SCALE = 4.0

#: stacked over all layers / the layers of one kind of the pattern
LAYER_LEAVES = ("ln.g",)
KIND_LEAVES = {
    "M": ("ssm.in.w", "ssm.conv.w", "ssm.conv.b", "ssm.dt_bias",
          "ssm.A_log", "ssm.D", "ssm.norm.g", "ssm.out.w"),
    "*": ("attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w"),
    "E": ("moe.router.w", "moe.bias", "moe.shared.up.w",
          "moe.shared.down.w", "moe.up.w", "moe.down.w"),
}
#: not trained: the program keeps it in ``aux``
BIAS = "moe.bias"
LOAD, ROWS_OVER = "moe.load", "moe.rows_over"

_LAYER = "backbone.layer{i}."
_MIX = _LAYER + "mixer."
#: reference leaf -> the zoo's parameter name (stacked leaves take {i})
PROGRAM_NAMES = {
    "wte": "backbone.word_embed.weight",
    "head.w": "lm_head.weight",
    "ln_f.g": "backbone.final_norm.gamma",
    "ln.g": _LAYER + "norm.gamma",
    "ssm.in.w": _MIX + "in_proj.weight",
    "ssm.conv.w": _MIX + "conv_weight",
    "ssm.conv.b": _MIX + "conv_bias",
    "ssm.dt_bias": _MIX + "dt_bias",
    "ssm.A_log": _MIX + "A_log",
    "ssm.D": _MIX + "D",
    "ssm.norm.g": _MIX + "norm_gamma",
    "ssm.out.w": _MIX + "out_proj.weight",
    "attn.q.w": _MIX + "query_proj.weight",
    "attn.k.w": _MIX + "key_proj.weight",
    "attn.v.w": _MIX + "value_proj.weight",
    "attn.o.w": _MIX + "out_proj.weight",
    "moe.router.w": _MIX + "router",
    "moe.bias": _MIX + "expert_bias",
    "moe.shared.up.w": _MIX + "shared_up",
    "moe.shared.down.w": _MIX + "shared_down",
    "moe.up.w": _MIX + "w_up",
    "moe.down.w": _MIX + "w_down",
}
#: the counts the program's expert layers keep in ``aux``
PROGRAM_COUNTS = {LOAD: _MIX + "expert_load", ROWS_OVER: _MIX + "rows_over"}
_KIND_OF = {n: k for k, names in KIND_LEAVES.items() for n in names}
_KIND_OF.update({LOAD: "E", ROWS_OVER: "E"})


def layers_of(name, cfg):
    """The model layers a stacked leaf has an entry for, in order (None
    for a leaf that is not stacked)."""
    pattern = cfg["hybrid_override_pattern"]
    if name in LAYER_LEAVES:
        return list(range(len(pattern)))
    if name in _KIND_OF:
        return [i for i, k in enumerate(pattern) if k == _KIND_OF[name]]
    return None


def leaf_shapes(cfg):
    """{reference leaf: shape}; stacked leaves carry a leading count of
    the layers that have them."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    heads = cfg["mamba_num_heads"]
    d_in = heads * cfg["mamba_head_dim"]
    conv = d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    n, held = cfg["n_routed_experts"], cfg["num_experts_held"]
    fm, fs = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    per = {"ln.g": (e,),
           "ssm.in.w": (d_in + conv + heads, e),
           "ssm.conv.w": (conv, cfg["conv_kernel"]), "ssm.conv.b": (conv,),
           "ssm.dt_bias": (heads,), "ssm.A_log": (heads,), "ssm.D": (heads,),
           "ssm.norm.g": (d_in,), "ssm.out.w": (e, d_in),
           "attn.q.w": (hq, e), "attn.k.w": (hk, e), "attn.v.w": (hk, e),
           "attn.o.w": (e, hq),
           "moe.router.w": (n, e), "moe.bias": (n,),
           "moe.shared.up.w": (fs, e), "moe.shared.down.w": (e, fs),
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
    if name == "ssm.D":
        w = jnp.ones(shape, jnp.float32)
    elif name == "ssm.A_log":
        w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif name == "ssm.dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
        w = dt + jnp.log(-jnp.expm1(-dt))       # the inverse softplus
    elif name in ("ssm.conv.w", "ssm.conv.b"):
        bound = cfg["conv_kernel"] ** -0.5
        w = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    else:
        w = jax.random.normal(k, shape, jnp.float32) * (
            0.01 if name == BIAS else 0.02)
        if name == "wte":
            w = w * EMBED_SCALE * math.sqrt(cfg["hidden_size"])
        if name in ("ssm.out.w", "attn.o.w", "moe.down.w",
                    "moe.shared.down.w"):
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


#: the leaf only a sublayer of its kind has
_MARK = {"M": _MIX + "in_proj.weight", "*": _MIX + "query_proj.weight",
         "E": _MIX + "router"}


def _kind(tree, i):
    """The kind of layer ``i`` of a program tree, from the leaf only its
    sublayer has; None where the tree holds none of them (the counts)."""
    for kind, mark in _MARK.items():
        if mark.format(i=i) in tree:
            return kind
    return None


def stack_program_tree(tree, n_layer):
    """{zoo parameter name: array} -> {reference leaf: array}, stacked
    leaves stacked again over the layers of their kind (the mixer's and
    the attention's out-projection share a zoo name, so a layer's kind
    is read from the tree itself); the inverse of ``program_leaves`` for
    any per-leaf tree of the program's, on the host (the check's
    per-leaf norms).  The leaves the tree lacks are left out (the first
    gradient has none for the selection bias), and the expert layers'
    counts are taken along where the tree has them."""
    import numpy as onp
    out = {}
    for n, pname in {**PROGRAM_NAMES, **PROGRAM_COUNTS}.items():
        if "{i}" not in pname:
            if pname in tree:
                out[n] = onp.asarray(tree[pname])
            continue
        want = _KIND_OF.get(n)      # None: every layer has the leaf
        rows = [onp.asarray(tree[pname.format(i=i)])
                for i in range(n_layer) if pname.format(i=i) in tree
                and (want is None or _kind(tree, i) in (None, want))]
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
        {n: out[n] for n in counts}, len(cfg["hybrid_override_pattern"])))
    return out


def build_net(cfg, weights):
    """The zoo's nemotron_h stack at the file's sizes, holding
    ``weights`` (in their type): this chip's share of the experts."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHForCausalLM

    lo = cfg["experts_held_from"]
    net = NemotronHForCausalLM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], num_groups=cfg["n_groups"],
        state_size=cfg["ssm_state_size"],
        num_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        shared_hidden_size=cfg["moe_shared_expert_intermediate_size"],
        held_experts=(lo, lo + cfg["num_experts_held"]),
        rows_bound=cfg["rows_bound"],
        route_scale=cfg["routed_scaling_factor"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        epsilon=cfg["layer_norm_epsilon"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"])
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
            "chipbench/families/nemotron_h.py: "
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
