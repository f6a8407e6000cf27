"""What the family files (``test_{afmoe,keye,nemotron_h,glm4_moe_lite,
ouro}.py``) and the kernel files (``test_dsa_*``, ``test_flash_*``,
``test_ssd_scan_kernel``, ``test_ssm_conv_kernel``) share.  A plain module:
not collected, imported by name (``tests/`` is on ``sys.path``); no test
file imports another test file.  ``tests/README.md`` says how to write a
family's tests with it.

**The rule: a test of a traced program traces it.**  Every cell of the
benchmark runs one traced step; none runs a model primitive by primitive.
So every ``value_and_grad`` / ``grad`` / ``vjp`` / forward over a zoo
model, an ``nn`` block, an ``ops/`` composition or an interpret-mode
``pallas_call`` runs under ONE ``jax.jit`` a side (program, oracle) —
``traced(f, *args)`` below.  Un-jitted, a small model's loss and gradients
are ~700 one-primitive programs, each lowered and compiled (40 s of a
57 s case); jitted they are two.  Eager mode stays tested where eager is
the thing under test, once a family (``Updates.eager_and_hybridized``).
"""
import collections
import functools
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import functional, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")
OPT = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}


def _chipbench(kind, family):
    path = os.path.join(REPO, "chipbench", kind, family + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load(family):
    """``(REF, FAMILY, FLOPS)`` of a family, loaded once a process (the
    generator keeps the net it last built)."""
    return tuple(_chipbench(k, family)
                 for k in ("reference", "families", "flops"))


@functools.lru_cache(maxsize=None)
def _maker(family, cfg_json):
    fam, cfg = load(family)[1], json.loads(cfg_json)

    def make(key):
        seed_key, fam.seed_key = fam.seed_key, lambda seed: key
        try:
            return fam.make_weights(cfg, 0)
        finally:
            fam.seed_key = seed_key

    return jax.jit(make)


def weights(family, cfg, seed):
    """``FAMILY.make_weights(cfg, seed)``, the same values.  The
    generator compiles a program of its own at every call (3–7 s); here
    it is traced once a (family, cfg), the seed's key its argument."""
    return _maker(family, json.dumps(cfg, sort_keys=True))(
        load(family)[1].seed_key(seed))


def config(name):
    """A cell's configuration file."""
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def tokens(cfg, batch=2, seq=16, seed=0):
    t = onp.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq + 1), dtype=onp.int32)
    return t[:, :-1], t[:, 1:]


def traced(fn, *args, **jit_kw):
    """``fn(*args)`` as one program, float32 products exact."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn, **jit_kw)(*args)


def put(param, value):
    param.set_data(mx.np.array(onp.asarray(value, onp.float32)))


def forward(block, *inputs):
    """A block's training forward as one program."""
    return traced(lambda p, *a: functional.functional_call(
        block, p, *a, train=True)[0], functional.param_arrays(block),
        *(jnp.asarray(a) for a in inputs))


def out_and_vjp(f, ct, *args):
    """``f(*args)`` and its cotangents for ``ct`` (in the output's
    type), as one program."""
    def run(ct_, *a):
        out, vjp = jax.vjp(f, *a)
        return out, vjp(ct_.astype(out.dtype))

    return traced(run, ct, *args)


def value_and_grads(f, args, ct):
    """``f(*args)`` and the gradients of ``sum(f * ct)`` in float32 by
    every argument, as one program."""
    def loss(*a):
        out = f(*a)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    (_, out), grads = traced(jax.value_and_grad(
        loss, argnums=range(len(args)), has_aux=True), *args)
    return out, grads


# ---- a zoo model against its reference -----------------------------------

def program_loss_and_grads(net, loss_fn, x, y):
    """``((loss, mutated aux), gradients)`` of ``loss_fn(net(x), y)`` by
    the trainable parameters."""
    trainable, aux = functional.split_params(net)

    def loss(tr):
        out, mutated = functional.functional_call(
            net, {**tr, **aux}, x, train=True)
        return loss_fn(out, y), mutated

    return traced(jax.value_and_grad(loss, has_aux=True), trainable)


def reference_loss_and_grads(sequence_loss, params, x, y):
    """``((mean loss, summed counts), gradients)`` of the reference's
    ``sequence_loss(p, tokens, labels) -> (sum, counts)`` over the rows
    of ``x``, a sequence at a time as ``REF.train_reference`` takes them
    (one body to compile, not one a row)."""
    def loss(p):
        sums, counts = jax.lax.map(lambda xy: sequence_loss(p, *xy),
                                   (jnp.asarray(x), jnp.asarray(y)))
        return jnp.sum(sums) / x.size, jax.tree_util.tree_map(
            lambda c: jnp.sum(c, 0), counts)

    return traced(jax.value_and_grad(loss, has_aux=True), dict(params))


def assert_leaves_close(stacked, reference, atol=3e-6, rtol=2e-3):
    assert set(stacked) == set(reference)
    for name, ref in reference.items():
        onp.testing.assert_allclose(stacked[name], ref, atol=atol, rtol=rtol,
                                    err_msg=name)


def against_the_reference(family, net, loss_fn, sequence_loss, params, x, y,
                          n_layer):
    """The zoo model's loss (to 2e-5) and every gradient leaf against
    the reference's; returns (the program's mutated aux, the reference's
    counts, the reference's gradients)."""
    (got, mutated), grads = program_loss_and_grads(net, loss_fn, x, y)
    (want, counts), ref_grads = reference_loss_and_grads(
        sequence_loss, params, x, y)
    assert abs(float(got) - float(want)) < 2e-5
    assert_leaves_close(load(family)[1].stack_program_tree(grads, n_layer),
                        ref_grads)
    return mutated, counts, ref_grads


def sharded_step(net, loss_fn, opt=OPT, **kw):
    mesh = MeshConfig(dp=1)
    return ShardedTrainStep(
        net, loss_fn, mx.optimizer.create(
            "adam", learning_rate=opt["lr"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]), mesh,
        batch_specs=mesh.batch_specs(2, 2), n_labels=1, **kw)


class Updates:
    """What the chip check compares, at a small size in float32.  The
    step's side: the seeded net through ``ShardedTrainStep`` over
    ``batches`` — ``losses``; ``first``, the first gradient's leaves
    (Adam's first moment after one update over ``1 - beta1``);
    ``change``; the step's ``aux``; ``last_counts`` / ``last_pdf`` as
    ``FAMILY.change_norms`` left them; ``seen``, what ``look(step)``
    returned (the step itself is let go).  The reference's side:
    ``REF.train_reference`` (``ref``), the gaps of the first gradient's
    norms and of the parameters' change (``g_gaps``, ``c_gaps``) and
    the dead leaves.  Beside them ``eager_and_hybridized``.  Each of the
    three runs when first read, once: no test pays for another's."""

    _STEP = ("losses", "first", "change", "aux", "last_counts", "last_pdf",
             "seen")

    def __init__(self, family, cfg, seed, batches, n_layer, opt=OPT,
                 look=None):
        self.family, self.cfg, self.seed = family, cfg, seed
        self.batches, self.n_layer, self.opt = batches, n_layer, opt
        self.look = look

    def _run_the_step(self):
        fam = load(self.family)[1]
        with jax.default_matmul_precision("highest"):
            step = sharded_step(
                fam.build_net(self.cfg, weights(self.family, self.cfg,
                                                self.seed)),
                fam.loss_fn, self.opt)
            self.losses, self.first = [], None
            for bx, by in self.batches:
                self.losses.append(float(step(bx, by).asnumpy()))
                if self.first is None:
                    self.first = {
                        n: onp.asarray(s[0]) / (1 - self.opt["beta1"])
                        for n, s in step.states.items()}
            self.change = jax.device_get(fam.change_norms(
                self.cfg, self.seed, step.trainable))
        self.aux = jax.device_get(step.aux)
        self.last_counts = dict(getattr(fam, "last_counts", {}))
        self.last_pdf = getattr(fam, "last_pdf", None)
        self.seen = self.look(step) if self.look else None

    def __getattr__(self, name):
        if name in self._STEP:
            self._run_the_step()
            return self.__dict__[name]
        raise AttributeError(name)

    @functools.cached_property
    def eager_and_hybridized(self):
        """The family's one eager case, on the first batch: the same
        seeded net's loss op by op under ``mx.autograd.record``, and
        hybridized."""
        fam = load(self.family)[1]
        net = fam.build_net(self.cfg, weights(self.family, self.cfg,
                                              self.seed))
        x, y = self.batches[0]

        def loss():
            with mx.autograd.record(train_mode=True):
                out = net(mx.np.array(x))
            return float(fam.loss_fn(jax.tree_util.tree_map(
                lambda o: o._data, out,
                is_leaf=lambda o: hasattr(o, "_data")), y))

        with jax.default_matmul_precision("highest"):
            eager = loss()
            net.hybridize()
            return eager, loss()

    @functools.cached_property
    def ref(self):
        with jax.default_matmul_precision("highest"):
            return load(self.family)[0].train_reference(
                lambda: weights(self.family, self.cfg, self.seed),
                self.batches, self.cfg, self.opt)

    def _gaps(self, program, reference):
        ref_mod, fam, _ = load(self.family)
        return ref_mod.leaf_gaps(
            fam.stack_program_tree(program, self.n_layer), reference)

    @functools.cached_property
    def g_gaps(self):
        return self._gaps({n: onp.sqrt(onp.sum(onp.square(g)))
                           for n, g in self.first.items()},
                          self.ref["grad_norms"])

    @functools.cached_property
    def c_gaps(self):
        return self._gaps(self.change, self.ref["change_norms"])

    @property
    def dead(self):
        return load(self.family)[0].dead_leaves(self.ref["grad_norms"])


three_updates = Updates


# ---- an expert layer and its shares --------------------------------------

def whole_experts(hidden, inner, n, seed=3, bias=True, gate=True,
                  shared=0):
    """All of one expert layer's weights (every published expert); the
    draws in the order the families' tests always made them."""
    rs = onp.random.RandomState(seed)
    w = {"router": rs.randn(n, hidden) * 0.3}
    if bias:
        w["bias"] = rs.randn(n) * 0.05
    if gate:
        w["gate"] = rs.randn(n, hidden, inner) * 0.2
    w["up"] = rs.randn(n, hidden, inner) * 0.2
    w["down"] = rs.randn(n, inner, hidden) * 0.2
    if shared:
        if gate:
            w["sg"] = rs.randn(shared, hidden) * 0.2
        w["su"] = rs.randn(shared, hidden) * 0.2
        w["sd"] = rs.randn(hidden, shared) * 0.2
    return w


_EXPERT_PARAMS = {"router": "router", "bias": "expert_bias",
                  "gate": "w_gate", "up": "w_up", "down": "w_down",
                  "sg": "shared_gate", "su": "shared_up",
                  "sd": "shared_down"}
_EXPERT_LEAVES = {"router": "moe.router.w", "gate": "moe.gate.w",
                  "up": "moe.up.w", "down": "moe.down.w",
                  "sg": "moe.shared.gate.w", "su": "moe.shared.up.w",
                  "sd": "moe.shared.down.w"}


def routed_experts(w, lo, hi, top, rows_bound, shared=True, **kw):
    """``nn.RoutedExperts`` holding experts ``lo:hi`` of ``w``."""
    n, hidden, inner = w["up"].shape
    layer = nn.RoutedExperts(
        hidden, inner, n, top, held=(lo, hi), rows_bound=rows_bound,
        shared_hidden_size=w["su"].shape[0] if shared and "su" in w else 0,
        **kw)
    layer.initialize()
    for name, attr in _EXPERT_PARAMS.items():
        if name in w and (shared or name not in ("sg", "su", "sd")):
            put(getattr(layer, attr),
                w[name][lo:hi] if name in ("gate", "up", "down")
                else w[name])
    return layer


def reference_leaves(w, lo=0, hi=None):
    """``w`` under the references' names: (leaves, bias)."""
    p = {leaf: jnp.asarray(
        w[n][lo:hi] if n in ("gate", "up", "down") else w[n], jnp.float32)
        for n, leaf in _EXPERT_LEAVES.items() if n in w}
    return p, (jnp.asarray(w["bias"], jnp.float32) if "bias" in w else None)


def rows(n, width, seed=5):
    return jnp.asarray(onp.random.RandomState(seed).randn(n, width),
                       jnp.float32)


def uncut(family, cfg, w, u):
    """``u`` through the whole layer by the reference, every published
    expert held: (output, assignments per expert)."""
    leaves, bias = reference_leaves(w)
    whole = dict(cfg, num_experts_held=w["up"].shape[0], experts_held_from=0)
    return traced(lambda u_: load(family)[0]._experts(
        u_, leaves, *(() if bias is None else (bias,)), whole), u)


def sum_of_shares(w, u, shares, top, **kw):
    """The parts ``shares`` chips compute of one layer, summed (the
    shared expert is what every chip computes alike: the first share
    alone holds it), and each share's counts: one traced program."""
    per = w["up"].shape[0] // shares
    layers = [routed_experts(w, s * per, (s + 1) * per, top,
                             rows_bound=u.shape[0] * top, shared=(s == 0),
                             **kw) for s in range(shares)]

    def run(params):
        total, counts = 0.0, []
        for layer, p in zip(layers, params):
            out, mutated = functional.functional_call(
                layer, p, u[None], train=True)
            total = total + out[0]
            counts.append({n.split(".")[-1]: c for n, c in mutated.items()})
        return total, counts

    total, counts = traced(run, [functional.param_arrays(l) for l in layers])
    return total, counts, layers


def assert_shares_add_up(uncut_layer, shares, top, **kw):
    """``uncut_layer``: (weights, tokens, the reference's output for the
    whole layer, its counts).  Every share counts what the whole layer
    counts, leaves nothing out, and the shares' sum is the whole layer's
    output; returns the shares' layers."""
    w, u, want, load = uncut_layer
    total, counts, layers = sum_of_shares(w, u, shares, top, **kw)
    for c in counts:
        onp.testing.assert_array_equal(c["expert_load"], load)
        assert int(c["rows_over"][0]) == 0
    onp.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    return layers


# ---- what a trace holds --------------------------------------------------

def scope_counts(fn, *args):
    """``fn(*args)`` and how often each ``mx...`` scope was entered
    while it traced."""
    from jax._src import source_info_util
    cls = source_info_util.ExtendNameStackContextManager
    entered, real = collections.Counter(), cls.__enter__

    def counting(self):
        if self.name.startswith("mx"):
            entered[self.name] += 1
        return real(self)

    cls.__enter__ = counting
    try:
        return fn(*args), entered
    finally:
        cls.__enter__ = real


def lowered_scopes(net, loss_fn, x, y):
    """(the lowered text of the net's train step, its scopes' counts)."""
    step = sharded_step(net, loss_fn)
    return scope_counts(lambda: step.lower(x, y).as_text(debug_info=True))


def on_the_backward_pass(text, scope, sep="/"):
    """A scope of the forward is carried by the backward pass."""
    return all(re.search(under + sep + re.escape(scope), text) for under in (
        r"jvp\(mx\.fwd\)", r"transpose\(jvp\(mx\.fwd\)\)"))


def jaxpr_text(layer, shape):
    """The jaxpr of a layer's training call and its gradients, with what
    differs between processes taken out."""
    layer.initialize()
    x = onp.zeros(shape, onp.float32)
    layer(mx.np.array(x))
    tr, aux = functional.split_params(layer)

    def loss(p, x_):
        out, _ = functional.functional_call(layer, {**p, **aux}, x_,
                                            train=True)
        return jnp.sum(out)

    # as a program traces it: without the test suite's matmul precision
    with jax.default_matmul_precision(None):
        text = str(jax.make_jaxpr(jax.value_and_grad(loss))(
            tr, jnp.asarray(x)))
    # addresses, and the count of functional calls the process has made
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return re.sub(r"(fold_in \w+) \d+:u32\[\]", r"\1 n:u32[]", text)


#: blocks whose default call is pinned in ``tests/data/<name>.jaxpr.txt``
#: (written by ``jaxpr_text`` at the parent of the PR that pinned it)
AS_BEFORE = {
    "routed_experts": lambda: nn.RoutedExperts(
        32, 16, 16, 4, held=(4, 8), rows_bound=48, shared_hidden_size=16,
        route_scale=2.5),
    "routed_experts_softmax": lambda: nn.RoutedExperts(
        32, 16, 16, 4, held=(0, 4), rows_bound=48, score_func="softmax"),
    "grouped_query_attention": lambda: nn.GroupedQueryAttention(
        32, 4, 2, 8, window=4, rotary=True),
    "grouped_query_attention_plain": lambda: nn.GroupedQueryAttention(
        32, 4, 2, 8, gate=False),
}


def as_before(name):
    """(what the block traces to now, what it traced to then)."""
    with open(os.path.join(DATA, name + ".jaxpr.txt")) as f:
        return jaxpr_text(AS_BEFORE[name](), (2, 8, 32)), f.read()


def eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub)


def pallas_calls(jaxpr):
    return [e for e in eqns(jaxpr) if e.primitive.name == "pallas_call"]


def pallas_scopes(f, *args):
    """[(kernel, the scopes it is traced under)] of what ``f`` traces."""
    return [(e.params["name"], str(e.source_info.name_stack))
            for e in pallas_calls(jax.make_jaxpr(f)(*args).jaxpr)]


def pallas_names(f, *args):
    return sorted(name for name, _ in pallas_scopes(f, *args))


def counters(prefix, f, *args):
    """``f(*args)`` and the telemetry counters under ``prefix`` of what
    it traced."""
    telemetry.reset()
    telemetry.enable()
    try:
        return f(*args), telemetry.counters(prefix)
    finally:
        telemetry.enable(False)
        telemetry.reset()


def kernel_tiles(f, *args):
    """``kernel.flash_tiles_total`` of one traced call: {kernel: {kind:
    tiles}}."""
    out, flat = counters("kernel.flash_tiles_total", f, *args)
    tiles = {}
    for key, n in flat.items():
        kernel = key.split('kernel="')[1].split('"')[0]
        kind = key.split('kind="')[1].split('"')[0]
        tiles.setdefault(kernel, {})[kind] = n
    return out, tiles


@pytest.fixture(scope="module")
def one_v5e():
    """A described v5e to compile for (no chip needed)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])
