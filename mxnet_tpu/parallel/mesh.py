"""Device-mesh helpers.

Reference parity: none — the reference scales via KVStore/ps-lite (SURVEY
§2.3); on TPU the mesh + GSPMD sharding is the native replacement and also
unlocks TP/PP/SP the reference lacks.

Axis convention (scaling-book style): 'dp' (data, across ICI or DCN), 'tp'
(tensor/model), 'pp' (pipeline stages), 'sp' (sequence/context): these four
are ``MeshConfig.AXES``, and ``make_mesh`` builds a mesh over any subset.
There is no expert axis: ``make_mesh`` accepts any axis name, so a raw mesh
can carry an 'ep' for ``nn.moe.moe_expert_specs``, but ``MeshConfig`` and
``ShardedTrainStep`` know none, and ``nn.RoutedExperts`` is one share of an
expert layer with no exchange between shares.
"""
from __future__ import annotations

import warnings

import numpy as onp

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .. import telemetry as _telemetry

_telemetry.declare_metric(
    "mesh.unused_devices", "gauge",
    "devices stranded by the last make_mesh call whose axis product "
    "undershot the device count (training silently runs on a subset)")

_current = None


def make_mesh(axes, devices=None):
    """Create a Mesh from {'dp': 4, 'tp': 2, ...} (row-major layout so the
    innermost axis maps to neighboring devices — keeps tp on the fastest ICI
    links).

    When the axis product undershoots ``len(devices)`` the leftover devices
    are NOT part of the mesh: that is sometimes deliberate (tests carve a
    2-way mesh out of the 8-device CI host), so it warns and counts
    ``mesh.unused_devices`` instead of raising — a production run scraping
    telemetry sees a non-zero gauge instead of silently training on a
    subset of the machine.
    """
    devices = list(devices if devices is not None else jax.devices())
    names = tuple(axes.keys())
    sizes = tuple(int(v) for v in axes.values())
    total = int(onp.prod(sizes))
    if total > len(devices):
        raise MXNetError(f"mesh {axes} needs {total} devices, "
                         f"have {len(devices)}")
    unused = len(devices) - total
    if unused:
        warnings.warn(
            f"mesh {axes} uses {total} of {len(devices)} devices; "
            f"{unused} stranded (pass an explicit device list, or size the "
            f"axes to the machine — MeshConfig enumerates factorizations)",
            stacklevel=2)
    if _telemetry.active():
        _telemetry.set_gauge("mesh.unused_devices", unused)
    arr = onp.array(devices[:total]).reshape(sizes)
    return Mesh(arr, names)


class MeshConfig:
    """The single entry point for composed parallelism: ``dp`` (data),
    ``tp`` (tensor/Megatron), ``pp`` (pipeline stages), ``sp`` (sequence/
    ring attention) — one config names the whole 4D layout and
    ``ShardedTrainStep`` composes the axes inside its one jitted step.

    Axis order on the physical device grid is ('dp', 'pp', 'sp', 'tp'):
    tp innermost so its allreduces ride the fastest ICI links, dp outermost
    so it can span DCN (scaling-book convention).

        cfg = MeshConfig(dp=2, tp=2, pp=2)      # 8 devices
        step = ShardedTrainStep(net, loss_fn, opt, cfg,
                                batch_specs=cfg.batch_specs(2, 2))

    All four axes always exist in the built Mesh (size-1 axes are free), so
    PartitionSpecs mentioning any of dp/tp/pp/sp are valid on every
    MeshConfig mesh — a checkpoint or batch spec written for one layout
    carries to another unchanged.
    """

    AXES = ("dp", "pp", "sp", "tp")

    def __init__(self, dp=1, tp=1, pp=1, sp=1):
        for name, v in (("dp", dp), ("tp", tp), ("pp", pp), ("sp", sp)):
            if int(v) != v or int(v) < 1:
                raise MXNetError(
                    f"MeshConfig {name}={v!r}: axis sizes are integers >= 1")
        self.dp, self.tp, self.pp, self.sp = int(dp), int(tp), int(pp), \
            int(sp)

    @property
    def shape(self):
        """Ordered {axis: size} over all four axes (size-1 included)."""
        return {a: getattr(self, a) for a in self.AXES}

    def size(self):
        return self.dp * self.tp * self.pp * self.sp

    def build(self, devices=None):
        """Build the jax Mesh (raises when the product exceeds the device
        count; warns + counts ``mesh.unused_devices`` on undershoot)."""
        devices = list(devices if devices is not None else jax.devices())
        if self.size() > len(devices):
            raise MXNetError(
                f"{self!r} needs {self.size()} devices, have "
                f"{len(devices)}")
        return make_mesh(self.shape, devices)

    def batch_spec(self, ndim):
        """PartitionSpec for one batch array: leading (batch) dim over
        'dp', second (sequence) dim over 'sp' when sp>1."""
        if ndim < 1:
            return P()
        parts = ["dp"]
        if ndim >= 2:
            parts.append("sp" if self.sp > 1 else None)
        return P(*parts)

    def batch_specs(self, *ndims):
        """Specs for a (inputs..., labels...) batch given each array's
        rank, e.g. ``cfg.batch_specs(2, 2)`` for GPT (tokens, labels)."""
        return tuple(self.batch_spec(n) for n in ndims)

    def activation_rules(self):
        """activation_sharding rules the step installs while tracing:
        the residual stream sharded (batch over dp, seq over sp) so the
        sp axis flows through the transformer layers' ``constrain`` hook
        and attention routes to ring_attention."""
        if self.sp > 1:
            return {"residual": P("dp", "sp", None)}
        return {}

    def replace(self, **axes):
        """A copy with the named axis sizes substituted, e.g.
        ``cfg.replace(dp=1)`` — how the fleet supervisor derives a
        degraded layout from the target one."""
        shape = self.shape
        for name in axes:
            if name not in shape:
                raise MXNetError(
                    f"MeshConfig.replace: unknown axis {name!r}; "
                    f"axes are {self.AXES}")
        shape.update(axes)
        return MeshConfig(**shape)

    def __repr__(self):
        return (f"MeshConfig(dp={self.dp}, tp={self.tp}, pp={self.pp}, "
                f"sp={self.sp})")

    def __eq__(self, other):
        return isinstance(other, MeshConfig) and self.shape == other.shape

    def __hash__(self):
        return hash(tuple(self.shape.items()))


def mesh_factorizations(n_devices=None, max_sp=1):
    """Enumerate every MeshConfig whose dp*tp*pp*sp product EXACTLY covers
    ``n_devices`` (no stranded devices) — the mesh axis mx.autotune
    searches over.  ``max_sp`` bounds the sequence axis (sp>1 only helps
    long-context models, so it defaults to off)."""
    if n_devices is None:
        n_devices = len(jax.devices())
    n_devices = int(n_devices)
    out = []
    for dp in range(1, n_devices + 1):
        if n_devices % dp:
            continue
        rem = n_devices // dp
        for tp in range(1, rem + 1):
            if rem % tp:
                continue
            rem2 = rem // tp
            for pp in range(1, rem2 + 1):
                if rem2 % pp:
                    continue
                sp = rem2 // pp
                if sp > max_sp:
                    continue
                out.append(MeshConfig(dp=dp, tp=tp, pp=pp, sp=sp))
    return out


def data_parallel_mesh(n=None):
    devs = jax.devices()
    n = n or len(devs)
    return make_mesh({"dp": n}, devs)


def set_mesh(mesh):
    global _current
    _current = mesh
    return mesh


def current_mesh():
    return _current


def shard(array, mesh, spec):
    """Place an ndarray/jax array with a PartitionSpec on a mesh."""
    from ..numpy.multiarray import ndarray, _wrap
    sharding = NamedSharding(mesh, spec if isinstance(spec, P) else P(*spec))
    raw = array._data if isinstance(array, ndarray) else array
    out = jax.device_put(raw, sharding)
    return _wrap(out) if isinstance(array, ndarray) else out


def replicate(array, mesh):
    return shard(array, mesh, P())


# -- activation sharding scope (sequence parallelism hook) ------------------
# Megatron-SP style: layers consult these rules to constrain their
# activations (residual stream sharded over ('dp', 'sp', None)); XLA then
# inserts the gather/scatter collectives around attention automatically.
_act_rules = None


class activation_sharding:
    """Scope installing activation PartitionSpec rules consulted by layers.

    with parallel.activation_sharding(mesh, residual=P('dp', 'sp', None)):
        out = net(x)            # or ShardedTrainStep built inside the scope
    """

    def __init__(self, mesh, **rules):
        self.mesh = mesh
        self.rules = rules
        self._prev = None

    def __enter__(self):
        global _act_rules
        self._prev = _act_rules
        _act_rules = (self.mesh, self.rules)
        return self

    def __exit__(self, *exc):
        global _act_rules
        _act_rules = self._prev


def constrain(x, kind):
    """Apply the active activation-sharding rule `kind` to x (ndarray or raw
    jax array); identity when no scope is active or rule missing."""
    if _act_rules is None:
        return x
    mesh, rules = _act_rules
    spec = rules.get(kind)
    if spec is None:
        return x
    from ..numpy.multiarray import ndarray, _wrap
    raw = x._data if isinstance(x, ndarray) else x
    if raw.ndim < len(spec):
        return x
    try:
        out = jax.lax.with_sharding_constraint(
            raw, NamedSharding(mesh, spec))
    except ValueError:
        return x
    return _wrap(out) if isinstance(x, ndarray) else out
