"""State layout — where a train step's leaves live on the mesh, decided once.

``ShardedTrainStep`` keeps, for every trainable leaf, a parameter and its
optimizer state.  How both are laid out on the mesh, and what their
layout-free form in a checkpoint is, is ONE decision: ``StateLayout`` plans
it from plain data — leaf shapes, the parameter specs, the mesh's axis
sizes, ``zero``, ``dp_axis`` — and answers every question the step has about
it.  It holds no array and no device, and ``PartitionSpec``s rather than
``NamedSharding``s: the step binds them to the mesh it traces under, so the
same plan serves a described (not attached) mesh in a compile rehearsal.

Per leaf the optimizer state takes one of three forms:

* ``PARAM`` — shaped and sharded like the parameter (``zero=0``, and a
  tensor-sharded leaf no free dimension of which ``dp`` divides).
* ``FLAT`` — a fully replicated parameter under ``zero>0``: the padded ravel
  in 1/dp shards, ``P(dp_axis)``.
* ``DP`` — a tensor-sharded (tp/ep/pp) parameter under ``zero>0``: the
  parameter's shape and spec with ``dp_axis`` inserted into its largest free
  dimension; gradients reduce-scatter onto it, the elementwise update runs
  on the (tp x dp)-sharded chunk and the new weights gather back to the
  parameter's spec (ZeRO x TP).  Also a replicated parameter whose flat
  shards would cut its rows (:func:`columns_spec`; ``replicated_dp`` names
  these leaves).

crossed with pipeline stacking: under ``pp>1`` each repeated
``<prefix>layerN.<suffix>`` family is one ``(L, ...)`` leaf whose leading
dimension shards over ``pp``.  Every pp group stores only its contiguous
block of layers, and the static per-layer index in the model's forward loop
is the stage handoff GSPMD lowers to a collective-permute — gpipe's ppermute
schedule expressed as sharding instead of shard_map, so it composes with
dp/tp/sp and the grad_accum microbatch scan.
"""
from __future__ import annotations

import math
import re
from typing import NamedTuple

import numpy as onp

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..amp import fp8 as _fp8
from ..base import MXNetError

PARAM, FLAT, DP = "param", "flat", "dp"

# params whose structural name matches <prefix>layer<i>.<suffix> with
# identical shapes across i are the pipeline-stackable layer family
_PP_LAYER_RE = re.compile(r"^(?P<pre>.*\blayer)(?P<idx>\d+)\.(?P<suf>.+)$")

# name-pattern Megatron rules for the transformer family
# (column-parallel: shard Dense units; row-parallel: shard in_units, psum)
_COLUMN_SUFFIXES = ("query_proj.weight", "key_proj.weight",
                    "value_proj.weight", "ffn_1.weight")
_ROW_SUFFIXES = ("out_proj.weight", "ffn_2.weight")
_COLUMN_BIAS = ("query_proj.bias", "key_proj.bias", "value_proj.bias",
                "ffn_1.bias")


def megatron_specs(param_shapes, tp_axis="tp"):
    """PartitionSpecs for transformer params by structural-name pattern."""
    specs = {}
    for name, shape in param_shapes.items():
        if any(name.endswith(s) for s in _COLUMN_SUFFIXES) and len(shape) == 2:
            specs[name] = P(tp_axis, None)
        elif any(name.endswith(s) for s in _ROW_SUFFIXES) and len(shape) == 2:
            specs[name] = P(None, tp_axis)
        elif any(name.endswith(s) for s in _COLUMN_BIAS):
            specs[name] = P(tp_axis)
        else:
            specs[name] = P()
    return specs


def _pp_layer_groups(names):
    """Group param names by (prefix, suffix) around a 'layerN.' segment:
    {(pre, suf): {idx: name}}."""
    groups = {}
    for n in names:
        m = _PP_LAYER_RE.match(n)
        if m:
            key = (m.group("pre"), m.group("suf"))
            groups.setdefault(key, {})[int(m.group("idx"))] = n
    return groups


def _insert_dp(spec, shape, dp_axis, dp_n):
    """Optimizer-state spec for a tensor-sharded param under ZeRO: the
    param's spec with ``dp_axis`` partitioning its largest free
    (replicated, evenly divisible) dimension — the reduce-scatter target.
    None when no dimension can take the dp axis (state then shards like
    the weight)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    flat = []
    for e in entries:
        flat.extend(e if isinstance(e, tuple) else (e,))
    if dp_axis in flat:
        return None
    free = [i for i, e in enumerate(entries)
            if e is None and shape[i] % dp_n == 0 and shape[i] >= dp_n]
    if not free:
        return None
    best = max(free, key=lambda i: shape[i])
    entries[best] = dp_axis
    return P(*entries)


def columns_spec(shape, dp_axis, dp_n):
    """The DP-form spec of a REPLICATED leaf, or None where it stays FLAT.

    A replicated leaf's optimizer state is its padded ravel in 1/dp shards
    -- unless those shards would cut its rows (``shape[0] % dp_n``: GPT-2's
    50257-row table under ``dp=4`` has 12564.25 rows a shard).  GSPMD can
    neither reduce-scatter a gradient onto such a shard nor gather a
    parameter from it: it all-reduces the leaf whole and pads.  Such a leaf
    shards along its largest dimension that ``dp`` divides instead; where
    none divides it stays FLAT with its padding."""
    if not shape or shape[0] % dp_n == 0:
        return None
    return _insert_dp(P(), shape, dp_axis, dp_n)


class Leaf(NamedTuple):
    """One trainable leaf's plan (a pp family is one leaf)."""
    shape: tuple        # the parameter's shape (a family's: stacked)
    form: str           # PARAM, FLAT or DP: the form of its optimizer state
    state_shape: tuple  # FLAT: (padded size,); else the parameter's shape
    state_spec: P


class StateLayout:
    """The plan.  ``trainable`` / ``aux`` map the block's own (per-layer)
    parameter names to shapes, ``param_specs`` names to PartitionSpecs
    (missing: replicated), ``axis_sizes`` is ``dict(mesh.shape)``.

    ``leaves`` maps each trainable leaf — pp families under their stacked
    name ``<prefix>layer*.<suffix>`` — to its :class:`Leaf`, in the order
    the step's dicts hold them; ``families`` maps stacked names (trainable
    and aux) to their members; ``param_specs`` gains the stacked names.
    ``fp8_sites`` and ``buckets`` are the plans of the two kinds of extra
    state: the delayed-scaling sites of ``precision="fp8"``, and the
    error-feedback buckets ``[(name, shape, size), ...]`` of a compressed
    dp reduce (``bucket_elems`` elements each, 0 for none), whose residuals
    live as one ``(dp, bucket)`` row per rank (``resid_shapes``) so the EF
    error stays rank-local across steps.
    """

    def __init__(self, trainable, aux, param_specs, axis_sizes, zero=0,
                 dp_axis="dp", fp8=False, bucket_elems=0):
        self.zero = int(zero)
        self.dp_axis = dp_axis
        self.param_specs = dict(param_specs)
        self.families = {}
        pp_n = int(axis_sizes.get("pp", 1))
        trainable = self._plan_families(dict(trainable), pp_n)
        self._plan_families(dict(aux), pp_n)
        if pp_n > 1 and not self.families:
            raise MXNetError(
                f"pp={pp_n} needs repeated 'layerN.' parameter families of "
                "identical shape to place on pipeline stages; none found in "
                "this block")
        if self.zero and dp_axis not in axis_sizes:
            raise MXNetError(
                f"zero={self.zero} requires a '{dp_axis}' mesh axis; mesh "
                f"has {tuple(axis_sizes)}")
        dp_n = int(axis_sizes[dp_axis]) if self.zero else 1
        self.leaves = {n: self._plan_leaf(tuple(s), self.param_spec(n), dp_n)
                       for n, s in trainable.items()}
        self.replicated_dp = [
            n for n, l in self.leaves.items() if l.form == DP
            and all(e is None for e in self.param_spec(n))]

        self.fp8_sites = []
        if fp8:
            shapes = {n: l.shape for n, l in self.leaves.items()}
            self.fp8_sites = _fp8.select_sites(shapes)
            if not self.fp8_sites:
                raise MXNetError(
                    "precision='fp8' found no eligible sites (2-D "
                    "'*.weight' params with >= amp.fp8_min_elems "
                    f"elements) among {sorted(shapes)}")
        self.buckets = []
        if bucket_elems:
            cur, cur_sz = [], 0
            for n in sorted(self.leaves):
                shape = self.leaves[n].shape
                size = math.prod(shape)
                if cur and cur_sz + size > bucket_elems:
                    self.buckets.append(cur)
                    cur, cur_sz = [], 0
                cur.append((n, shape, size))
                cur_sz += size
            if cur:
                self.buckets.append(cur)
        self.resid_shapes = {
            f"bucket{i}": (int(axis_sizes.get(dp_axis, 1)),
                           sum(s for _, _, s in members))
            for i, members in enumerate(self.buckets)}

    # -- planning ------------------------------------------------------------
    def _plan_families(self, shapes, pp_n):
        """Replace each stackable family in ``shapes`` by its stacked leaf,
        the way :meth:`stack` will the arrays (same order)."""
        if pp_n <= 1:
            return shapes
        for (pre, suf), idx_map in _pp_layer_groups(shapes).items():
            L = len(idx_map)
            if sorted(idx_map) != list(range(L)):
                continue   # holes in the index range: not a family
            members = [idx_map[i] for i in range(L)]
            if len({tuple(shapes[m]) for m in members}) != 1:
                continue
            if L % pp_n:
                raise MXNetError(
                    f"pp={pp_n}: layer family '{pre}N.{suf}' has {L} layers "
                    f"— not divisible into {pp_n} pipeline stages")
            sname = f"{pre}*.{suf}"
            shape = tuple(shapes[members[0]])
            for m in members:
                del shapes[m]
            shapes[sname] = (L,) + shape
            self.param_specs[sname] = P(
                "pp", *tuple(self.param_spec(members[0])))
            self.families[sname] = tuple(members)
        return shapes

    def _plan_leaf(self, shape, spec, dp_n):
        if not self.zero:
            return Leaf(shape, PARAM, shape, spec)
        replicated = all(e is None for e in spec)
        sspec = (columns_spec(shape, self.dp_axis, dp_n) if replicated
                 else _insert_dp(spec, shape, self.dp_axis, dp_n))
        if sspec is not None:
            return Leaf(shape, DP, shape, sspec)
        if replicated:
            padded = -(-math.prod(shape) // dp_n) * dp_n
            return Leaf(shape, FLAT, (padded,), P(self.dp_axis))
        return Leaf(shape, PARAM, shape, spec)

    def param_spec(self, n):
        return self.param_specs.get(n, P())

    def names(self, form):
        """The leaves whose optimizer state has ``form``, in step order."""
        return [n for n, l in self.leaves.items() if l.form == form]

    def census(self, itemsize):
        """Leaves and parameter bytes by form, and of the replicated leaves
        that took the DP form: the ``train.plan`` span's attributes.
        ``itemsize`` maps a parameter's own (per-layer) name to its
        element's bytes."""
        def nbytes(n):
            member = self.families.get(n, (n,))[0]
            return math.prod(self.leaves[n].shape) * itemsize[member]

        out = {}
        groups = {form: self.names(form) for form in (PARAM, FLAT, DP)}
        groups["replicated_dp"] = self.replicated_dp
        for key, names in groups.items():
            out[f"{key}_leaves"] = len(names)
            out[f"{key}_bytes"] = sum(map(nbytes, names))
        return out

    # -- parameter form <-> state form ---------------------------------------
    def to_state_form(self, n, x):
        """``x``, shaped like parameter ``n``, in the shape its optimizer
        state has: the padded ravel (FLAT), else ``x`` itself.  Host arrays
        stay on the host."""
        leaf = self.leaves[n]
        if leaf.form != FLAT:
            return x
        xp = onp if isinstance(x, onp.ndarray) else jnp
        flat, pad = xp.ravel(x), leaf.state_shape[0] - math.prod(leaf.shape)
        return xp.pad(flat, (0, pad)) if pad else flat

    def from_state_form(self, n, x):
        """Inverse of :meth:`to_state_form`: un-pad and reshape."""
        leaf = self.leaves[n]
        if leaf.form != FLAT:
            return x
        return x[:math.prod(leaf.shape)].reshape(leaf.shape)

    def pin_state(self, n, x, mesh):
        """Pin state-form ``x`` to the layout of ``n``'s optimizer state
        (nothing to pin for PARAM) — on a gradient this IS the reduce-scatter
        over dp, and keeps it from ever materializing replicated."""
        leaf = self.leaves[n]
        if leaf.form == PARAM:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, leaf.state_spec))

    def pin_param(self, n, x, mesh):
        """Pin ``x`` to parameter ``n``'s own spec: updated DP-form weights
        gather back to the tensor-sharded layout."""
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, self.param_spec(n)))

    # -- pipeline families ---------------------------------------------------
    def stack(self, d):
        """Stack the pipeline families present in ``d`` (per-layer names ->
        one ``(L, ...)`` entry under the stacked name): the parameters at
        construction, a checkpoint's arrays, and the mutated-aux dict the
        forward returns (BatchNorm running stats inside pipelined layers)."""
        if not self.families or not d:
            return d
        out = dict(d)
        for sname, members in self.families.items():
            hit = [m for m in members if m in out]
            if not hit:
                continue
            if len(hit) != len(members):
                raise MXNetError(
                    f"pipeline family {sname}: only {len(hit)}/"
                    f"{len(members)} member layers present — stages must "
                    "update aux state uniformly")
            parts = [out.pop(m) for m in members]
            xp = onp if isinstance(parts[0], onp.ndarray) else jnp
            out[sname] = xp.stack(parts)
        return out

    def unstack(self, d):
        """Unstack pipeline families back to per-layer names (the block's
        forward, a checkpoint): a static slice of the stack a layer."""
        if not self.families:
            return d
        out = dict(d)
        for sname, members in self.families.items():
            if sname not in out:
                continue
            stacked = out.pop(sname)
            for i, member in enumerate(members):
                out[member] = stacked[i]
        return out

    # -- the checkpoint's layout-free form -----------------------------------
    def to_canonical(self, trainable, aux, states, extra):
        """The step's state as host numpy in the CANONICAL layout-free form:
        FLAT state un-padded and reshaped back to its weight's shape, shards
        gathered to the full array (``onp.asarray``), pp families unstacked
        back to their per-layer names."""
        arrays = {}
        for prefix, d in (("trainable", trainable), ("aux", aux)):
            host = {n: onp.asarray(v) for n, v in d.items()}
            for n, v in self.unstack(host).items():
                arrays[f"{prefix}/{n}"] = v
        for n, s in states.items():
            for i, leaf in enumerate(jax.tree_util.tree_leaves(s)):
                a = self.from_state_form(n, onp.asarray(leaf))
                for m, part in self.unstack({n: a}).items():
                    arrays[f"state/{m}/{i}"] = part
        for site, hist in extra["fp8"].items():
            for k, v in hist.items():
                arrays[f"fp8/{site}/{k}"] = onp.asarray(v)
        for bname, v in extra["resid"].items():
            # canonical EF residual = the SUM over dp ranks: what the sum
            # of rank-local errors still owes the trajectory.  Restoring
            # it into one rank (from_canonical) preserves the total
            # exactly at any dp size — f32 x + 0.0 is bitwise x.
            a = onp.asarray(v)
            arrays[f"efresid/{bname}"] = a.sum(axis=0, dtype=a.dtype)
        return arrays

    def from_canonical(self, arrays, like):
        """``to_canonical``'s inverse under THIS plan (which may differ from
        the saving run's — families re-stack, FLAT state re-pads): host
        arrays in the structure of ``like = (trainable, aux, states,
        extra)``, of which only structure and shapes are read."""
        trainable, aux, states, extra = like

        def gather(prefix, n, suffix=""):
            members = self.families.get(n)
            if members is None:
                return arrays[f"{prefix}/{n}{suffix}"]
            return onp.stack([arrays[f"{prefix}/{m}{suffix}"]
                              for m in members])

        new_states = {}
        for n, s in states.items():
            leaves, treedef = jax.tree_util.tree_flatten(s)
            new_states[n] = jax.tree_util.tree_unflatten(treedef, [
                self.to_state_form(n, onp.asarray(gather("state", n, f"/{i}")))
                for i in range(len(leaves))])
        # fp8 amax histories: tolerate missing keys (resuming a pre-fp8
        # bundle into an fp8 step keeps the fresh zero history) and a
        # changed history length (clip newest-first / zero-pad oldest)
        fp8_new = {}
        for site, hist in extra["fp8"].items():
            fp8_new[site] = {}
            for k, v in hist.items():
                a = arrays.get(f"fp8/{site}/{k}")
                if a is not None:
                    h = int(v.shape[0])
                    a = onp.asarray(a).astype(onp.float32)[:h]
                    v = onp.pad(a, (0, h - a.shape[0]))
                fp8_new[site][k] = v
        resid_new = {}
        for bname, v in extra["resid"].items():
            a = arrays.get(f"efresid/{bname}")
            if a is not None:
                # canonical sum restores into rank 0; other ranks start
                # with zero error debt (bucket layout depends only on param
                # names and comm.bucket_mb, so it is dp-size invariant)
                v = onp.zeros(v.shape, onp.float32)
                v[0] = onp.asarray(a)
            resid_new[bname] = v
        return ({n: gather("trainable", n) for n in trainable},
                {n: gather("aux", n) for n in aux}, new_states,
                {"fp8": fp8_new, "resid": resid_new})
