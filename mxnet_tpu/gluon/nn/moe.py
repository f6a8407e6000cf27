"""Mixture-of-Experts layers.

Reference parity: none (the reference has no MoE — SURVEY §2.3 marks EP
out of its scope; first-class here per the long-context/distributed brief).

Two layers.  ``MoEDense`` is the Switch/top-k formulation: experts as
stacked weights with a leading expert dim, dispatch and combine as einsums
over a one-hot ``(tokens, experts, capacity)`` mask, tokens past an
expert's capacity dropped.  Its expert dim can be sharded over any mesh
axis a caller names (``moe_expert_specs``); ``MeshConfig`` itself has no
'ep' axis, so that means a raw ``jax.sharding.Mesh``.

``RoutedExperts`` is one chip's share of a drop-free expert layer: it is
told the published expert count and which experts it holds, routes every
token over all of them, and computes its own experts' part by grouped
matrix products over ragged groups (``lax.ragged_dot``) — no capacity a
expert, no one-hot tensor.  What the absent experts would add is left
out: the sum over the shares of a layer is the whole layer.  There is no
exchange between shares here (no 'ep' axis, no all-to-all).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import telemetry as _telemetry
from ...numpy.multiarray import _invoke
from ..block import HybridBlock
from ..parameter import Parameter


class MoEDense(HybridBlock):
    """Top-k routed expert FFN on (batch, seq, units) or (tokens, units).

    forward returns (output, aux_loss) where aux_loss is the Switch
    load-balancing loss (mean over experts of fraction_tokens *
    fraction_router_prob * n_experts).
    """

    def __init__(self, units, hidden_size, num_experts, num_experts_per_tok=1,
                 capacity_factor=1.25, activation="gelu", dtype="float32"):
        super().__init__()
        if num_experts_per_tok > num_experts:
            raise ValueError(
                f"num_experts_per_tok {num_experts_per_tok} > "
                f"num_experts {num_experts}")
        self._units = units
        self._hidden = hidden_size
        self._n_exp = num_experts
        self._topk = num_experts_per_tok
        self._cap = capacity_factor
        self._act = activation
        self.gate = Parameter("gate", shape=(units, num_experts), dtype=dtype)
        self.w_in = Parameter("w_in", shape=(num_experts, units, hidden_size),
                              dtype=dtype)
        self.w_out = Parameter("w_out",
                               shape=(num_experts, hidden_size, units),
                               dtype=dtype)

    def forward(self, x):
        for p in (self.gate, self.w_in, self.w_out):
            if p._data is None:
                p._finish_deferred_init()
        n_exp, topk, cap_f, act = self._n_exp, self._topk, self._cap, self._act

        def fn(x_, gate, w_in, w_out):
            shape = x_.shape
            tokens = x_.reshape(-1, shape[-1])          # (T, d)
            T = tokens.shape[0]
            capacity = max(1, int(cap_f * T * topk / n_exp))
            logits = tokens @ gate                       # (T, E)
            probs = jax.nn.softmax(logits.astype(jnp.float32), -1)

            # top-k routing with per-expert capacity (Switch formulation)
            combine = jnp.zeros((T, n_exp, capacity), jnp.float32)
            dispatch = jnp.zeros((T, n_exp, capacity), jnp.bool_)
            remaining = probs
            position_in_expert = jnp.zeros((n_exp,), jnp.int32)
            route_count = jnp.zeros((n_exp,), jnp.float32)
            gate_sum = jnp.zeros((T,), jnp.float32)
            for _ in range(topk):
                choice = jnp.argmax(remaining, -1)               # (T,)
                gate_val = jnp.take_along_axis(
                    remaining, choice[:, None], -1)[:, 0]
                onehot = jax.nn.one_hot(choice, n_exp, dtype=jnp.int32)
                pos = position_in_expert[None, :] + \
                    (jnp.cumsum(onehot, 0) - onehot)             # (T, E)
                pos_tok = jnp.sum(pos * onehot, -1)              # (T,)
                keep = pos_tok < capacity
                pos_oh = jax.nn.one_hot(jnp.clip(pos_tok, 0, capacity - 1),
                                        capacity, dtype=jnp.float32)
                sel = (onehot.astype(jnp.float32)
                       * keep[:, None].astype(jnp.float32))
                dispatch = dispatch | (
                    sel[:, :, None] * pos_oh[:, None, :] > 0)
                combine = combine + (gate_val[:, None, None]
                                     * sel[:, :, None] * pos_oh[:, None, :])
                gate_sum = gate_sum + gate_val
                position_in_expert = position_in_expert + jnp.sum(
                    onehot * keep[:, None].astype(jnp.int32), 0)
                # pre-drop router assignments (Switch defines f_i over what
                # the router *chose*, not what survived capacity)
                route_count = route_count + jnp.sum(
                    onehot.astype(jnp.float32), 0)
                remaining = remaining * (1.0 - onehot.astype(jnp.float32))

            if topk > 1:
                # GShard top-k: renormalize combine weights over the chosen
                # experts (pre-capacity-drop), so kept gates sum to <= 1;
                # top-1 keeps the raw router prob (Switch formulation)
                combine = combine / (gate_sum[:, None, None] + 1e-9)

            # dispatch tokens to expert buffers: (E, C, d)
            exp_in = jnp.einsum("tec,td->ecd",
                                dispatch.astype(x_.dtype), tokens)
            h = jnp.einsum("ecd,edh->ech", exp_in, w_in)
            h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
            exp_out = jnp.einsum("ech,ehd->ecd", h, w_out)
            out = jnp.einsum("tec,ecd->td", combine.astype(x_.dtype),
                             exp_out)

            # load-balancing aux loss (Switch): E * sum_e f_e * P_e, with
            # f_e the PRE-capacity-drop routed fraction so the gradient
            # keeps penalizing collapse even when the hot expert overflows
            f = route_count / (T * topk)
            p_mean = jnp.mean(probs, 0)
            aux = n_exp * jnp.sum(f * p_mean)
            return out.reshape(shape), aux

        return _invoke(fn, (x, self.gate.data(), self.w_in.data(),
                            self.w_out.data()), name="moe_dense")


#: ``lax.ragged_dot``'s operands are padded with zeros to widths this
#: divides: XLA's TPU kernel takes its blocks from what divides a width
#: (512, 256 or 128), and at 2688 x 1856, which 256 divides neither way,
#: its 128 x 128 blocks took 14.6-16.2 ms a layer's two products, forward
#: and backward, against 8.0-8.5 at 2816 x 2048 (v5e, PERF.md section 6,
#: PR 36).  Widths it divides already (or under it) are left alone.
_BLOCK = 256


class RoutedExperts(HybridBlock):
    """One share of a drop-free expert layer on (batch, seq, units), plus
    the shared expert every share computes (where the layer has one).

    ``num_experts`` is the published count the router scores;
    ``held = (lo, hi)`` the experts whose weights live here.  Per token:
    ``s = sigmoid(u Wr)`` or, with ``score_func="softmax"``, the softmax
    of ``u Wr`` over all the experts (float32); the
    ``num_experts_per_tok`` largest of ``s + expert_bias`` are selected,
    their weights are
    ``route_scale * s_e / (sum of the selected s + 1e-20)`` — normalised
    over all selected experts, held or not — and the layer returns
    ``Shared(u) + sum over selected held e of w_e Expert_e(u)``.  An
    expert of width ``hidden_size`` is, by ``activation``, a SwiGLU of
    three matrices, ``(silu(u Wg) * (u Wu)) Wd`` (``"swiglu"``, the
    default), or a squared-ReLU feed-forward of two,
    ``relu(u Wu)**2 Wd`` (``"relu2"``: no gate matrix exists, routed or
    shared); the shared expert has the routed experts' form at its own
    width.

    The assignments that name a held expert are sorted by expert and
    computed as ragged groups of at most ``rows_bound`` rows in all (a
    static bound: shapes stay fixed for jit).  Assignments past it are
    left out and counted.  State that is no trained parameter rides the
    aux channel like BatchNorm's statistics: ``expert_bias`` (selection
    bias; nothing here changes it), ``expert_load`` (assignments per
    published expert, accumulated over training calls) and ``rows_over``
    (assignments past the bound, accumulated).
    """

    def __init__(self, units, hidden_size, num_experts, num_experts_per_tok,
                 held, rows_bound, shared_hidden_size=0, route_scale=1.0,
                 dtype="float32", score_func="sigmoid",
                 activation="swiglu"):
        super().__init__()
        if activation not in ("swiglu", "relu2"):
            raise ValueError(f"activation {activation!r} is neither "
                             "'swiglu' nor 'relu2'")
        self._gated = activation == "swiglu"
        if score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {score_func!r} is neither "
                             "'sigmoid' nor 'softmax'")
        self._score = jax.nn.sigmoid if score_func == "sigmoid" \
            else jax.nn.softmax
        lo, hi = held
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"held experts {held} outside [0, "
                             f"{num_experts})")
        self._n_exp, self._topk = num_experts, num_experts_per_tok
        self._lo, self._n_held = lo, hi - lo
        self._rows, self._scale = int(rows_bound), float(route_scale)
        n = self._n_held
        self.router = Parameter("router", shape=(num_experts, units),
                                dtype=dtype)
        if self._gated:
            self.w_gate = Parameter("w_gate",
                                    shape=(n, units, hidden_size),
                                    dtype=dtype)
        self.w_up = Parameter("w_up", shape=(n, units, hidden_size),
                              dtype=dtype)
        self.w_down = Parameter("w_down", shape=(n, hidden_size, units),
                                dtype=dtype)
        self.expert_bias = Parameter("expert_bias", grad_req="null",
                                     shape=(num_experts,), init="zeros")
        self.expert_load = Parameter("expert_load", grad_req="null",
                                     shape=(num_experts,), dtype="int32",
                                     init="zeros")
        self.rows_over = Parameter("rows_over", grad_req="null",
                                   shape=(1,), dtype="int32", init="zeros")
        # the shared expert's matrices, laid out as Dense keeps them
        # (out, in); computed inside the layer's own scope
        self._shared = bool(shared_hidden_size)
        if self._shared:
            f = shared_hidden_size
            if self._gated:
                self.shared_gate = Parameter("shared_gate",
                                             shape=(f, units), dtype=dtype)
            self.shared_up = Parameter("shared_up", shape=(f, units),
                                       dtype=dtype)
            self.shared_down = Parameter("shared_down", shape=(units, f),
                                         dtype=dtype)

    def forward(self, x):
        from ... import amp, autograd
        gated = self._gated
        # an expert's matrices in the order it applies them; a relu^2
        # expert has no gate matrix
        experts = ((self.w_gate,) if gated else ()) \
            + (self.w_up, self.w_down)
        shared = ()
        if self._shared:
            shared = ((self.shared_gate,) if gated else ()) \
                + (self.shared_up, self.shared_down)
        for p in (self.router, self.expert_bias, self.expert_load,
                  self.rows_over) + experts + shared:
            if p._data is None:
                p._finish_deferred_init()
        n_exp, topk, lo, n_held = (self._n_exp, self._topk, self._lo,
                                   self._n_held)
        bound, scale, score = self._rows, self._scale, self._score
        compute = amp.target_dtype() if amp.is_active() else None
        if _telemetry._active:
            _telemetry.inc("moe.rows_bound_total", bound)

        def hidden(product, mats):
            """An expert's hidden activation: ``product(w)`` is its input
            times one of ``mats``, its matrices but the last."""
            if gated:
                gate, up = mats
                return jax.nn.silu(product(gate)) * product(up)
            return jnp.square(jax.nn.relu(product(mats[0])))

        def fn(x_, router, *rest):
            n = len(experts)
            mats, bias, sh = rest[:n], rest[n], rest[n + 1:]
            u = x_.reshape(-1, x_.shape[-1])
            dt = u.dtype if compute is None else compute
            with jax.named_scope("mx.moe"):
                with jax.named_scope("mx.moe.route"):
                    s = score(jnp.dot(
                        u.astype(jnp.float32), router.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST))
                    _, idx = jax.lax.top_k(s + bias, topk)      # (T, k)
                    # the selected scores and the counts from one
                    # (T, k, n) comparison that fuses into its
                    # reductions: a gather of k scores a token took
                    # 0.5 ms a layer on the v5e
                    hot = idx[..., None] == jnp.arange(n_exp)
                    chosen = jnp.sum(jnp.where(hot, s[:, None, :], 0.0), -1)
                    w = scale * chosen / (
                        jnp.sum(chosen, -1, keepdims=True) + 1e-20)
                    load = jnp.sum(hot, axis=(0, 1), dtype=jnp.int32)
                    # held assignments first, grouped by expert; the
                    # sort is stable, so a group keeps its tokens in order
                    local = idx.reshape(-1) - lo
                    key = jnp.where((local >= 0) & (local < n_held), local,
                                    n_held)
                    n_rows = min(bound, key.shape[0])   # a short call
                    rows = jnp.argsort(key, stable=True)[:n_rows]
                    held = load[lo:lo + n_held]
                    ends = jnp.minimum(jnp.cumsum(held), n_rows)
                    sizes = jnp.diff(ends, prepend=0)
                    over = jnp.sum(held) - ends[-1]
                    token = rows // topk
                    live = (jnp.arange(n_rows) < ends[-1])[:, None]
                    w_rows = w.reshape(-1)[rows][:, None]
                    x_rows = jnp.where(live, u.astype(dt)[token], 0)

                def grouped(rows, w):
                    # rows past the groups belong to no expert, and the
                    # grouped product leaves them unwritten on the chip
                    # (whatever the buffer held, NaN too), forward and
                    # transposed.  They are selected away, never
                    # multiplied away, after every product: the select's
                    # transpose does the same to the cotangents
                    w = w.astype(dt)
                    # zeros up to the next multiple of the kernel's block
                    # change no value
                    k, n = w.shape[1:]
                    pad_k, pad_n = (-d % _BLOCK if d > _BLOCK else 0
                                    for d in (k, n))
                    if pad_k or pad_n:
                        rows = jnp.pad(rows, ((0, 0), (0, pad_k)))
                        w = jnp.pad(w, ((0, 0), (0, pad_k), (0, pad_n)))
                    return jnp.where(live, jax.lax.ragged_dot(
                        rows, w, sizes)[:, :n], 0)

                with jax.named_scope("mx.moe.experts"):
                    y = grouped(hidden(lambda w: grouped(x_rows, w),
                                       mats[:-1]), mats[-1])
                    out = 0.0
                    if sh:
                        *ins, down = (m.astype(dt) for m in sh)
                        ud = u.astype(dt)
                        out = (hidden(lambda w: ud @ w.T, ins)
                               @ down.T).astype(jnp.float32)
                out = out + jnp.zeros(u.shape, jnp.float32).at[token].add(
                    y.astype(jnp.float32) * w_rows)
                # the counts leave as floats (exact: at most tokens x k):
                # an eager recording has no cotangent for an integer
                return (out.astype(x_.dtype).reshape(x_.shape),
                        load.astype(jnp.float32),
                        over.astype(jnp.float32).reshape(1))

        out, load, over = _invoke(
            fn, (x, self.router.data())
            + tuple(p.data() for p in experts)
            + (self.expert_bias.data(),) + tuple(p.data() for p in shared),
            name="routed_experts")
        if autograd.is_training():
            counted, past = self.expert_load.data(), self.rows_over.data()
            counted._rebind(counted._data + load._data.astype(jnp.int32))
            past._rebind(past._data + over._data.astype(jnp.int32))
        return out


def moe_expert_specs(ep_axis="ep"):
    """PartitionSpecs for MoEDense params: experts sharded over the mesh
    axis named ``ep_axis`` (the parallel.layout.megatron_specs analog).
    ``MeshConfig`` builds no such axis: pass a raw Mesh that has one."""
    from jax.sharding import PartitionSpec as P
    return {
        "gate": P(),
        "w_in": P(ep_axis, None, None),
        "w_out": P(ep_axis, None, None),
    }
