"""Transformer layers.

Reference parity: the reference ships fused attention *ops* (src/operator/
contrib/transformer.cc:675-828) and no Gluon transformer *layers* (gluon-nlp
had them); these are that family: the Pallas flash kernels on a TPU, an XLA
composition elsewhere, ring attention for sequence sharding.

A Mosaic kernel's compile-cache key holds its callers' line numbers: a line
added or removed above a ``multi_head_attention`` call here recompiles every
step that runs one, once a cell (PERF.md section 6, PR 32).
"""
from __future__ import annotations

from ... import numpy as np
from ... import numpy_extension as npx
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense, Dropout, Identity, LayerNorm, RMSNorm


class MultiHeadAttention(HybridBlock):
    """Multi-head (self or cross) attention on (batch, seq, units).

    Reference: the op pair _contrib_interleaved_matmul_selfatt_qk/valatt
    (src/operator/contrib/transformer.cc:675-828) computed exactly this
    with explicit score materialization; here scores stay on-chip.
    """

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 causal=False):
        super().__init__()
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by "
                             f"num_heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self._dropout = dropout
        self.query_proj = Dense(units, use_bias=use_bias, flatten=False)
        self.key_proj = Dense(units, use_bias=use_bias, flatten=False)
        self.value_proj = Dense(units, use_bias=use_bias, flatten=False)
        self.out_proj = Dense(units, use_bias=use_bias, flatten=False)

    def forward(self, query, key=None, value=None, mask=None):
        from ...ops.attention import multi_head_attention
        key = query if key is None else key
        value = key if value is None else value
        q = self.query_proj(query)
        k = self.key_proj(key)
        v = self.value_proj(value)
        out = multi_head_attention(
            q, k, v, self._heads, mask=mask,
            dropout_p=self._dropout, causal=self._causal)
        return self.out_proj(out)

    # -- KV-cache serving surface (mx.serve) ---------------------------
    # Self-attention only: prefill writes a whole prompt into one cache
    # slot, decode_step advances every live slot by one token. Both are
    # pure in (x, cache) -> (y, cache) so hybridize()/jit can trace them
    # as cached graphs with the cache donated across steps.

    def init_cache(self, max_slots, max_seq, dtype="float32"):
        """Preallocate one (k, v) cache pair:
        (max_slots, max_seq, units) each, a row as the projections give it
        (the heads side by side: what the decode step's kernel reads).

        ``dtype="int8"`` selects quantized storage: each of k/v becomes a
        (values int8, scales float32) pair with one symmetric scale per
        (slot, row, head) — same fixed footprint at a quarter of the
        fp32 bytes (docs/SERVING.md "Low-bit weights and KV cache")."""
        shape = (max_slots, max_seq, self._units)
        if str(dtype) == "int8":
            sshape = (max_slots, max_seq, self._heads)
            return ((np.zeros(shape, dtype="int8"),
                     np.ones(sshape, dtype="float32")),
                    (np.zeros(shape, dtype="int8"),
                     np.ones(sshape, dtype="float32")))
        return (np.zeros(shape, dtype=dtype), np.zeros(shape, dtype=dtype))

    @staticmethod
    def _cache_is_q8(kv):
        return isinstance(kv[0], (tuple, list))

    def prefill(self, x, kv, slot):
        """Full causal self-attention over one prompt (1, L, units),
        recording projected K/V into cache slot ``slot``."""
        from ...ops.attention import (multi_head_attention,
                                      write_prefill_kv, write_prefill_kv_q8)
        q = self.query_proj(x)
        k = self.key_proj(x)
        v = self.value_proj(x)
        if self._cache_is_q8(kv):
            (kc, ks), (vc, vs) = kv
            kc, ks, vc, vs = write_prefill_kv_q8(kc, ks, vc, vs, k, v,
                                                 slot, self._heads)
            new_kv = ((kc, ks), (vc, vs))
        else:
            k_cache, v_cache = write_prefill_kv(kv[0], kv[1], k, v, slot,
                                                self._heads)
            new_kv = (k_cache, v_cache)
        out = multi_head_attention(q, k, v, self._heads, causal=True)
        return self.out_proj(out), new_kv

    def decode_step(self, x, kv, positions, live=None):
        """One cached decode step: x is (slots, 1, units), ``positions``
        (slots,) each slot's cache row, ``live`` the slots that are read."""
        from ...ops.attention import decode_attention, decode_attention_q8
        q = self.query_proj(x)
        k = self.key_proj(x)
        v = self.value_proj(x)
        if self._cache_is_q8(kv):
            (kc, ks), (vc, vs) = kv
            out, kc, ks, vc, vs = decode_attention_q8(
                q, k, v, kc, ks, vc, vs, positions, self._heads)
            return self.out_proj(out), ((kc, ks), (vc, vs))
        out, k_cache, v_cache = decode_attention(
            q, k, v, kv[0], kv[1], positions, self._heads, live)
        return self.out_proj(out), (k_cache, v_cache)

    def prefill_suffix(self, x, kv, slot, start):
        """Prefix-cache suffix prefill: x (1, Ls, units) is the prompt
        *suffix*; rows [0, start) of ``slot`` already hold a copied
        prefix the suffix attends to (docs/SERVING.md
        "Prefix caching")."""
        from ...ops.attention import (suffix_prefill_attention,
                                      suffix_prefill_attention_q8)
        q = self.query_proj(x)
        k = self.key_proj(x)
        v = self.value_proj(x)
        if self._cache_is_q8(kv):
            (kc, ks), (vc, vs) = kv
            out, kc, ks, vc, vs = suffix_prefill_attention_q8(
                q, k, v, kc, ks, vc, vs, slot, start, self._heads)
            return self.out_proj(out), ((kc, ks), (vc, vs))
        out, k_cache, v_cache = suffix_prefill_attention(
            q, k, v, kv[0], kv[1], slot, start, self._heads)
        return self.out_proj(out), (k_cache, v_cache)

    def decode_multi(self, x, kv, positions):
        """k-token cached decode (the speculative-decoding verify):
        x is (slots, t, units), slot i's token j landing at cache row
        positions[i] + j with causal visibility."""
        from ...ops.attention import (decode_multi_attention,
                                      decode_multi_attention_q8)
        q = self.query_proj(x)
        k = self.key_proj(x)
        v = self.value_proj(x)
        if self._cache_is_q8(kv):
            (kc, ks), (vc, vs) = kv
            out, kc, ks, vc, vs = decode_multi_attention_q8(
                q, k, v, kc, ks, vc, vs, positions, self._heads)
            return self.out_proj(out), ((kc, ks), (vc, vs))
        out, k_cache, v_cache = decode_multi_attention(
            q, k, v, kv[0], kv[1], positions, self._heads)
        return self.out_proj(out), (k_cache, v_cache)

    def copy_cache_rows(self, kv, src_slot, src_row, dst_slot, dst_row,
                        rows):
        """Copy ``rows`` KV rows between slots — the prefix-cache block
        copy.  Works on the fp and the int8 (values, scales) layouts
        alike (scales copy with their rows)."""
        from ...ops.attention import copy_cache_rows
        return copy_cache_rows(kv, src_slot, src_row, dst_slot, dst_row,
                               rows)


class PositionwiseFFN(HybridBlock):
    """Transformer FFN block (dense → act → dense), gluon-nlp layout."""

    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 use_bias=True):
        super().__init__()
        self.ffn_1 = Dense(hidden_size, use_bias=use_bias, flatten=False)
        self._activation = activation
        self.ffn_2 = Dense(units, use_bias=use_bias, flatten=False)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x):
        h = self.ffn_1(x)
        h = npx.leaky_relu(h, act_type="gelu") if self._activation == "gelu" \
            else npx.activation(h, act_type=self._activation)
        h = self.ffn_2(h)
        if self.dropout is not None:
            h = self.dropout(h)
        return h


class GatedFFN(HybridBlock):
    """Gated feed-forward (SwiGLU, Shazeer 2020):
    ``down(silu(gate(x)) * up(x))``, no biases."""

    def __init__(self, units, hidden_size):
        super().__init__()
        self.gate_proj = Dense(hidden_size, use_bias=False, flatten=False)
        self.up_proj = Dense(hidden_size, use_bias=False, flatten=False)
        self.down_proj = Dense(units, use_bias=False, flatten=False)

    def forward(self, x):
        return self.down_proj(
            npx.activation(self.gate_proj(x), act_type="silu")
            * self.up_proj(x))


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention on (batch, seq, units) with ``num_heads``
    query heads over ``num_kv_heads`` key/value heads, no biases.  With
    ``qk_norm`` (the default) RMSNorm over the head dimension on q and k,
    one scale vector each; with ``gate`` (the default) a sigmoid gate on
    the output from a fourth projection of the input, ``(o * sigmoid(g))
    Wo``.  Optional by layer: rotary embedding on q and k, and a causal
    ``window``.  The core is ``ops.attention.multi_head_attention``: the
    flash kernels on a TPU, the XLA composition elsewhere.
    """

    def __init__(self, units, num_heads, num_kv_heads, head_dim=None,
                 window=None, rotary=False, rope_theta=10000.0,
                 epsilon=1e-5, gate=True, qk_norm=True):
        super().__init__()
        head_dim = units // num_heads if head_dim is None else head_dim
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not group over "
                             f"{num_kv_heads} KV heads")
        self._heads, self._kv_heads, self._dim = (num_heads, num_kv_heads,
                                                  head_dim)
        self._window, self._rotary, self._theta = window, rotary, rope_theta

        def proj(n):
            return Dense(n, use_bias=False, flatten=False)

        self.query_proj = proj(num_heads * head_dim)
        self.key_proj = proj(num_kv_heads * head_dim)
        self.value_proj = proj(num_kv_heads * head_dim)
        self._gate = bool(gate)
        if gate:
            self.gate_proj = proj(num_heads * head_dim)
        self.out_proj = proj(units)
        norm = (lambda: RMSNorm(epsilon, in_channels=head_dim)) if qk_norm \
            else Identity                     # no norm, no parameter
        self.q_norm, self.k_norm = norm(), norm()

    def _heads_normed(self, t, norm, heads):
        b, s, _ = t.shape
        return norm(t.reshape(b, s, heads, self._dim)).reshape(b, s, -1)

    def _qkv(self, x):
        """The core's operands: q and k normed and, where the layer has
        positions, rotated."""
        q = self._heads_normed(self.query_proj(x), self.q_norm, self._heads)
        k = self._heads_normed(self.key_proj(x), self.k_norm,
                               self._kv_heads)
        v = self.value_proj(x)
        if self._rotary:
            q = npx.rotary_embedding(q, self._heads, self._theta)
            k = npx.rotary_embedding(k, self._kv_heads, self._theta)
        return q, k, v

    def _output(self, out, x):
        if self._gate:
            out = out * npx.sigmoid(self.gate_proj(x))
        return self.out_proj(out)

    def forward(self, x):
        from ...ops.attention import multi_head_attention
        q, k, v = self._qkv(x)
        out = multi_head_attention(q, k, v, self._heads, causal=True,
                                   kv_heads=self._kv_heads,
                                   window=self._window)
        return self._output(out, x)


def _amp_operands(*arrays):
    """Raw arrays in AMP's type where it is on (what a ``Dense`` hands
    its product), else as they are."""
    from ... import amp
    if not amp.is_active():
        return arrays
    return tuple(a.astype(amp.target_dtype()) for a in arrays)


class SparseIndexer(HybridBlock):
    """The indexer of a learned sparse attention (DeepSeek-V3.2's
    "lightning indexer") on (batch, seq, units): ``num_heads`` small query
    heads against one key head, ``qI = x WqI``, ``kI = LayerNorm(x WkI)``,
    rotary on both, head weights ``w = x Ww / sqrt(num_heads)``, scores
    ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(head_dim)``.
    forward returns ``(scores (b, s, s) float32, selection (b, s, s)
    int8)``: each query's ``topk`` best positions ``s <= t`` (all while
    ``t < topk``; ties to the lower ``s``).

    Its input is detached: nothing upstream learns from the indexer, and
    the selection has no gradient, so the indexer's own leaves learn only
    from a loss on the scores (``nn.IndexedAttention``'s alignment loss).
    The score products take AMP's type where it is on, like a ``Dense``;
    their sums and everything after are float32.
    Counts that are no trained parameter ride the aux channel, holding
    the **last** training call's values: ``selected_pairs`` (1,) and
    ``select_grid`` (16, 16), selected pairs by sixteenth of the sequence
    of the query and of the key.
    """

    def __init__(self, units, num_heads, head_dim, topk, rope_theta=10000.0,
                 epsilon=1e-6):
        super().__init__()
        from ...ops.sparse_index import GRID
        self._heads, self._dim, self._topk = num_heads, head_dim, int(topk)
        self._theta = rope_theta
        self.query_proj = Dense(num_heads * head_dim, use_bias=False,
                                flatten=False)
        self.key_proj = Dense(head_dim, use_bias=False, flatten=False)
        self.weight_proj = Dense(num_heads, use_bias=False, flatten=False)
        self.key_norm = LayerNorm(epsilon=epsilon, in_channels=head_dim)
        self.selected_pairs = Parameter(
            "selected_pairs", grad_req="null", shape=(1,), dtype="int32",
            init="zeros")
        self.select_grid = Parameter(
            "select_grid", grad_req="null", shape=(GRID, GRID), dtype="int32",
            init="zeros")

    def forward(self, x):
        import jax

        from ... import autograd
        from ...numpy.multiarray import _invoke
        from ...ops import sparse_index
        for p in (self.selected_pairs, self.select_grid):
            if p._data is None:
                p._finish_deferred_init()
        heads, dim, topk = self._heads, self._dim, self._topk

        def scores(q, k, w):
            b, s, _ = q.shape
            q, k = _amp_operands(q, k)
            return sparse_index.index_scores(
                q.reshape(b, s, heads, dim), k, w * heads ** -0.5)

        def select(i):
            chosen = sparse_index.select_topk(i, topk)
            return (chosen,) + sparse_index.selection_counts(chosen)

        # one scope a layer each: projections, norm, rotary and scores
        # (their backward is this scope's transpose), then the selection
        with jax.named_scope("mx.dsa.index"):
            u = _invoke(jax.lax.stop_gradient, (x,), name="stop_gradient")
            q = npx.rotary_embedding(self.query_proj(u), heads, self._theta)
            k = npx.rotary_embedding(self.key_norm(self.key_proj(u)), 1,
                                     self._theta)
            index = _invoke(scores, (q, k, self.weight_proj(u)),
                            name="sparse_index_scores")
        with jax.named_scope("mx.dsa.select"):
            chosen, pairs, grid = _invoke(select, (index,),
                                          name="sparse_index_select")
        if autograd.is_training():
            self.selected_pairs.data()._rebind(pairs._data)
            self.select_grid.data()._rebind(grid._data)
        return index, chosen


class IndexedAttention(GroupedQueryAttention):
    """``GroupedQueryAttention`` whose every query reads only the keys its
    ``SparseIndexer`` selects (``topk`` of the earlier positions), the
    selection handed to the attention core as data; rotary positions on
    q and k, no gate, no window.  forward returns
    ``(output, alignment loss)``: the loss is ``mean_t KL(p_t ||
    softmax_{S_t}(I[t, :]))`` with ``p_t`` the core's own probabilities
    over the selected keys averaged over the heads — the only thing the
    indexer's leaves learn from, and nothing else learns from it.  Add it
    to the model's loss.

    Where the core ran its flash kernels (a TPU, a sequence of 512 or
    more) it hands out each head's log-sum-exp over the selected keys,
    and the loss is then one Pallas pass over q and k
    (``ops/pallas/dsa_align.py``: no head's probabilities reach HBM) for
    a sequence its blocks divide into; elsewhere (the CPU, short or
    ragged sequences) it is the XLA composition of
    ``ops/sparse_index.py``, the kernel's oracle.
    """

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 index_heads, index_dim, topk, rope_theta=10000.0,
                 epsilon=1e-5):
        super().__init__(units, num_heads, num_kv_heads, head_dim,
                         rotary=True, rope_theta=rope_theta,
                         epsilon=epsilon, gate=False)
        self.indexer = SparseIndexer(units, index_heads, index_dim, topk,
                                     rope_theta=rope_theta, epsilon=epsilon)

    def forward(self, x):
        import jax

        from ...numpy.multiarray import _invoke
        from ...ops import sparse_index
        from ...ops.attention import multi_head_attention
        heads, kv_heads = self._heads, self._kv_heads
        q, k, v = self._qkv(x)
        index, chosen = self.indexer(x)
        out, lse = multi_head_attention(q, k, v, heads, causal=True,
                                        kv_heads=kv_heads, selection=chosen,
                                        return_lse=True)

        def align(i, q_, k_, *lse_):
            # the core's operand type, so that p is what the core computed
            q_, k_ = _amp_operands(q_, k_)
            with jax.named_scope("mx.dsa.align"):
                return sparse_index.align_loss(i, chosen._data, q_, k_,
                                               heads, kv_heads, *lse_)

        loss = _invoke(align, (index, q, k) + (() if lse is None else (lse,)),
                       name="sparse_index_align")
        return self._output(out, x), loss


def _fused_ln_residual(x, h, ln, p):
    """Route ``LN(x + dropout(h))`` through the fused Pallas kernel
    (ops/pallas/ln_residual.py) when eligible, else return None.

    Gated by mx.config ``fused_ln_residual``: 'auto' engages only on TPU
    AND when dropout is live (training mode, p > 0) — the measured-win
    case; with no dropout XLA's own residual+LN fusion is faster, and the
    kernel works only via interpret=True off-TPU. 'on' forces it
    everywhere. Feature dim must be a lane multiple (128) and the
    LayerNorm must be the default last-axis one.
    """
    import jax

    from ... import autograd, config
    from ... import random as _random
    from ... import runtime as _runtime
    from ...numpy.multiarray import _invoke

    mode = config.get("fused_ln_residual")
    if mode == "off" or ln._axis not in (-1, x.ndim - 1):
        return None
    if mode == "auto" and not _runtime.on_tpu():
        return None
    if mode == "auto" and not (autograd.is_training() and float(p) > 0):
        # Measured on TPU v5lite (round 5, tools/tpu_ab.py): with dropout
        # OFF XLA's own residual+LN fusion is ~2% faster than the kernel;
        # with dropout ON the kernel wins ~5% (one VMEM pass over the
        # stream vs mask materialization + three passes). auto = only the
        # measured-win case; 'on' forces it everywhere.
        return None
    dim = x.shape[-1]
    if dim % 128 != 0:
        return None
    ch = x.shape[-1]
    for prm in (ln.gamma, ln.beta):
        if not prm._shape_known():
            prm._finish_deferred_init((ch,))
        elif prm._data is None:
            prm._finish_deferred_init()
    from ...ops.pallas.ln_residual import ln_residual_dropout

    p_eff = float(p) if autograd.is_training() else 0.0
    key = _random._next_key() if p_eff > 0 else None
    eps = ln._epsilon
    interpret = _runtime.pallas_interpret()

    def fn(x_, h_, g_, b_):
        mask = (jax.random.bernoulli(key, 1.0 - p_eff, h_.shape)
                if p_eff > 0 else None)
        return ln_residual_dropout(x_, h_, g_, b_, p=p_eff, mask=mask,
                                   eps=eps, interpret=interpret)

    return _invoke(fn, (x, h, ln.gamma.data(), ln.beta.data()),
                   name="fused_ln_residual")


class TransformerEncoderCell(HybridBlock):
    """One encoder layer: MHA + FFN with residuals.

    pre_norm=False (post-norm) is the BERT/original-transformer layout;
    pre_norm=True is the modern LLM layout.
    """

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, activation="gelu", pre_norm=False,
                 causal=False):
        super().__init__()
        self._pre_norm = pre_norm
        self.attention = MultiHeadAttention(units, num_heads,
                                            dropout=attention_dropout,
                                            causal=causal)
        self.attn_ln = LayerNorm()
        self.ffn = PositionwiseFFN(units, hidden_size, activation, dropout)
        self.ffn_ln = LayerNorm()
        self.dropout = Dropout(dropout) if dropout else None
        self._dropout_rate = float(dropout)

    def forward(self, x, mask=None):
        from ...parallel.mesh import constrain
        if self._pre_norm:
            h = self.attention(self.attn_ln(x), mask=mask)
            x = constrain(x + (self.dropout(h) if self.dropout else h),
                          "residual")
            h = self.ffn(self.ffn_ln(x))
            return constrain(x + h, "residual")
        h = self.attention(x, mask=mask)
        p = self._dropout_rate if self.dropout is not None else 0.0
        fused = _fused_ln_residual(x, h, self.attn_ln, p)
        if fused is not None:
            x = constrain(fused, "residual")
        else:
            x = constrain(
                self.attn_ln(x + (self.dropout(h) if self.dropout else h)),
                "residual")
        h = self.ffn(x)
        fused = _fused_ln_residual(x, h, self.ffn_ln, 0.0)
        if fused is not None:
            return constrain(fused, "residual")
        return constrain(self.ffn_ln(x + h), "residual")

    # -- KV-cache serving surface (mx.serve) ---------------------------
    # Inference-only: dropout is skipped (serving never trains) and the
    # residual stream follows the same pre/post-norm layout as forward().

    def init_cache(self, max_slots, max_seq, dtype="float32"):
        return self.attention.init_cache(max_slots, max_seq, dtype)

    def prefill(self, x, kv, slot):
        if self._pre_norm:
            h, kv = self.attention.prefill(self.attn_ln(x), kv, slot)
            x = x + h
            return x + self.ffn(self.ffn_ln(x)), kv
        h, kv = self.attention.prefill(x, kv, slot)
        x = self.attn_ln(x + h)
        return self.ffn_ln(x + self.ffn(x)), kv

    def decode_step(self, x, kv, positions, live=None):
        if self._pre_norm:
            h, kv = self.attention.decode_step(self.attn_ln(x), kv,
                                               positions, live)
            x = x + h
            return x + self.ffn(self.ffn_ln(x)), kv
        h, kv = self.attention.decode_step(x, kv, positions, live)
        x = self.attn_ln(x + h)
        return self.ffn_ln(x + self.ffn(x)), kv

    def prefill_suffix(self, x, kv, slot, start):
        if self._pre_norm:
            h, kv = self.attention.prefill_suffix(self.attn_ln(x), kv,
                                                  slot, start)
            x = x + h
            return x + self.ffn(self.ffn_ln(x)), kv
        h, kv = self.attention.prefill_suffix(x, kv, slot, start)
        x = self.attn_ln(x + h)
        return self.ffn_ln(x + self.ffn(x)), kv

    def decode_multi(self, x, kv, positions):
        if self._pre_norm:
            h, kv = self.attention.decode_multi(self.attn_ln(x), kv,
                                                positions)
            x = x + h
            return x + self.ffn(self.ffn_ln(x)), kv
        h, kv = self.attention.decode_multi(x, kv, positions)
        x = self.attn_ln(x + h)
        return self.ffn_ln(x + self.ffn(x)), kv

    def copy_cache_rows(self, kv, src_slot, src_row, dst_slot, dst_row,
                        rows):
        return self.attention.copy_cache_rows(
            kv, src_slot, src_row, dst_slot, dst_row, rows)


class TransformerDecoderCell(HybridBlock):
    """One decoder layer: causal self-attn, cross-attn, FFN (post-norm)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, activation="relu"):
        super().__init__()
        self.self_attention = MultiHeadAttention(
            units, num_heads, dropout=attention_dropout, causal=True)
        self.self_ln = LayerNorm()
        self.cross_attention = MultiHeadAttention(
            units, num_heads, dropout=attention_dropout)
        self.cross_ln = LayerNorm()
        self.ffn = PositionwiseFFN(units, hidden_size, activation, dropout)
        self.ffn_ln = LayerNorm()
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x, mem, mem_mask=None):
        h = self.self_attention(x)
        x = self.self_ln(x + (self.dropout(h) if self.dropout else h))
        h = self.cross_attention(x, mem, mem, mask=mem_mask)
        x = self.cross_ln(x + (self.dropout(h) if self.dropout else h))
        return self.ffn_ln(x + self.ffn(x))


class TransformerEncoder(HybridBlock):
    """Stack of encoder cells."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, attention_dropout=0.0, activation="gelu",
                 pre_norm=False, causal=False):
        super().__init__()
        self._layers = []
        for i in range(num_layers):
            cell = TransformerEncoderCell(
                units, hidden_size, num_heads, dropout, attention_dropout,
                activation, pre_norm, causal)
            setattr(self, f"layer{i}", cell)
            self._layers.append(cell)

    def forward(self, x, mask=None):
        for cell in self._layers:
            x = cell(x, mask=mask)
        return x

    # -- KV-cache serving surface (mx.serve) ---------------------------

    def init_cache(self, max_slots, max_seq, dtype="float32"):
        """One (k, v) pair per layer — the whole decode footprint,
        allocated once and donated across steps by the serve engine."""
        return [cell.init_cache(max_slots, max_seq, dtype)
                for cell in self._layers]

    def prefill(self, x, caches, slot):
        out = []
        for cell, kv in zip(self._layers, caches):
            x, kv = cell.prefill(x, kv, slot)
            out.append(kv)
        return x, out

    def decode_step(self, x, caches, positions, live=None):
        out = []
        for cell, kv in zip(self._layers, caches):
            x, kv = cell.decode_step(x, kv, positions, live)
            out.append(kv)
        return x, out

    def prefill_suffix(self, x, caches, slot, start):
        out = []
        for cell, kv in zip(self._layers, caches):
            x, kv = cell.prefill_suffix(x, kv, slot, start)
            out.append(kv)
        return x, out

    def decode_multi(self, x, caches, positions):
        out = []
        for cell, kv in zip(self._layers, caches):
            x, kv = cell.decode_multi(x, kv, positions)
            out.append(kv)
        return x, out

    def copy_cache_rows(self, caches, src_slot, src_row, dst_slot,
                        dst_row, rows):
        return [cell.copy_cache_rows(kv, src_slot, src_row, dst_slot,
                                     dst_row, rows)
                for cell, kv in zip(self._layers, caches)]


def valid_length_mask(valid_length, seq_len):
    """(batch,) valid lengths → (batch, 1, 1, seq) attention mask, the
    npx.sequence_mask convention lifted to attention scores."""
    ar = np.arange(seq_len).reshape(1, 1, 1, seq_len)
    return ar < valid_length.reshape(-1, 1, 1, 1)


def positional_encoding(seq_len, units, dtype="float32"):
    """Sinusoidal position table (batch-free, (seq, units))."""
    import numpy as onp
    pos = onp.arange(seq_len)[:, None]
    dim = onp.arange((units + 1) // 2)[None]
    angle = pos / onp.power(10000.0, 2 * dim / units)
    table = onp.zeros((seq_len, units), dtype=dtype)
    table[:, 0::2] = onp.sin(angle)
    table[:, 1::2] = onp.cos(angle[:, : units // 2])
    return np.array(table)


class LatentAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2's MLA) on (batch, seq,
    units), causal, no biases.  Queries and keys/values are projected
    through low-rank latents with an RMSNorm each:
    ``c_q = RMS(x W_qa)`` (``q_lora_rank``), ``q = c_q W_qb``, a head
    ``(qk_nope_head_dim | qk_rope_head_dim)``;
    ``[c_kv | k_r] = x W_kva`` (``kv_lora_rank | qk_rope_head_dim``),
    ``[k_nope | v] = RMS(c_kv) W_kvb``, a head
    ``(qk_nope_head_dim | v_head_dim)``.  The rotary embedding turns
    each head's ``rope`` part of q and the **one** ``k_r``, which every
    head's key then carries: ``k_h = [k_nope_h | k_r]``.  Scores are
    scaled by the whole query head's width.

    The plain form: K is assembled in HBM with ``k_r`` repeated a head,
    and the core is ``ops.attention.multi_head_attention`` with as many
    KV heads as query heads — the flash kernels on a TPU, the XLA
    composition elsewhere.  The core takes one head width, so
    ``v_head_dim`` has to equal ``qk_nope_head_dim + qk_rope_head_dim``.
    Training path only: no latent cache, no absorbed decode form.

    Scopes: ``mx.mla`` round the body, ``mx.mla.assemble`` round what
    exists only because the keys are latent (the rotary on the ``rope``
    parts, the split of ``W_kvb``'s output, the repeat of ``k_r`` and the
    concatenations).
    """

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, epsilon=1e-5):
        super().__init__()
        if v_head_dim != qk_nope_head_dim + qk_rope_head_dim:
            raise ValueError(
                f"value heads of {v_head_dim} beside query / key heads of "
                f"{qk_nope_head_dim} + {qk_rope_head_dim}: the attention "
                "core takes one head width")
        self._heads, self._kv_rank = num_heads, kv_lora_rank
        self._nope, self._rope, self._theta = (qk_nope_head_dim,
                                               qk_rope_head_dim, rope_theta)

        def proj(n):
            return Dense(n, use_bias=False, flatten=False)

        self.q_a_proj = proj(q_lora_rank)
        self.q_a_norm = RMSNorm(epsilon, in_channels=q_lora_rank)
        self.q_b_proj = proj(num_heads * v_head_dim)
        self.kv_a_proj = proj(kv_lora_rank + qk_rope_head_dim)
        self.kv_a_norm = RMSNorm(epsilon, in_channels=kv_lora_rank)
        self.kv_b_proj = proj(num_heads * (qk_nope_head_dim + v_head_dim))
        self.out_proj = proj(units)

    def forward(self, x):
        import jax

        from ...ops.attention import multi_head_attention
        heads, nope, rope = self._heads, self._nope, self._rope
        b, s, _ = x.shape
        with jax.named_scope("mx.mla"):
            q = self.q_b_proj(self.q_a_norm(self.q_a_proj(x)))
            kv_a = self.kv_a_proj(x)
            kv = self.kv_b_proj(self.kv_a_norm(kv_a[..., :self._kv_rank]))
            with jax.named_scope("mx.mla.assemble"):
                q = q.reshape(b, s, heads, -1)
                kv = kv.reshape(b, s, heads, -1)
                q_r = npx.rotary_embedding(
                    q[..., nope:].reshape(b, s, -1), heads, self._theta)
                k_r = npx.rotary_embedding(kv_a[..., self._kv_rank:], 1,
                                           self._theta)
                q = np.concatenate(
                    [q[..., :nope], q_r.reshape(b, s, heads, rope)], axis=-1)
                k = np.concatenate(
                    [kv[..., :nope], np.broadcast_to(
                        k_r.reshape(b, s, 1, rope), (b, s, heads, rope))],
                    axis=-1)
                q, k, v = (t.reshape(b, s, -1)
                           for t in (q, k, kv[..., nope:]))
            out = multi_head_attention(q, k, v, heads, causal=True)
            return self.out_proj(out)
