"""Sparse-attention mixture-of-experts decoder (the language model of the
``KeyeVL2`` layout: Keye-VL-2.0-30B-A3B).

A pre-norm decoder with two norms a layer: ``h = x + Attn(RMS(x))``,
``x' = h + Experts(RMS(h))``.  Attention is grouped-query with RMSNorm on
q and k and rotary positions on every layer, and reads, for each query,
only the ``topk`` earlier positions its indexer scores highest
(``nn.IndexedAttention``: no gate, no window).  Every layer's
feed-forward is a softmax-routed expert layer without a shared expert
(``nn.RoutedExperts``), of which this process holds the experts it is told
(``held_experts``): one chip's share of an expert-parallel job, with no
exchange between shares here.  The head is its own matrix.

The indexers learn from their own loss and nothing else does: the model
returns it beside the hidden states (``forward -> (logits, index loss)``),
summed over the layers, to be added to the language-model loss.

Text only: the three position streams of the layout's M-RoPE coincide on
text, so the rotary embedding is the plain one; there is no vision tower
here.  Training path only (no KV-cache surface: the serve engine keeps no
cache for the indexer's keys).
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import Dense, Embedding, IndexedAttention, RMSNorm, RoutedExperts

__all__ = ["KeyeModel", "KeyeForCausalLM"]


class KeyeDecoderLayer(HybridBlock):
    """One layer: two norms, indexed grouped-query attention, routed
    experts (``experts``: the arguments of ``nn.RoutedExperts``).
    forward -> (hidden states, the layer's index loss)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, experts,
                 index_heads, index_dim, topk, rope_theta=1e7, epsilon=1e-6):
        super().__init__()
        self.input_norm = RMSNorm(epsilon, in_channels=units)
        self.attention = IndexedAttention(
            units, num_heads, num_kv_heads, head_dim,
            index_heads=index_heads, index_dim=index_dim, topk=topk,
            rope_theta=rope_theta, epsilon=epsilon)
        self.post_attn_norm = RMSNorm(epsilon, in_channels=units)
        self.mlp = RoutedExperts(units, score_func="softmax", **experts)

    def forward(self, x):
        out, index_loss = self.attention(self.input_norm(x))
        h = x + out
        return h + self.mlp(self.post_attn_norm(h)), index_loss


class KeyeModel(HybridBlock):
    """forward(inputs (b, s) int) -> (hidden states (b, s, units), index
    loss).  ``held_experts = (lo, hi)`` and ``rows_bound`` are this
    share's experts and its static bound on the rows they are handed in
    one call."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 num_kv_heads, head_dim, num_experts, num_experts_per_tok,
                 expert_hidden_size, held_experts, rows_bound,
                 index_heads=16, index_dim=64, topk=2048, rope_theta=1e7,
                 epsilon=1e-6):
        super().__init__()
        self.word_embed = Embedding(vocab_size, units)
        self._layers = []
        for i in range(num_layers):
            cell = KeyeDecoderLayer(
                units, num_heads, num_kv_heads, head_dim,
                experts=dict(
                    hidden_size=expert_hidden_size, num_experts=num_experts,
                    num_experts_per_tok=num_experts_per_tok,
                    held=held_experts, rows_bound=rows_bound),
                index_heads=index_heads, index_dim=index_dim, topk=topk,
                rope_theta=rope_theta, epsilon=epsilon)
            setattr(self, f"layer{i}", cell)
            self._layers.append(cell)
        self.final_norm = RMSNorm(epsilon, in_channels=units)

    def forward(self, inputs):
        x = self.word_embed(inputs)
        index_loss = 0.0
        for cell in self._layers:
            x, one = cell(x)
            index_loss = index_loss + one
        return self.final_norm(x), index_loss


class KeyeForCausalLM(HybridBlock):
    """Next-token head over KeyeModel, untied.  forward -> (logits, index
    loss): train on ``xent(logits, labels) + index loss``."""

    def __init__(self, backbone=None, **kwargs):
        super().__init__()
        self.backbone = backbone if backbone is not None \
            else KeyeModel(**kwargs)
        self.lm_head = Dense(self.backbone.word_embed._input_dim,
                             use_bias=False, flatten=False)

    def forward(self, inputs):
        hidden, index_loss = self.backbone(inputs)
        return self.lm_head(hidden), index_loss
