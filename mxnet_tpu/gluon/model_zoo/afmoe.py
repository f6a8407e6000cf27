"""Sparse decoder-only LLM family (the ``afmoe`` layout: Trinity).

A pre- and post-norm decoder: per layer
``h = x + RMS(Attn(RMS(x)))``, ``x' = h + RMS(FF(RMS(h)))``.  Attention is
grouped-query with RMSNorm on q and k and a sigmoid gate on its output;
``sliding_attention`` layers carry rotary positions and a causal window,
``full_attention`` layers neither.  The first ``num_dense_layers`` layers
have a SwiGLU feed-forward, the rest a sigmoid-routed expert layer with a
shared expert (``nn.RoutedExperts``), of which this process holds the
experts it is told (``held_experts``): one chip's share of an
expert-parallel job, with no exchange between shares here.  Embeddings are
scaled by sqrt(units); the head is its own matrix.

Training path only: no KV-cache surface yet (window layers need a ring
cache the serve engine does not have).
"""
from __future__ import annotations

import math

from ..block import HybridBlock
from ..nn import (Dense, Embedding, GatedFFN, GroupedQueryAttention,
                  RMSNorm, RoutedExperts)

__all__ = ["AfmoeModel", "AfmoeForCausalLM"]


class AfmoeDecoderLayer(HybridBlock):
    """One layer: four norms, gated grouped-query attention, and a dense
    or routed feed-forward (``experts`` None or the arguments of
    ``nn.RoutedExperts``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 hidden_size, window=None, rotary=False, rope_theta=10000.0,
                 epsilon=1e-5, experts=None):
        super().__init__()
        self.input_norm = RMSNorm(epsilon, in_channels=units)
        self.attention = GroupedQueryAttention(
            units, num_heads, num_kv_heads, head_dim, window=window,
            rotary=rotary, rope_theta=rope_theta, epsilon=epsilon)
        self.post_attn_norm = RMSNorm(epsilon, in_channels=units)
        self.pre_mlp_norm = RMSNorm(epsilon, in_channels=units)
        self.mlp = GatedFFN(units, hidden_size) if experts is None \
            else RoutedExperts(units, **experts)
        self.post_mlp_norm = RMSNorm(epsilon, in_channels=units)

    def forward(self, x):
        h = x + self.post_attn_norm(self.attention(self.input_norm(x)))
        return h + self.post_mlp_norm(self.mlp(self.pre_mlp_norm(h)))


class AfmoeModel(HybridBlock):
    """forward(inputs (b, s) int) -> hidden states (b, s, units).

    ``layer_types`` gives one of ``"sliding_attention"`` /
    ``"full_attention"`` a layer; ``held_experts = (lo, hi)`` and
    ``rows_bound`` are this share's experts and its static bound on the
    rows they are handed in one call."""

    def __init__(self, vocab_size, units, num_heads, num_kv_heads, head_dim,
                 hidden_size, layer_types, num_dense_layers, num_experts,
                 num_experts_per_tok, expert_hidden_size,
                 shared_hidden_size, held_experts, rows_bound,
                 sliding_window, route_scale=1.0, rope_theta=10000.0,
                 epsilon=1e-5):
        super().__init__()
        self._scale = math.sqrt(units)
        self.word_embed = Embedding(vocab_size, units)
        self._layers = []
        for i, kind in enumerate(layer_types):
            sliding = kind == "sliding_attention"
            experts = None if i < num_dense_layers else dict(
                hidden_size=expert_hidden_size, num_experts=num_experts,
                num_experts_per_tok=num_experts_per_tok, held=held_experts,
                rows_bound=rows_bound,
                shared_hidden_size=shared_hidden_size,
                route_scale=route_scale)
            cell = AfmoeDecoderLayer(
                units, num_heads, num_kv_heads, head_dim, hidden_size,
                window=sliding_window if sliding else None, rotary=sliding,
                rope_theta=rope_theta, epsilon=epsilon, experts=experts)
            setattr(self, f"layer{i}", cell)
            self._layers.append(cell)
        self.final_norm = RMSNorm(epsilon, in_channels=units)

    def forward(self, inputs):
        x = self.word_embed(inputs) * self._scale
        for cell in self._layers:
            x = cell(x)
        return self.final_norm(x)


class AfmoeForCausalLM(HybridBlock):
    """Next-token head over AfmoeModel, untied. forward -> logits."""

    def __init__(self, backbone=None, **kwargs):
        super().__init__()
        self.backbone = backbone if backbone is not None \
            else AfmoeModel(**kwargs)
        self.lm_head = Dense(self.backbone.word_embed._input_dim,
                             use_bias=False, flatten=False)

    def forward(self, inputs):
        return self.lm_head(self.backbone(inputs))
