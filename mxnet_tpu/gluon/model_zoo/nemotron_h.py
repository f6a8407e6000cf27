"""Hybrid state-space / attention / expert decoder family (the
``nemotron_h`` layout: Nemotron-H, Nemotron-3).

Every layer is one norm and one sublayer, ``x + F(RMSNorm(x))``, and
``pattern`` — the family's ``hybrid_override_pattern`` — gives each
layer's ``F`` in one character: ``M`` a Mamba-2 mixer
(``nn.Mamba2Mixer``), ``*`` causal grouped-query attention with no
positions, no q/k norm and no gate, ``E`` a sigmoid-routed expert layer
with a shared expert (``nn.RoutedExperts``) whose experts are squared-ReLU
feed-forwards of two matrices, of which this process holds the experts it
is told (``held_experts``): one chip's share of an expert-parallel job,
with no exchange between shares here.  Embeddings are not scaled; a final
RMSNorm; the head is its own matrix.

Training path only.  Serving a mixer needs what the cache contract does
not have yet (``ROADMAP.md`` M9): a layer's recurrent state ``(heads,
head_dim, state)`` and the last ``conv_kernel - 1`` inputs of its
convolution as cache entries beside attention's keys and values, a
prefill that hands both on, and a decode step that advances them.
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import (Dense, Embedding, GroupedQueryAttention, Mamba2Mixer,
                  RMSNorm, RoutedExperts)

__all__ = ["NemotronHModel", "NemotronHForCausalLM"]


class NemotronHLayer(HybridBlock):
    """``x + mixer(norm(x))``: ``mixer`` is the layer's one sublayer."""

    def __init__(self, units, mixer, epsilon=1e-5):
        super().__init__()
        self.norm = RMSNorm(epsilon, in_channels=units)
        self.mixer = mixer

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(HybridBlock):
    """forward(inputs (b, s) int) -> hidden states (b, s, units).

    ``pattern`` has one of ``M`` / ``*`` / ``E`` a layer;
    ``held_experts = (lo, hi)`` and ``rows_bound`` are this share's
    experts and its static bound on the rows they are handed in one
    call."""

    def __init__(self, vocab_size, units, pattern, num_heads, num_kv_heads,
                 head_dim, mamba_num_heads, mamba_head_dim, num_groups,
                 state_size, num_experts, num_experts_per_tok,
                 expert_hidden_size, shared_hidden_size, held_experts,
                 rows_bound, route_scale=1.0, conv_kernel=4, chunk_size=128,
                 epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        makers = {
            "M": lambda: Mamba2Mixer(
                units, mamba_num_heads, mamba_head_dim, num_groups,
                state_size, conv_kernel=conv_kernel, chunk_size=chunk_size,
                epsilon=epsilon, time_step_min=time_step_min,
                time_step_max=time_step_max,
                time_step_floor=time_step_floor),
            "*": lambda: GroupedQueryAttention(
                units, num_heads, num_kv_heads, head_dim, epsilon=epsilon,
                gate=False, qk_norm=False),
            "E": lambda: RoutedExperts(
                units, expert_hidden_size, num_experts, num_experts_per_tok,
                held=held_experts, rows_bound=rows_bound,
                shared_hidden_size=shared_hidden_size,
                route_scale=route_scale, activation="relu2"),
        }
        unknown = set(pattern) - set(makers)
        if unknown:
            raise ValueError(f"pattern {pattern!r} has layer kinds "
                             f"{sorted(unknown)}; known: M, *, E")
        self.word_embed = Embedding(vocab_size, units)
        self._layers = []
        for i, kind in enumerate(pattern):
            cell = NemotronHLayer(units, makers[kind](), epsilon)
            setattr(self, f"layer{i}", cell)
            self._layers.append(cell)
        self.final_norm = RMSNorm(epsilon, in_channels=units)

    def forward(self, inputs):
        x = self.word_embed(inputs)
        for cell in self._layers:
            x = cell(x)
        return self.final_norm(x)


class NemotronHForCausalLM(HybridBlock):
    """Next-token head over NemotronHModel, untied. forward -> logits."""

    def __init__(self, backbone=None, **kwargs):
        super().__init__()
        self.backbone = backbone if backbone is not None \
            else NemotronHModel(**kwargs)
        self.lm_head = Dense(self.backbone.word_embed._input_dim,
                             use_bias=False, flatten=False)

    def forward(self, inputs):
        return self.lm_head(self.backbone(inputs))
