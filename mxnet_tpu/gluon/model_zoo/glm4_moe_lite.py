"""Latent-attention mixture-of-experts decoder family (the
``glm4_moe_lite`` layout: GLM-4.7-Flash; DeepSeek-V3's block at a small
width).

A pre-norm decoder with two norms a layer: ``h = x + Attn(RMS(x))``,
``x' = h + FF(RMS(h))``.  Attention is multi-head latent attention
(``nn.LatentAttention``: queries and keys/values through low-rank
latents, one rotary key shared by all heads).  The first
``num_dense_layers`` layers have a SwiGLU feed-forward, the rest a
sigmoid-routed expert layer with a shared expert (``nn.RoutedExperts``:
a selection bias, weights normalised over the selected and scaled), of
which this process holds the experts it is told (``held_experts``): one
chip's share of an expert-parallel job, with no exchange between shares
here.  Embeddings are not scaled; a final RMSNorm; the head is its own
matrix.

With ``num_nextn_predict_layers=1`` the model also trains DeepSeek-V3's
multi-token-prediction depth: from the last layer's output ``h_i`` (before
the final norm) and the embedding of the next token,
``h'_i = W_eh [RMS(Emb(t_{i+1})) ; RMS(h_i)]``, one more expert decoder
layer and a norm of its own, then **the main head**; it predicts
``t_{i+2}``.  The embedding and the head are the main model's, so they
learn from both terms.  ``forward`` then returns ``(logits, mtp_logits)``
and ``next_token_loss`` adds the two cross-entropies.  Inside a step's
fixed shapes the next token of a row's last position is not among the
inputs: the inputs are rolled by one, and the loss leaves that position
out.

Training path only: no latent KV cache and no absorbed decode form, and
the serve engine has no draft head to hand the second depth to.
"""
from __future__ import annotations

from ... import numpy as np
from ..block import HybridBlock
from ..nn import (Dense, Embedding, GatedFFN, LatentAttention, RMSNorm,
                  RoutedExperts)

__all__ = ["Glm4MoeLiteModel", "Glm4MoeLiteForCausalLM", "next_token_loss"]


class Glm4MoeLiteDecoderLayer(HybridBlock):
    """One layer: two norms, latent attention (``attention``: the
    arguments of ``nn.LatentAttention``), and a dense or routed
    feed-forward (``experts`` None or the arguments of
    ``nn.RoutedExperts``)."""

    def __init__(self, units, attention, hidden_size, epsilon=1e-5,
                 experts=None):
        super().__init__()
        self.input_norm = RMSNorm(epsilon, in_channels=units)
        self.attention = LatentAttention(units, epsilon=epsilon, **attention)
        self.post_attn_norm = RMSNorm(epsilon, in_channels=units)
        self.mlp = GatedFFN(units, hidden_size) if experts is None \
            else RoutedExperts(units, **experts)

    def forward(self, x):
        h = x + self.attention(self.input_norm(x))
        return h + self.mlp(self.post_attn_norm(h))


class Glm4MoeLiteModel(HybridBlock):
    """forward(inputs (b, s) int) -> hidden states (b, s, units).

    ``held_experts = (lo, hi)`` and ``rows_bound`` are this share's
    experts and its static bound on the rows they are handed in one
    call."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, hidden_size, num_dense_layers,
                 num_experts, num_experts_per_tok, expert_hidden_size,
                 shared_hidden_size, held_experts, rows_bound,
                 route_scale=1.0, rope_theta=10000.0, epsilon=1e-5):
        super().__init__()
        self._units, self._epsilon = units, epsilon
        self._attention = dict(
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta)
        self._experts = dict(
            hidden_size=expert_hidden_size, num_experts=num_experts,
            num_experts_per_tok=num_experts_per_tok, held=held_experts,
            rows_bound=rows_bound, shared_hidden_size=shared_hidden_size,
            route_scale=route_scale)
        self._hidden_size = hidden_size
        self.word_embed = Embedding(vocab_size, units)
        self._layers = []
        for i in range(num_layers):
            cell = self.decoder_layer(dense=i < num_dense_layers)
            setattr(self, f"layer{i}", cell)
            self._layers.append(cell)
        self.final_norm = RMSNorm(epsilon, in_channels=units)

    def decoder_layer(self, dense=False):
        """A new layer at this model's sizes, of the dense or the expert
        kind."""
        return Glm4MoeLiteDecoderLayer(
            self._units, self._attention, self._hidden_size, self._epsilon,
            experts=None if dense else self._experts)

    def hidden_states(self, inputs):
        """The last layer's output, before the final norm."""
        x = self.word_embed(inputs)
        for cell in self._layers:
            x = cell(x)
        return x

    def forward(self, inputs):
        return self.final_norm(self.hidden_states(inputs))


class Glm4MoeLiteNextN(HybridBlock):
    """The multi-token-prediction depth: forward(hidden states before the
    final norm, the next tokens' embeddings) -> hidden states for the
    main head, normed by this module's own norm."""

    def __init__(self, backbone):
        super().__init__()
        units, epsilon = backbone._units, backbone._epsilon
        self.embed_norm = RMSNorm(epsilon, in_channels=units)
        self.hidden_norm = RMSNorm(epsilon, in_channels=units)
        self.eh_proj = Dense(units, use_bias=False, flatten=False)
        self.layer = backbone.decoder_layer()
        self.final_norm = RMSNorm(epsilon, in_channels=units)

    def forward(self, hidden, next_embed):
        x = self.eh_proj(np.concatenate(
            [self.embed_norm(next_embed), self.hidden_norm(hidden)],
            axis=-1))
        return self.final_norm(self.layer(x))


class Glm4MoeLiteForCausalLM(HybridBlock):
    """Next-token head over Glm4MoeLiteModel, untied.  forward -> logits,
    or with ``num_nextn_predict_layers=1`` ``(logits, mtp_logits)``:
    position ``i`` of the second predicts token ``i + 2`` (its last
    position has no input and no target: ``next_token_loss`` leaves it
    out).  With 0 no parameter of the second depth exists."""

    def __init__(self, backbone=None, num_nextn_predict_layers=0, **kwargs):
        super().__init__()
        if num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"{num_nextn_predict_layers} prediction depths:"
                             " the family trains none or one")
        self.backbone = backbone if backbone is not None \
            else Glm4MoeLiteModel(**kwargs)
        self.lm_head = Dense(self.backbone.word_embed._input_dim,
                             use_bias=False, flatten=False)
        if num_nextn_predict_layers:
            self.mtp = Glm4MoeLiteNextN(self.backbone)
        self._mtp = bool(num_nextn_predict_layers)

    def forward(self, inputs):
        if not self._mtp:
            return self.lm_head(self.backbone(inputs))
        import jax
        hidden = self.backbone.hidden_states(inputs)
        logits = self.lm_head(self.backbone.final_norm(hidden))
        with jax.named_scope("mx.mtp"):
            following = self.backbone.word_embed(np.roll(inputs, -1, axis=1))
            return logits, self.lm_head(self.mtp(hidden, following))


def next_token_loss(out, labels, mtp_weight=0.3):
    """Mean token cross-entropy of ``Glm4MoeLiteForCausalLM``'s output
    against ``labels`` (batch, seq) = the inputs shifted by one; where the
    output is ``(logits, mtp_logits)``, plus ``mtp_weight`` times the
    second depth's: position ``i`` against ``labels[:, i + 1]``, a mean
    over the ``seq - 1`` positions that have a target.  Raw jax values
    (a ``ShardedTrainStep`` loss function)."""
    import jax.numpy as jnp

    from ...ops.xent import sparse_softmax_xent
    if not isinstance(out, (tuple, list)):
        return jnp.mean(sparse_softmax_xent(out, labels))
    logits, mtp_logits = out
    seq = labels.shape[1]
    second = sparse_softmax_xent(mtp_logits, jnp.roll(labels, -1, axis=1))
    has_target = jnp.arange(seq) < seq - 1
    return jnp.mean(sparse_softmax_xent(logits, labels)) \
        + mtp_weight * jnp.sum(jnp.where(has_target, second, 0.0)) \
        / (labels.shape[0] * (seq - 1))
