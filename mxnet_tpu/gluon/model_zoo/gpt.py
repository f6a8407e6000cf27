"""Decoder-only LLM family (GPT-2 layout).

Reference parity: the reference's transformer story is the fused attention
ops (src/operator/contrib/transformer.cc:675-828) consumed by gluon-nlp
models (model/gpt.py: GPT2Model/gpt2_117m/gpt2_345m). This is that family
TPU-native: pre-norm causal blocks whose attention routes through the
Pallas flash kernel at long sequence (ops/attention.py — no (s, s) score
materialization in HBM), learned positions, tied LM head; shard with
mxnet_tpu.parallel (tp specs on the projections, sp ring for very long
context).
"""
from __future__ import annotations

from ... import numpy as np
from ..block import HybridBlock
from ..nn import Dropout, Embedding, LayerNorm
from ..nn.transformer import TransformerEncoder

__all__ = ["GPTModel", "GPTForCausalLM", "gpt2_124m", "gpt2_355m"]


class GPTModel(HybridBlock):
    """Causal pre-norm transformer decoder stack (GPT-2 layout).

    forward(inputs (b, s) int) -> hidden states (b, s, units)
    """

    def __init__(self, vocab_size=50257, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=1024,
                 dropout=0.1, embed_dropout=0.1):
        super().__init__()
        self._units = units
        self._max_length = max_length
        self.word_embed = Embedding(vocab_size, units)
        self.position_embed = Embedding(max_length, units)
        self.embed_dropout = Dropout(embed_dropout) if embed_dropout else None
        self.decoder = TransformerEncoder(
            num_layers, units, hidden_size, num_heads, dropout=dropout,
            attention_dropout=dropout, activation="gelu", pre_norm=True,
            causal=True)
        self.final_ln = LayerNorm(epsilon=1e-5)

    def forward(self, inputs):
        b, s = inputs.shape
        pos = np.arange(s, dtype="int32").reshape(1, s)
        x = self.word_embed(inputs) + self.position_embed(pos)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        return self.final_ln(self.decoder(x))

    # -- KV-cache serving surface (mx.serve) ---------------------------

    @property
    def max_length(self):
        return self._max_length

    def init_cache(self, max_slots, max_seq=None, dtype="float32"):
        """Fixed-footprint decode cache: per layer one
        (max_slots, max_seq, units) K and V pair."""
        max_seq = self._max_length if max_seq is None else max_seq
        if max_seq > self._max_length:
            raise ValueError(
                f"max_seq {max_seq} exceeds the learned position table "
                f"({self._max_length})")
        return self.decoder.init_cache(max_slots, max_seq, dtype)

    def prefill(self, inputs, caches, slot):
        """Run one prompt (1, L) through the stack, writing K/V into
        cache slot ``slot``. Returns (hidden (1, L, units), caches)."""
        b, s = inputs.shape
        pos = np.arange(s, dtype="int32").reshape(1, s)
        x = self.word_embed(inputs) + self.position_embed(pos)
        x, caches = self.decoder.prefill(x, caches, slot)
        return self.final_ln(x), caches

    def decode_step(self, tokens, caches, positions, live=None):
        """Advance every slot one token: tokens (slots, 1) int32,
        positions (slots,) int32 cache rows, live (slots,) bool the slots
        whose output is read. Returns (hidden (slots, 1, units), caches)."""
        x = self.word_embed(tokens) \
            + self.position_embed(positions.reshape(-1, 1))
        x, caches = self.decoder.decode_step(x, caches, positions, live)
        return self.final_ln(x), caches

    def prefill_suffix(self, inputs, caches, slot, start):
        """Prefix-cache suffix prefill: ``inputs`` (1, Ls) is the
        prompt suffix; rows [0, start) of cache slot ``slot`` already
        hold a copied prefix, so positions offset by ``start`` and the
        suffix attends the cached rows.  Returns
        (hidden (1, Ls, units), caches)."""
        b, s = inputs.shape
        pos = np.arange(s, dtype="int32").reshape(1, s) + start
        pos = np.minimum(pos, self._max_length - 1)
        x = self.word_embed(inputs) + self.position_embed(pos)
        x, caches = self.decoder.prefill_suffix(x, caches, slot, start)
        return self.final_ln(x), caches

    def decode_multi(self, tokens, caches, positions):
        """Advance every slot t tokens at once (the speculative-decode
        verify): tokens (slots, t) int32, slot i's token j landing at
        cache row positions[i] + j.  Returns
        (hidden (slots, t, units), caches)."""
        n, t = tokens.shape
        pos = np.arange(t, dtype="int32").reshape(1, t) \
            + positions.reshape(-1, 1)
        pos = np.minimum(pos, self._max_length - 1)
        x = self.word_embed(tokens) + self.position_embed(pos)
        x, caches = self.decoder.decode_multi(x, caches, positions)
        return self.final_ln(x), caches

    def copy_cache_rows(self, caches, src_slot, src_row, dst_slot,
                        dst_row, rows):
        """Copy ``rows`` KV rows between slots in every layer's cache —
        the prefix-cache block-copy surface."""
        return self.decoder.copy_cache_rows(
            caches, src_slot, src_row, dst_slot, dst_row, rows)


class GPTForCausalLM(HybridBlock):
    """Next-token LM head over GPTModel, weight-tied to the embedding.

    forward -> logits (b, s, vocab)
    """

    def __init__(self, backbone=None, **kwargs):
        super().__init__()
        self.backbone = backbone if backbone is not None \
            else GPTModel(**kwargs)

    def forward(self, inputs):
        h = self.backbone(inputs)
        w = self.backbone.word_embed.weight.data()
        return np.dot(h, w.T)

    # -- KV-cache serving surface (mx.serve) ---------------------------

    @property
    def max_length(self):
        return self.backbone.max_length

    def init_cache(self, max_slots, max_seq=None, dtype="float32"):
        return self.backbone.init_cache(max_slots, max_seq, dtype)

    def prefill(self, inputs, caches, slot):
        h, caches = self.backbone.prefill(inputs, caches, slot)
        w = self.backbone.word_embed.weight.data()
        return np.dot(h, w.T), caches

    def decode_step(self, tokens, caches, positions, live=None):
        h, caches = self.backbone.decode_step(tokens, caches, positions,
                                              live)
        w = self.backbone.word_embed.weight.data()
        return np.dot(h[:, 0], w.T), caches

    def prefill_suffix(self, inputs, caches, slot, start):
        h, caches = self.backbone.prefill_suffix(inputs, caches, slot,
                                                 start)
        w = self.backbone.word_embed.weight.data()
        return np.dot(h, w.T), caches

    def decode_multi(self, tokens, caches, positions):
        h, caches = self.backbone.decode_multi(tokens, caches, positions)
        w = self.backbone.word_embed.weight.data()
        return np.dot(h, w.T), caches

    def copy_cache_rows(self, caches, src_slot, src_row, dst_slot,
                        dst_row, rows):
        return self.backbone.copy_cache_rows(
            caches, src_slot, src_row, dst_slot, dst_row, rows)


def gpt2_124m(vocab_size=50257, **kwargs):
    """GPT-2 small: 12 layers, 768 units, 12 heads (117M-class)."""
    return GPTModel(vocab_size=vocab_size, units=768, hidden_size=3072,
                    num_layers=12, num_heads=12, **kwargs)


def gpt2_355m(vocab_size=50257, **kwargs):
    """GPT-2 medium: 24 layers, 1024 units, 16 heads (345M-class)."""
    return GPTModel(vocab_size=vocab_size, units=1024, hidden_size=4096,
                    num_layers=24, num_heads=16, **kwargs)
