"""What GPT-2's arithmetic needs, from its shapes.

Matrix multiplications only (2 x m x n x k each); causal attention is
counted at half the square; the backward pass is twice the forward;
recomputation (remat, the flash kernel's re-made scores) is not needed
work and is not counted.  Embedding look-ups, LayerNorm, GELU and the
softmax are left out: they are under 1 % and leaving them out can only
make a share smaller.
"""
from __future__ import annotations


def forward_flops_per_token(cfg, seq):
    e, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 8 * e * e + 4 * e * f + 2 * seq * e
    return cfg["n_layer"] * per_layer + 2 * e * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward, per token of a sequence of ``seq`` tokens."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_train_flops(cfg, batch, seq):
    """One update's needed attention-core work: 2 products forward
    (QK^T, PV) and 4 backward (dV, dP, dQ, dK), each 2*s*s*d per head,
    halved for the causal mask."""
    head = cfg["n_embd"] // cfg["n_head"]
    per_head = 6 * 2 * seq * seq * head // 2
    return cfg["n_layer"] * batch * cfg["n_head"] * per_head


def flash_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed attention-core traffic at the published head
    size (the 64 -> 128 lane padding is not needed): forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    per_head = (4 + 8) * seq * (cfg["n_embd"] // cfg["n_head"]) * itemsize
    return cfg["n_layer"] * batch * cfg["n_head"] * per_head
