"""What the ouro family's arithmetic needs, from its shapes.

Matrix multiplications only (2 x m x n x k each); the backward pass is
twice the forward; recomputation (a layer application made again behind
its boundary, the flash kernel's re-made scores, the chunked head's
re-made logits) is not needed work and is not counted.  Embedding
look-ups, norms, rotary, SiLU, the softmaxes and the exit distribution
are left out: they are under 1 % and leaving them out can only make a
share smaller.

A layer is counted once an application: ``total_ut_steps`` x the layers
held.  Attention's core is counted at half the square (causal, no
window).  The head is counted once an exit, the gate's ``hidden -> 1``
product once an exit that reads it (the last exit takes what is left).
"""
from __future__ import annotations


def layer_flops_per_token(cfg, seq):
    """One layer application, forward: four attention projections, the
    core at half the square, three feed-forward products."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * e * (2 * hq + 2 * hk) + 4 * hq * (seq / 2) + 6 * e * f


def head_flops_per_token(cfg):
    """One exit's head product, forward."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def forward_flops_per_token(cfg, seq):
    steps = cfg["total_ut_steps"]
    return steps * cfg["num_hidden_layers"] * layer_flops_per_token(cfg, seq) \
        + steps * head_flops_per_token(cfg) \
        + (steps - 1) * 2 * cfg["hidden_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward, per token of a sequence of ``seq`` tokens."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_train_flops(cfg, batch, seq):
    """One update's needed attention-core work: 2 products forward
    (QK^T, PV) and 4 backward (dV, dP, dQ, dK), each 2*d a (query, key)
    pair and head, at half the square, once a layer application."""
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    return apps * batch * cfg["num_attention_heads"] * seq * (seq / 2) \
        * 6 * 2 * cfg["head_dim"]


def flash_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed attention-core traffic: a query head's q, o
    (forward) and q, o, do, dq (backward); a KV head's k, v (forward)
    and k, v, dk, dv (backward), each once a layer application."""
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    rows = 6 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    return apps * batch * rows * seq * cfg["head_dim"] * itemsize


def exit_head_train_flops(cfg, batch, seq):
    """One update's needed work of the exits' heads: the logits' product
    forward, ``dz`` and ``dW`` backward, once an exit."""
    return 3 * cfg["total_ut_steps"] * batch * seq \
        * head_flops_per_token(cfg)


def exit_head_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed traffic of the exits' heads: the head's
    matrix read forward and backward and its gradient written, each once
    for all exits; every exit's states read forward and backward and
    their gradient written.  The logits are not needed traffic: a head
    that keeps a block of them on the chip reads and writes none."""
    e = cfg["hidden_size"]
    return 3 * itemsize * (cfg["vocab_size"] * e
                           + cfg["total_ut_steps"] * batch * seq * e)
