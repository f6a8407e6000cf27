"""What serving GPT-2 needs, from its shapes: the operations of a prompt's
prefill and of one decoded token, and the bytes one decode step must move.

As ``flops/gpt2.py`` counts: matrix multiplications only (2 x m x n x k),
attention at the keys a query may see (a prompt's causal half; a decoded
token's own context), LayerNorm, GELU, softmax and look-ups left out.
*Needed* work only: a prefill needs the head at its last position alone
(the program computes every position's logits: not needed, not counted),
a prompt is counted at its own length, not its bucket's, and idle slots
of a decode step do no needed work.
"""
from __future__ import annotations


def _layer_matmul_flops(cfg):
    """One token through one block's four projections and two MLP
    products."""
    e, f = cfg["n_embd"], cfg["n_inner"]
    return 8 * e * e + 4 * e * f


def _head_flops(cfg):
    return 2 * cfg["n_embd"] * cfg["vocab_size"]


def prefill_flops(cfg, prompt):
    """A prompt of ``prompt`` tokens: every token through every block,
    QK^T and PV over the causal half (token i sees i + 1 keys), the
    head once."""
    e, layers = cfg["n_embd"], cfg["n_layer"]
    pairs = prompt * (prompt + 1) // 2
    return layers * (prompt * _layer_matmul_flops(cfg) + 4 * e * pairs) \
        + _head_flops(cfg)


def decode_flops(cfg, context):
    """One decoded token that attends ``context`` keys (itself
    included)."""
    e, layers = cfg["n_embd"], cfg["n_layer"]
    return layers * (_layer_matmul_flops(cfg) + 4 * e * context) \
        + _head_flops(cfg)


def request_flops(cfg, prompt, first, last):
    """Served tokens ``first`` .. ``last`` - 1 (0-based) of a request
    whose prompt has ``prompt`` tokens.  Token 0 is the prefill's;
    token j >= 1 is decoded from token j - 1 at cache row prompt + j - 1
    and attends prompt + j keys."""
    total = 0
    if first == 0 and last > 0:
        total += prefill_flops(cfg, prompt)
    for j in range(max(first, 1), last):
        total += decode_flops(cfg, prompt + j)
    return total


def weight_bytes(cfg, itemsize):
    """What one decode step must read of the weights: every block's
    matrices and vectors, the final LayerNorm and the whole tied table
    (the head is a product over all of it); of the position table only
    the rows in use, which is nothing beside these."""
    e, f, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    block = 4 * e * e + 2 * e * f + 9 * e + f      # 4 e-biases, ln x 4, fc/proj biases
    return (layers * block + cfg["vocab_size"] * e + 2 * e) * itemsize


def cache_row_bytes(cfg, itemsize):
    """One position's keys and values over all layers."""
    return cfg["n_layer"] * 2 * cfg["n_embd"] * itemsize


def decode_step_bytes(cfg, contexts, weight_itemsize, cache_itemsize):
    """One decode step over live slots whose tokens attend ``contexts``
    keys each: the weights once, every live slot's cached rows read,
    one new row a live slot written.  The rows of idle slots and past a
    slot's context need not move."""
    row = cache_row_bytes(cfg, cache_itemsize)
    return weight_bytes(cfg, weight_itemsize) \
        + row * (sum(contexts) + len(contexts))
