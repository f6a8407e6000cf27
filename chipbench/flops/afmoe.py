"""What the afmoe family's arithmetic needs, from its shapes.

Matrix multiplications only (2 x m x n x k each); the backward pass is
twice the forward; recomputation (remat, the flash kernel's re-made
scores) is not needed work and is not counted.  Embedding look-ups,
norms, rotary, SiLU, sigmoids, the softmax, the top-k and the sort are
left out: they are under 1 % and leaving them out can only make a share
smaller.

Attention's core is counted at the keys a query attends: the band on a
``sliding_attention`` layer (1792.1 keys a query at 8192 with a window
of 2048) and half the square on a ``full_attention`` one.  The routed
experts are counted at the rows this share expects: experts a token x
held / published (0.5 a token for 8 of 128 at 8 a token), here and in
``moe_experts_roofline.train``: what a run really held stays in the
step's ``aux``, which no reader sees.
"""
from __future__ import annotations


def keys_per_query(seq, window=None):
    """Mean keys a query of a causal sequence attends; a full layer is
    counted at half the square as the GPT-2 family counts it."""
    if window is None or window >= seq:
        return seq / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def _windows(cfg):
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"]]


def expected_rows_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


def forward_flops_per_token(cfg, seq):
    e, f, fm = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    n_layer, n_dense = len(cfg["layer_types"]), cfg["num_dense_layers"]
    proj = 2 * e * (3 * hq + 2 * hk)            # q, gate, out; k, v
    core = sum(4 * hq * keys_per_query(seq, w) for w in _windows(cfg))
    dense = 6 * e * f
    sparse = 2 * e * cfg["num_experts"] \
        + 6 * e * fm * cfg["num_shared_experts"] \
        + 6 * e * fm * expected_rows_per_token(cfg)
    return n_layer * proj + core + n_dense * dense \
        + (n_layer - n_dense) * sparse + 2 * e * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward, per token of a sequence of ``seq`` tokens."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_train_flops(cfg, batch, seq):
    """One update's needed attention-core work: 2 products forward
    (QK^T, PV) and 4 backward (dV, dP, dQ, dK), each 2*d a (query, key)
    pair and head, at the band on the window layers."""
    pairs = sum(seq * keys_per_query(seq, w) for w in _windows(cfg))
    return batch * cfg["num_attention_heads"] * pairs \
        * 6 * 2 * cfg["head_dim"]


def flash_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed attention-core traffic: a query head's q, o
    (forward) and q, o, do, dq (backward); a KV head's k, v (forward)
    and k, v, dk, dv (backward), each once — K and V are read once a KV
    head, not once a query head."""
    rows = 6 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    return len(cfg["layer_types"]) * batch * rows * seq \
        * cfg["head_dim"] * itemsize


def experts_train_flops(cfg, rows):
    """One expert layer's grouped products over ``rows`` rows in all:
    three products forward, six backward."""
    return 3 * 6 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_train_bytes(cfg, rows, itemsize=2):
    """One expert layer's needed traffic for them: the held experts'
    three matrices read forward, read backward and their gradient
    written; the rows' input, three hidden activations and output, each
    touched forward, backward and as a gradient."""
    e, fm = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts_held"] * 3 * e * fm
    return 3 * itemsize * (weights + rows * (2 * e + 3 * fm))
