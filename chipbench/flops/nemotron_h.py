"""What the nemotron_h family's arithmetic needs, from its shapes.

Matrix multiplications only (2 x m x n x k each); the backward pass is
twice the forward; recomputation (remat, the flash kernel's re-made
scores, the scan made again for its gradient) is not needed work and is
not counted.  Embedding look-ups, norms, the convolution's four taps,
SiLU, softplus, the exponentials of the decays, the softmax, the top-k
and the sort are left out: they are under 1 % and leaving them out can
only make a share smaller.

Attention's core is counted at half the square (causal, no window).  The
routed experts are counted at the rows this share expects: experts a
token x held / published (0.375 a token for 8 of 128 at 6 a token), two
products an expert (relu^2 has no gate matrix).  The state-space scan is
counted in its chunked form at the chunk the configuration gives, the
form every implementation takes (the recurrence token by token is the
same values at more operations): inside a chunk of ``Q`` tokens the
causal half of ``C . B`` a group (``G Q N`` a token) and of the
triangular product a head (``H Q P``), then a chunk's state and the
state's output (``2 H N P`` each).
"""
from __future__ import annotations


def _mamba(cfg):
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return heads, dim, cfg["n_groups"], cfg["ssm_state_size"]


def _count(cfg, kind):
    return cfg["hybrid_override_pattern"].count(kind)


def expected_rows_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["n_routed_experts"]


def scan_forward_flops_per_token(cfg):
    """One mixer's scan, forward, a token."""
    heads, dim, groups, state = _mamba(cfg)
    q = cfg["chunk_size"]
    return groups * q * state + heads * (q * dim + 4 * state * dim)


def forward_flops_per_token(cfg, seq):
    e = cfg["hidden_size"]
    heads, dim, groups, state = _mamba(cfg)
    d_in = heads * dim
    mixer = 2 * e * (2 * d_in + 2 * groups * state + heads) \
        + 2 * d_in * e + scan_forward_flops_per_token(cfg)
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = 2 * e * (2 * hq + 2 * hk) + 4 * hq * seq / 2
    experts = 2 * e * cfg["n_routed_experts"] \
        + 4 * e * cfg["moe_shared_expert_intermediate_size"] \
        + 4 * e * cfg["moe_intermediate_size"] * expected_rows_per_token(cfg)
    return _count(cfg, "M") * mixer + _count(cfg, "*") * attention \
        + _count(cfg, "E") * experts + 2 * e * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward, per token of a sequence of ``seq`` tokens."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_train_flops(cfg, batch, seq):
    """One update's needed attention-core work: 2 products forward
    (QK^T, PV) and 4 backward (dV, dP, dQ, dK), each 2*d a (query, key)
    pair and head, at half the square."""
    return _count(cfg, "*") * batch * cfg["num_attention_heads"] \
        * seq * seq / 2 * 6 * 2 * cfg["head_dim"]


def flash_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed attention-core traffic: a query head's q, o
    (forward) and q, o, do, dq (backward); a KV head's k, v (forward)
    and k, v, dk, dv (backward), each once — K and V are read once a KV
    head, not once a query head."""
    rows = 6 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    return _count(cfg, "*") * batch * rows * seq * cfg["head_dim"] * itemsize


def experts_train_flops(cfg, rows):
    """One expert layer's grouped products over ``rows`` rows in all:
    two products forward, four backward."""
    return 3 * 4 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_train_bytes(cfg, rows, itemsize=2):
    """One expert layer's needed traffic for them: the held experts' two
    matrices read forward, read backward and their gradient written; the
    rows' input, two hidden activations (before and after relu^2) and
    output, each touched forward, backward and as a gradient."""
    e, fm = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts_held"] * 2 * e * fm
    return 3 * itemsize * (weights + rows * (2 * e + 2 * fm))


def scan_train_flops(cfg, batch, seq):
    """One update's needed scan work, all mixers: forward and twice that
    backward."""
    return _count(cfg, "M") * 3 * batch * seq \
        * scan_forward_flops_per_token(cfg)


def scan_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed scan traffic, all mixers: forward one read of
    ``x``, ``B``, ``C`` (AMP's type) and ``dt`` (float32) and one write
    of ``y``; backward one more read of them and of ``dy``, and one write
    of their four gradients.  Nothing the chunked form writes between
    (decays, chunk states) is needed traffic."""
    heads, dim, groups, state = _mamba(cfg)
    inputs = itemsize * (heads * dim + 2 * groups * state) + 4 * heads
    y = itemsize * heads * dim
    return _count(cfg, "M") * batch * seq * ((inputs + y)
                                             + (inputs + y + inputs))
