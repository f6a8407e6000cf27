"""What the glm4_moe_lite family's arithmetic needs, from its shapes.

Matrix multiplications only (2 x m x n x k each); the backward pass is
twice the forward; recomputation (remat, the flash kernel's re-made
scores) is not needed work and is not counted.  Embedding look-ups,
norms, rotary, SiLU, sigmoids, the softmax, the top-k and the sort are
left out: they are under 1 % and leaving them out can only make a share
smaller.

Latent attention is counted in the plain form its equations state: the
two down-projections, the two up-projections and the output projection;
the core at half the square with a query / key head of ``nope + rope``
and a value head of ``v_head_dim`` (both 256 here), one key and one value
head a query head.  The routed experts are counted at the rows this share
expects: experts a token x held / published (0.5 a token for 8 of 64 at
4 a token).  The second prediction depth, where the configuration trains
it, adds its joining projection, one expert layer and one more head
product a token (the ``1 / seq`` of positions it leaves out is not taken
off).
"""
from __future__ import annotations


def _heads(cfg):
    """(heads, query / key head width, value head width)."""
    return (cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _n_expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        + cfg["num_nextn_predict_layers"]


def _n_attention_layers(cfg):
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def expected_rows_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["n_routed_experts"]


def mla_projection_flops_per_token(cfg):
    """One layer's five projections, forward, a token."""
    e, rq, rkv = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    heads, dqk, dv = _heads(cfg)
    return 2 * (e * rq + rq * heads * dqk
                + e * (rkv + cfg["qk_rope_head_dim"])
                + rkv * heads * (cfg["qk_nope_head_dim"] + dv)
                + heads * dv * e)


def core_flops_per_token(cfg, seq):
    """One layer's attention core, forward, a token: QK^T and PV over
    ``seq / 2`` keys a query."""
    heads, dqk, dv = _heads(cfg)
    return heads * (2 * dqk + 2 * dv) * seq / 2


def expert_layer_flops_per_token(cfg):
    """One expert layer's feed-forward, forward, a token: router, shared
    expert, routed experts at the expected rows."""
    e, fm = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * e * cfg["n_routed_experts"] \
        + 6 * e * fm * cfg["n_shared_experts"] \
        + 6 * e * fm * expected_rows_per_token(cfg)


def forward_flops_per_token(cfg, seq):
    e = cfg["hidden_size"]
    mtp = cfg["num_nextn_predict_layers"]
    return _n_attention_layers(cfg) * (mla_projection_flops_per_token(cfg)
                                       + core_flops_per_token(cfg, seq)) \
        + cfg["first_k_dense_replace"] * 6 * e * cfg["intermediate_size"] \
        + _n_expert_layers(cfg) * expert_layer_flops_per_token(cfg) \
        + (1 + mtp) * 2 * e * cfg["vocab_size"] + mtp * 2 * (2 * e) * e


def train_flops_per_token(cfg, seq):
    """Forward + backward, per token of a sequence of ``seq`` tokens."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_train_flops(cfg, batch, seq):
    """One update's needed attention-core work: 2 products forward
    (QK^T, PV) and 4 backward (dV, dP, dQ, dK); the three over the query /
    key width and the three over the value width, a (query, key) pair and
    head, at half the square."""
    heads, dqk, dv = _heads(cfg)
    return _n_attention_layers(cfg) * batch * heads * seq * seq / 2 \
        * 3 * 2 * (dqk + dv)


def flash_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed attention-core traffic: a head's q, o
    (forward) and q, o, do, dq (backward), its k, v (forward) and k, v,
    dk, dv (backward), each once — K and V are read once a head (the
    rotary key that the plain form repeats a head in HBM is counted
    inside each head's K, as the kernels read it)."""
    heads, dqk, dv = _heads(cfg)
    a_head = 3 * (dqk + dv) + 3 * (dqk + dv)   # q, o rows; k, v rows
    return _n_attention_layers(cfg) * batch * heads * seq * a_head * itemsize


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
