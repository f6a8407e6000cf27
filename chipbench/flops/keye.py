"""What the keye family's arithmetic needs, from its shapes.

Matrix multiplications only (2 x m x n x k each); the backward pass is
twice the forward — but for the indexer's three projections, whose input
is detached (``nn.SparseIndexer``): their backward is the weights'
gradient alone, once the forward, and no product gives their input one;
recomputation (the flash kernels' re-made scores, the
indexer's re-made per-head products, the second pass over q and k that
gives the indexer's loss the attention's probabilities) is not needed
work and is not counted.  Embedding look-ups, norms, rotary, SiLU, relu,
the softmaxes, the top-k and the sort by expert are left out: they are
under 1 % and leaving them out can only make a share smaller.

Attention's core is counted at the keys a query **selects**:
``min(t + 1, topk)`` for query ``t``, 1792.1 a query at 8192 with a
``topk`` of 2048 — whatever the kernels execute (they run the whole
causal triangle under a mask: 4096.5 a query).  The indexer scores every
causal pair, (seq + 1) / 2 keys a query: that is its work, not a
recomputation.  The routed experts are counted at the rows this share
expects: experts a token x held / published (1 a token for 16 of 128 at
8 a token), here and in ``moe_experts_roofline.train``.
"""
from __future__ import annotations


def keys_per_query(seq, topk=None):
    """Mean keys a query of a causal sequence reads: ``min(t + 1, topk)``
    over ``t``; without a ``topk`` the causal half, counted at half the
    square as the other families count it."""
    if topk is None or topk >= seq:
        return seq / 2
    return (topk * (topk + 1) / 2 + (seq - topk) * topk) / seq


def causal_keys_per_query(seq):
    """Mean positions ``s <= t`` over the queries: the pairs the indexer
    scores."""
    return (seq + 1) / 2


def selected_pairs(seq, topk):
    """``sum_t min(t + 1, topk)``: the pairs one sequence selects in one
    layer."""
    return round(seq * keys_per_query(seq, topk)) if topk < seq \
        else seq * (seq + 1) // 2


def expected_rows_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


def _index_widths(cfg):
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return hi, di, hi * di + sa["indexer_num_kv_heads"] * di + hi


def index_proj_flops_per_token(cfg):
    """The indexer's three projections of one token in one layer,
    forward; as much again backward (weight gradients: the input is
    detached and gets none)."""
    return 2 * cfg["hidden_size"] * _index_widths(cfg)[2]


def forward_flops_per_token(cfg, seq):
    e, fm = cfg["hidden_size"], cfg["moe_intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    hi, di, _ = _index_widths(cfg)
    proj = 2 * e * (2 * hq + 2 * hk)            # q, out; k, v
    index = index_proj_flops_per_token(cfg) \
        + 2 * hi * di * causal_keys_per_query(seq)
    core = 4 * hq * keys_per_query(seq, cfg["sa_config"]["topk"])
    sparse = 2 * e * cfg["num_experts"] \
        + 6 * e * fm * expected_rows_per_token(cfg)
    return cfg["num_hidden_layers"] * (proj + index + core + sparse) \
        + 2 * e * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward, per token of a sequence of ``seq`` tokens:
    three times the forward, less the input gradient the indexer's
    projections do not have."""
    return 3 * forward_flops_per_token(cfg, seq) \
        - cfg["num_hidden_layers"] * index_proj_flops_per_token(cfg)


def flash_train_flops(cfg, batch, seq):
    """One update's needed attention-core work: 2 products forward
    (QK^T, PV) and 4 backward (dV, dP, dQ, dK), each 2*d a (query, key)
    pair and head, at the selected pairs."""
    pairs = cfg["num_hidden_layers"] * seq * keys_per_query(
        seq, cfg["sa_config"]["topk"])
    return batch * cfg["num_attention_heads"] * pairs \
        * 6 * 2 * cfg["head_dim"]


def flash_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed attention-core traffic: a query head's q, o
    (forward) and q, o, do, dq (backward); a KV head's k, v (forward)
    and k, v, dk, dv (backward), each once; and the selection under the
    diagonal, one byte a pair, once a kernel (three), shared by the
    heads."""
    rows = 6 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    return cfg["num_hidden_layers"] * batch * (
        rows * seq * cfg["head_dim"] * itemsize
        + 3 * seq * causal_keys_per_query(seq))


def index_train_flops(cfg, batch, seq):
    """One update's needed indexer work: its three projections, forward
    and once more backward (their weights' gradient: the input is
    detached), and the per-head score products over every causal pair,
    forward and twice that backward."""
    hi, di, _ = _index_widths(cfg)
    per_token = 2 * index_proj_flops_per_token(cfg) \
        + 3 * 2 * hi * di * causal_keys_per_query(seq)
    return cfg["num_hidden_layers"] * batch * seq * per_token


def index_train_bytes(cfg, batch, seq, itemsize=2):
    """One update's needed indexer traffic: the three matrices read
    forward and their gradient written (no input gradient reads them
    again); a token's input read forward and for the weights' gradient,
    its projections touched forward, backward and as a gradient; the
    scores under the diagonal written once and their gradient read once,
    float32."""
    _, _, index_out = _index_widths(cfg)
    e = cfg["hidden_size"]
    dense = itemsize * (2 * e * index_out
                        + batch * seq * (2 * e + 3 * index_out))
    scores = 2 * 4 * batch * seq * causal_keys_per_query(seq)
    return cfg["num_hidden_layers"] * (dense + scores)


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
