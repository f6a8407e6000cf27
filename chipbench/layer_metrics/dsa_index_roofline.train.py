"""The sparse indexer of one update against its roofline: max(needed
FLOPs / bf16 peak, needed bytes / HBM bandwidth) of its projections and
its score products over every causal pair, forward and backward
(``flops/<family>.py``: ``index_train_flops`` / ``index_train_bytes``),
over the device time under ``mx.dsa.index`` (``dsa_index_ms.train``),
device 0.  The per-head products the program makes again in the backward
pass are not needed work."""
import program_trace

SCOPE = "mx.dsa.index"


def read(obs):
    ctx = obs["ctx"]
    flops = ctx["flops"]
    if not hasattr(flops, "index_train_flops"):
        return None
    ms = program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
    if not ms:
        return None
    per_chip = obs["sequences"] // ctx["chips"]
    least = max(
        flops.index_train_flops(ctx["cfg"], per_chip, obs["seq_len"])
        / ctx["peak"]["bf16_flops"],
        flops.index_train_bytes(ctx["cfg"], per_chip, obs["seq_len"])
        / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
