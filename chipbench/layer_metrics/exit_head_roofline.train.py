"""The exits' heads of one update against their roofline: max(needed
FLOPs / bf16 peak, needed bytes / HBM bandwidth) (``flops/<family>.py``:
``exit_head_train_flops`` / ``exit_head_train_bytes`` — one logits
product forward and two backward an exit; the head's matrix and the
exits' states read and their gradients written once) over the device
time under ``mx.exit`` (``exit_head_ms.train``), device 0.  The needed
work is the same whatever computes the head: logits made again for the
gradient, or kept and read back, are not needed work, so the share
cannot pass 100.  None where the family has no such function or the
trace no such scope."""
import program_trace

SCOPE = "mx.exit"


def read(obs):
    ctx = obs["ctx"]
    flops = ctx["flops"]
    if not hasattr(flops, "exit_head_train_flops"):
        return None
    ms = program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
    if not ms:
        return None
    per_chip = obs["sequences"] // ctx["chips"]
    least = max(
        flops.exit_head_train_flops(ctx["cfg"], per_chip, obs["seq_len"])
        / ctx["peak"]["bf16_flops"],
        flops.exit_head_train_bytes(ctx["cfg"], per_chip, obs["seq_len"])
        / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
