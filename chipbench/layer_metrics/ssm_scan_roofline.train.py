"""The state-space scans of one update against their roofline:
max(needed FLOPs / bf16 peak, needed bytes / HBM bandwidth)
(``flops/<family>.py``: ``scan_train_flops`` / ``scan_train_bytes`` —
the chunked form's products at the causal half inside a chunk, forward
and twice that backward; one read of ``x``, ``B``, ``C``, ``dt`` and one
write of ``y`` forward, their gradients' counterparts backward) over the
device time under ``mx.ssm.scan`` (``ssm_scan_ms.train``), device 0.
What the chunked form writes between — decays, chunk states — and the
forward made again for the gradient are not needed work.  None where
the family has no such function or the trace no such scope."""
import program_trace

SCOPE = "mx.ssm.scan"


def read(obs):
    ctx = obs["ctx"]
    flops = ctx["flops"]
    if not hasattr(flops, "scan_train_flops"):
        return None
    ms = program_trace.ms_per_update(
        obs, lambda o: SCOPE in o["op_name"] and not o["collective"])
    if not ms:
        return None
    per_chip = obs["sequences"] // ctx["chips"]
    least = max(
        flops.scan_train_flops(ctx["cfg"], per_chip, obs["seq_len"])
        / ctx["peak"]["bf16_flops"],
        flops.scan_train_bytes(ctx["cfg"], per_chip, obs["seq_len"])
        / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
