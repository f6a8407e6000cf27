"""The three flash attention kernels of one update, by name
(``mx_flash_fwd``, ``mx_flash_bwd_dkv``, ``mx_flash_bwd_dq``), against
their roofline: max(needed FLOPs / bf16 peak, needed bytes / HBM
bandwidth) over their summed device time, device 0.  Needed work from
``flops/<family>.py``: the band on window layers, K and V read once a KV
head, no recomputation.  (``flash_roofline.train`` takes the same
count and divides by every Mosaic call, which here holds XLA's
``ragged-dot`` kernels too; a cell with other Mosaic kernels reports
this one.)"""
import program_trace

KERNELS = ("mx_flash_fwd", "mx_flash_bwd_dkv", "mx_flash_bwd_dq")


def read(obs):
    ctx = obs["ctx"]
    times = [program_trace.kernel_ms(obs, k) for k in KERNELS]
    if not all(times):
        return None
    per_chip = obs["sequences"] // ctx["chips"]
    fl = ctx["flops"].flash_train_flops(ctx["cfg"], per_chip, obs["seq_len"])
    by = ctx["flops"].flash_train_bytes(ctx["cfg"], per_chip, obs["seq_len"])
    least = max(fl / ctx["peak"]["bf16_flops"],
                by / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (sum(times) / 1e3)
