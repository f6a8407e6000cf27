"""The Mosaic kernels of one update (the flash attention forward, dK/dV
and dQ calls; the program names none of them, so they are told apart
only as custom calls) against their roofline: max(needed FLOPs / bf16
peak, needed bytes / HBM bandwidth) over their summed device time, per
chip.  Needed work from ``flops/<family>.py``: no recomputation, no
64 -> 128 lane padding."""
import xplane


def read(obs):
    ctx, tr = obs["ctx"], obs["device_trace"]
    d0 = tr["devices"][0]
    steps = d0["step_modules"]      # whole updates inside the traced window
    if not steps:
        return None
    kernel_s = sum(o["end"] - o["start"] for o in d0["ops"]
                   if xplane.is_mosaic(o)
                   and any(m["start"] <= o["start"] and o["end"] <= m["end"]
                           for m in steps)) / 1e9
    if kernel_s <= 0:
        return None
    per_chip = obs["sequences"] // ctx["chips"]
    fl = ctx["flops"].flash_train_flops(ctx["cfg"], per_chip, obs["seq_len"])
    by = ctx["flops"].flash_train_bytes(ctx["cfg"], per_chip, obs["seq_len"])
    least = max(fl / ctx["peak"]["bf16_flops"],
                by / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * len(steps) / kernel_s
