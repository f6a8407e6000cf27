"""The routed experts' grouped products of one update against their
roofline: max(needed FLOPs / bf16 peak, needed bytes / HBM bandwidth)
over the device time of the kernels XLA lowers ``lax.ragged_dot`` to
(Mosaic calls named ``ragged-dot-...``: the products and their group
metadata), device 0.  Needed work from ``flops/<family>.py`` at the rows
this share **expects** a layer — experts a token x held / published x
tokens a chip and update, the count ``train_mfu`` uses — and not at the rows the
traced updates really held, which no reader can see (the driver frees
the step before a reader runs).  More rows than expected take longer and
read as a smaller share; fewer read as a larger one."""
import program_trace
import xplane


def read(obs):
    ctx = obs["ctx"]
    flops = ctx["flops"]
    if not hasattr(flops, "experts_train_flops"):
        return None
    ms = program_trace.ms_per_update(
        obs, lambda o: o["mosaic"]
        and xplane.family_of(o["name"]).startswith("ragged-dot"))
    if not ms:
        return None
    cfg = ctx["cfg"]
    rows = flops.expected_rows_per_token(cfg) \
        * (obs["sequences"] // ctx["chips"]) * obs["seq_len"]
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    least = layers * max(
        flops.experts_train_flops(cfg, rows) / ctx["peak"]["bf16_flops"],
        flops.experts_train_bytes(cfg, rows)
        / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
