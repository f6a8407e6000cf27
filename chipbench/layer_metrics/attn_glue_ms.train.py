"""Device time an update of the operations under ``mx.attn`` (the body of
``ops.attention.multi_head_attention``, forward and backward) that are
no Mosaic kernel: the splits, pads to 128 lanes, slices, transposes and
copies round the kernels.  Device 0, whole updates of the traced window."""
import program_trace


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: program_trace.ATTN in o["op_name"]
        and not o["mosaic"] and not o["collective"])
