"""Device time an update of the Mosaic kernel named ``mx_flash_fwd`` (the
``name=`` of its ``pallas_call``), device 0, whole updates of the traced
window.  With ``flash_dkv_ms.train`` and ``flash_dq_ms.train`` it is the
time ``flash_roofline.train`` divides by."""
import program_trace


def read(obs):
    return program_trace.kernel_ms(obs, "mx_flash_fwd")
