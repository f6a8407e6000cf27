"""Device time an update of the Mosaic kernel named ``mx_flash_bwd_dkv``
(dK and dV of the flash attention backward), device 0, whole updates of
the traced window."""
import program_trace


def read(obs):
    return program_trace.kernel_ms(obs, "mx_flash_bwd_dkv")
