"""Peak bytes in use on the fullest chip at the window's close
(``device.memory_stats()``), in GB."""


def read(obs):
    if not obs["memory_peak_bytes"]:     # the CPU rehearsal keeps none
        return None
    return obs["memory_peak_bytes"] / 1e9
