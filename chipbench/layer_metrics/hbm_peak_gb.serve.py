"""Peak bytes in use on the chip at the window's close
(``device.memory_stats()``), in GB: weights, the cache's fixed footprint,
the largest program's temporaries."""


def read(obs):
    if not obs["memory_peak_bytes"]:     # the CPU rehearsal keeps none
        return None
    return obs["memory_peak_bytes"] / 1e9
