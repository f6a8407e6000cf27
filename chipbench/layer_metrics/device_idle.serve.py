"""1 - (union of device-operation intervals / traced stretch), device
0, from the trace."""


def read(obs):
    tr = obs["device_trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
