"""Per update, device time inside collective operations during which no
compute operation runs on that device (a ``while`` that only wraps the
micro-batch loop is not compute); the worst device's, from the trace, over
the updates the traced part of the window held."""


def read(obs):
    tr = obs["device_trace"]
    if len(tr["devices"]) < 2:
        return None
    window_updates = tr["window_s"] / (obs["window"][1] - obs["window"][0]) \
        * obs["updates"]
    return 1e3 * max(tr["collective_exposed_s"]) / max(window_updates, 1.0)
