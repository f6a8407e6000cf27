"""Live slots as a share of ``max_slots``, mean over the window's
``eng.step()`` calls that found any (the driver's count of its requests
that hold a slot after each step)."""


def read(obs):
    lo, hi = obs["serve_window"]
    live = [s["live"] for s in obs["steps"]
            if s["live"] and lo <= s["t0"] <= hi]
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / obs["max_slots"]
