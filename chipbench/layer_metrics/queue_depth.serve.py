"""Requests submitted and not yet admitted, mean over the window's
``eng.step()`` calls (the driver's count after each step).  Near 0 while
the engine keeps up; it grows all through a flooded window."""


def read(obs):
    lo, hi = obs["serve_window"]
    q = [s["queued"] for s in obs["steps"] if lo <= s["t0"] <= hi]
    return sum(q) / len(q) if q else None
