"""Median host time of one ``eng.step()`` that found live slots, over
the window: admission and prefill dispatch, the decode dispatch, and
whatever drain of the emit window the step forced (a fetch waits for the
device)."""
from common import median


def read(obs):
    lo, hi = obs["serve_window"]
    return median([1e3 * (s["t1"] - s["t0"]) for s in obs["steps"]
                   if s["live"] and lo <= s["t0"] <= hi])
