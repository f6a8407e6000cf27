"""Median host time of one update: between successive loss fetches,
divided by the updates between them (a step completes unseen by the
host; a fetch is where the host learns that it has)."""
from common import median


def read(obs):
    f = obs["fetches"]
    per = [1e3 * (t1 - t0) / (n1 - n0)
           for (t0, n0), (t1, n1) in zip(f, f[1:]) if n1 > n0]
    return median(per)
