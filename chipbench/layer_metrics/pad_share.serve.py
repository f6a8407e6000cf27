"""Bucket padding as a share of the rows prefilled: over the requests
due in the window, (bucket - prompt) summed over buckets summed."""


def read(obs):
    rows = sum(r["bucket"] for r in obs["requests"])
    if not rows:
        return None
    return 100.0 * sum(r["bucket"] - r["prompt"]
                       for r in obs["requests"]) / rows
