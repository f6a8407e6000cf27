"""Of the causal (query, key) pairs of an update, the share the indexers
selected: ``selected_pairs`` (``nn.SparseIndexer``'s count of its last
training call, in the step's ``aux``) as the family last read it — after
the check's third update; the driver frees the step before a reader runs
— over ``sequences x seq (seq + 1) / 2``, the mean of the layers.  100 is
dense attention; a ``topk`` of 2048 at 8192 tokens reads 43.75.  None for
a family that keeps no such count.

While a run is ``correct`` this is a constant of the configuration and
the mix: the check holds ``selected_pairs`` to ``sum_t min(t + 1, topk)``
exactly, so the share cannot move and neither direction is better.  It
is the program's own statement of how much of the causal triangle the
core needs (what ``attn_core_roofline.train`` counts as needed), and it
starts to move with a selection whose size is data: by blocks, or with
a learned ``k``."""


def read(obs):
    ctx = obs["ctx"]
    counts = getattr(ctx["family"], "last_counts", None) or {}
    pairs = counts.get("dsa.pairs")
    if pairs is None or not len(pairs):
        return None
    seq = obs["seq_len"]
    causal = obs["sequences"] * seq * (seq + 1) / 2
    return 100.0 * float(sum(pairs)) / len(pairs) / causal
