"""Load imbalance over the experts this share holds: the most loaded held
expert's assignments over the mean held expert's, from the live step's
``expert_load`` (``nn.RoutedExperts``' count, in the step's ``aux``) as
the family last read it, the worst of the expert layers.  1 is even; the
grouped products take as long as their rows in all, so imbalance costs
nothing here until the rows pass the layer's static bound."""


def read(obs):
    ctx = obs["ctx"]
    counts = getattr(ctx["family"], "last_counts", None)
    if not counts:
        return None
    lo = ctx["cfg"]["experts_held_from"]
    held = counts["moe.load"][:, lo:lo + ctx["cfg"]["num_experts_held"]]
    return float((held.max(axis=1) / held.mean(axis=1)).max())
