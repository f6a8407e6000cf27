"""End of ``train.init`` to the end of the first ``train.call``: what the
wall sees of trace + lower + compile-or-load of the step and of the
``train.scalars`` helper programs (``compile_s`` sums JAX's events, whose
nested ones repeat their parents' time).
One of the six pieces ``setup_timeline`` cuts ``setup_s`` into."""
import setup_timeline


def read(obs):
    return setup_timeline.piece(obs, "first_call_s.setup")
