"""End of the ``import`` span to the start of the first ``train.init``:
AMP, the benchmark's weight generator, the zoo's constructors,
``collect_params``, a ``set_data`` a leaf.
One of the six pieces ``setup_timeline`` cuts ``setup_s`` into."""
import setup_timeline


def read(obs):
    return setup_timeline.piece(obs, "net_build_s.setup")
