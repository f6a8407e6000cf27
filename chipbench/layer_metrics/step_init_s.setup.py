"""The first ``train.init`` span: ``ShardedTrainStep.__init__`` — the plan
(``train.plan``), the parameters' placement (``train.place``) and the
optimizer state's (``train.states``).
One of the six pieces ``setup_timeline`` cuts ``setup_s`` into."""
import setup_timeline


def read(obs):
    return setup_timeline.piece(obs, "step_init_s.setup")
