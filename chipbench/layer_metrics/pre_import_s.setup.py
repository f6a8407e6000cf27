"""``T_PROCESS`` to the start of the package's ``import`` span: the
interpreter, ``import jax``, the TPU runtime's start (``jax.devices()``):
the one piece of ``setup_s`` that is not the program's own.
One of the six pieces ``setup_timeline`` cuts ``setup_s`` into."""
import setup_timeline


def read(obs):
    return setup_timeline.piece(obs, "pre_import_s.setup")
