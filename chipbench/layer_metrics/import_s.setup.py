"""The package's ``import`` span (``mx.trace.startup()``): every
``mxnet_tpu.*`` module, first line of ``mxnet_tpu/__init__.py`` to its last.
One of the six pieces ``setup_timeline`` cuts ``setup_s`` into."""
import setup_timeline


def read(obs):
    return setup_timeline.piece(obs, "import_s.setup")
