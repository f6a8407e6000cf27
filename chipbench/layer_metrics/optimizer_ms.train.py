"""Device time an update of the operations under the ``mx.optimizer`` scope
(``ShardedTrainStep._apply_updates``), device 0, whole updates of the
traced window, collectives left out (ZeRO's gather is
``coll_exposed_ms.train``'s)."""
import program_trace


def read(obs):
    return program_trace.scope_ms(obs, "optimizer")
