"""Median host time of one ``ShardedTrainStep.__call__`` inside the traced
window: the program's own span ``mx/train.call`` on the profiler's clock
(it holds ``mx/train.shard_batch``, ``mx/train.scalars`` and
``mx/train.dispatch``)."""
import program_trace
from common import median


def read(obs):
    path = program_trace.trace_file(obs)
    if path is None:
        return None
    return median([(s["end"] - s["start"]) / 1e6
                   for s in program_trace.host_spans(path)
                   if s["name"] == "train.call"])
