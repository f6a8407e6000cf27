"""Median, over the requests due in the window, of the time between
admission (prefill dispatched) and the first token reaching the caller
(``Request.t_admitted`` -> ``Request.t_first``): the prefill's device
time, whatever ran before it on the device, and the wait in the engine's
emit window until a drain fetched it; in a traced run over those served
before the profiler started."""
import serve_trace
from common import median


def read(obs):
    return median([1e3 * (r["t_first"] - r["t_admitted"])
                   for r in serve_trace.untraced_requests(obs)])
