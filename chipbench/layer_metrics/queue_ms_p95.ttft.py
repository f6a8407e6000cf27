"""95th percentile, over the requests due in the window, of the time
between ``submit`` and admission into a slot, from the program's own
stamps (``Request.t_submit``, ``Request.t_admitted``); in a traced run
over those served before the profiler started."""
import serve_trace


def read(obs):
    return serve_trace.percentile(
        [1e3 * (r["t_admitted"] - r["t_submit"])
         for r in serve_trace.untraced_requests(obs)], 95)
