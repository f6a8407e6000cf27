"""95th percentile of how late the driver submitted a request after its
due time (one thread submits between ``eng.step()`` calls, so a request
waits for the step in flight): a gauge of the load generator, counted in
``ttft_p95_ms`` because TTFT runs from the due time; in a traced run
over the requests served before the profiler started."""
import serve_trace


def read(obs):
    return serve_trace.percentile(
        [1e3 * r["late"] for r in serve_trace.untraced_requests(obs)], 95)
