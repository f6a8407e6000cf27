"""``prefill_ms.serve`` as the steady cell reports it, where it moves
``ttft_p95_ms``: the same reader under that name."""
import serve_trace

read = serve_trace.sibling(__file__)
