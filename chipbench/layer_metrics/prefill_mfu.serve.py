"""The prefills of the traced stretch against the chip's peak: the
operations their prompts need at their own lengths (the head once a
prompt; ``flops/<family>.serve.py``) over the summed device time of the
prefill programs' executions."""
import serve_trace


def read(obs):
    ms = serve_trace.module_ms(obs, serve_trace.PREFILL)
    reqs = serve_trace.traced_prefills(obs)
    if not ms or not reqs:
        return None
    ctx = obs["ctx"]
    need = sum(obs["serve_flops"].prefill_flops(ctx["cfg"], r["prompt"])
               for r in reqs) / len(reqs)
    return 100.0 * need / (sum(ms) / len(ms) / 1e3) \
        / ctx["peak"]["bf16_flops"]
