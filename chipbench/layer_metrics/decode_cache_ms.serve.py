"""Device time a decode step of the operations that move the KV cache
whole rather than compute on it: XLA ``copy`` operations (and the
``copy-start`` / ``copy-done`` pairs) inside the decode program's
executions, device 0.  The engine donates the cache through the step and
``decode_attention`` scatters one row a slot into it; where XLA cannot
alias the update it copies the array first — every layer's K and V, each
``max_slots x max_seq`` rows.  This is ROADMAP M2's size; None where the
step has no such operation."""
import serve_trace


def read(obs):
    ops, n = serve_trace.ops_inside(obs, serve_trace.DECODE)
    total = sum(o["end"] - o["start"] for o in ops
                if o["opcode"].startswith("copy"))
    return total / 1e6 / n if n and total else None
