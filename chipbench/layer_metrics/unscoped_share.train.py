"""Of the device-operation time of the traced window's whole updates (device
0), the share that carries none of the step's scopes (``mx.fwd``, its
transpose, ``mx.optimizer``) and is no collective: the guard that the
names cover the step.  None for a program that names nothing."""
import program_trace


def read(obs):
    ops, _ = program_trace.update_ops(obs)
    if not ops or not any(o["scope"] for o in ops):
        return None
    total = sum(o["end"] - o["start"] for o in ops)
    bare = sum(o["end"] - o["start"] for o in ops
               if o["scope"] is None and not o["collective"])
    return 100.0 * bare / total
