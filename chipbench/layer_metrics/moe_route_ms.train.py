"""Device time an update of what ``mx.moe`` holds outside
``mx.moe.experts``: the router's product, sigmoid, top-k and weights, the
sort by expert and the gather of the rows (scope ``mx.moe.route``), and
the weighted scatter-add that combines the experts' rows into tokens
(directly under ``mx.moe``), forward and backward.  With the grouped
products and the shared expert it adds up to ``moe_ms.train``."""
import program_trace

MOE, EXPERTS = "mx.moe", "mx.moe.experts"


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: MOE in o["op_name"] and EXPERTS not in o["op_name"]
        and not o["collective"])
