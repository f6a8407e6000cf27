"""Device time an update of the operations under ``mx.moe`` (the body of
``nn.RoutedExperts``: router, selection, sort, gather, the grouped
products, the shared expert and the combine, forward and backward), all
expert layers together, device 0, whole updates of the traced window.
The kernels XLA lowers ``lax.ragged_dot`` to are Mosaic calls of its own
making that carry no ``op_name``: they are taken by their name,
``ragged-dot-...``."""
import program_trace
import xplane

MOE = "mx.moe"


def grouped(op):
    """One of XLA's grouped-product kernels (or their group metadata)."""
    return op["mosaic"] and xplane.family_of(op["name"]).startswith(
        "ragged-dot")


def read(obs):
    return program_trace.ms_per_update(
        obs, lambda o: (MOE in o["op_name"] or grouped(o))
        and not o["collective"])
