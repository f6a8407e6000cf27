"""Row lookup into a table (``npx.embedding``), and its data-parallel form.

Plain, a lookup is ``jnp.take(table, ids, axis=0)`` and GSPMD partitions it
and its backward as the gradient's consumer asks.  Where ZeRO lays the
table's optimizer state along its columns (``parallel/layout.py``:
``columns_spec``, a table whose rows ``dp`` does not divide), the consumer
asks for the gradient in column blocks, and GSPMD then partitions the
backward's scatter-add along the columns: every rank needs every rank's
token ids, the partitioner all-gathers them in the backward, and the TPU
compiler starts that 32 KB gather at the top of the step and stretches it
over every matmul down to the end of the backward -- a chain of in-place
fusions whose buffer aliases, and with them the executable and its compile
time, grow with the square of the depth (PERF.md section 6, PR 31).

So under such a mesh the lookup is partitioned along the columns in BOTH
directions, by hand: forward, every rank gathers the ids once, looks all
rows up in its own column block and an all-to-all hands each rank its own
batch rows whole; backward, the all-to-all runs the other way and the rank
scatter-adds into its column block with the ids the forward gathered.  The
gather is consumed where it is issued, the weight gradient needs no
reduction, and the wire carries activations instead of the table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

_AXIS = "dp"


def take_rows(table, ids):
    """``table[ids]`` for a 2-D ``table``: :func:`jnp.take` along axis 0,
    or its column-partitioned form inside a mesh scope whose ``dp`` axis
    cuts the table's rows (the step's plan then holds the table's state in
    column blocks, ``layout.columns_spec``) and divides the batch."""
    from ..parallel.layout import columns_spec
    from .attention import _scope_mesh
    mesh = _scope_mesh()
    n = 1 if mesh is None else int(mesh.shape.get(_AXIS, 1))
    if (n > 1 and table.ndim == 2 and ids.ndim >= 1
            and ids.shape[0] % n == 0
            and columns_spec(table.shape, _AXIS, n) == P(None, _AXIS)
            and _AXIS not in jax.sharding.get_abstract_mesh().manual_axes):
        return _take_rows_by_columns(table, ids, mesh, n)
    return jnp.take(table, ids, axis=0)


def _take_rows_by_columns(table, ids, mesh, n):
    rows, cols = table.shape

    def forward(table, ids):
        def local(block, mine):
            every = lax.all_gather(mine, _AXIS, axis=0, tiled=True)
            found = jnp.take(block, every, axis=0)
            # batch blocks out, column blocks in: my rows, whole
            return lax.all_to_all(found, _AXIS, split_axis=0,
                                  concat_axis=found.ndim - 1,
                                  tiled=True), every

        return shard_map(
            local, mesh=mesh, in_specs=(P(None, _AXIS), P(_AXIS)),
            out_specs=(P(_AXIS), P()), check_vma=False)(table, ids)

    def backward(every, dout):
        def local(dmine, every):
            dblock = lax.all_to_all(dmine, _AXIS, split_axis=dmine.ndim - 1,
                                    concat_axis=0, tiled=True)
            return jnp.zeros((rows, cols // n), dmine.dtype).at[every].add(
                dblock)

        return shard_map(
            local, mesh=mesh, in_specs=(P(_AXIS), P()),
            out_specs=P(None, _AXIS), check_vma=False)(dout, every), None

    @jax.custom_vjp
    def lookup(table, ids):
        return forward(table, ids)[0]

    lookup.defvjp(forward, backward)
    return lookup(table, ids)
