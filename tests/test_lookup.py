"""ops/lookup.py — the embedding lookup and its column-partitioned form.

Inside a mesh scope whose ``dp`` axis cuts the table's rows, the lookup and
its backward run column block by column block (ids gathered once, an
all-to-all each way); everywhere else it is ``jnp.take``.  Values are those
of the plain lookup bit for bit, gradients to a rounding (a row hit twice
is summed in another order, as GSPMD's own partitions do)."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from mxnet_tpu.ops.lookup import take_rows
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.mesh import activation_sharding

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def _data(rows, cols, batch, seq=8, seed=0):
    rs = onp.random.RandomState(seed)
    table = rs.standard_normal((rows, cols)).astype("float32")
    ids = rs.randint(0, rows, (batch, seq)).astype("int32")
    ids[0, :3] = ids[-1, -1]          # the same row on several ranks
    dout = rs.standard_normal((batch, seq, cols)).astype("float32")
    return table, ids, dout


def _plain(table, ids, dout):
    out, vjp = jax.vjp(lambda t: jnp.take(t, ids, axis=0), table)
    return out, vjp(dout)[0]


def _scoped(mesh, table, ids, dout):
    """out, d table and the lowered text of the lookup traced in a scope."""
    sh = lambda *spec: NamedSharding(mesh, P(*spec))

    @jax.jit
    def f(table, ids, dout):
        out, vjp = jax.vjp(lambda t: take_rows(t, ids), table)
        return out, vjp(dout)[0]

    args = (jax.device_put(table, sh()), jax.device_put(ids, sh("dp")),
            jax.device_put(dout, sh("dp")))
    with activation_sharding(mesh):
        text = f.lower(*args).as_text()
        out, dtable = f(*args)
    return onp.asarray(out), onp.asarray(dtable), text


@pytest.mark.parametrize("axes,rows,cols,batch", [
    ({"dp": 4}, 66, 16, 8),            # GPT-2's case, small: 66 % 4 == 2
    ({"dp": 2}, 67, 6, 4),
    ({"dp": 4, "tp": 2}, 50, 8, 4),    # the other axes see the table whole
    ({"dp": 8}, 9, 8, 8),              # one batch row a rank
])
def test_lookup_by_columns_matches_take(axes, rows, cols, batch):
    table, ids, dout = _data(rows, cols, batch)
    mesh = make_mesh(axes)
    out, dtable, text = _scoped(mesh, table, ids, dout)
    want_out, want_d = _plain(table, ids, dout)
    onp.testing.assert_array_equal(out, onp.asarray(want_out))
    # a row hit twice is summed in another order: a rounding apart at most
    onp.testing.assert_allclose(dtable, onp.asarray(want_d),
                                rtol=1e-6, atol=1e-6)
    assert text.count('"stablehlo.all_to_all"') == 2
    assert text.count('"stablehlo.all_gather"') == 1


@pytest.mark.parametrize("axes,rows,cols,batch,why", [
    ({"dp": 4}, 64, 16, 8, "whole rows to every rank: the state is flat"),
    ({"dp": 4}, 66, 18, 8, "no dimension divides"),
    ({"dp": 4}, 66, 16, 6, "the batch does not divide"),
    ({"dp": 1, "tp": 4}, 66, 16, 8, "no data-parallel axis to cut rows"),
])
def test_lookup_stays_plain(axes, rows, cols, batch, why):
    table, ids, dout = _data(rows, cols, batch)
    if batch % 4:                    # nothing to place: traced alone
        with activation_sharding(make_mesh(axes)):
            text = jax.jit(take_rows).lower(table, ids).as_text()
    else:
        out, dtable, text = _scoped(make_mesh(axes), table, ids, dout)
        want_out, want_d = _plain(table, ids, dout)
        onp.testing.assert_array_equal(out, onp.asarray(want_out))
        onp.testing.assert_allclose(dtable, onp.asarray(want_d),
                                    rtol=1e-6, atol=1e-6)
    assert "all_to_all" not in text and "manual_computation" not in text, why


def test_lookup_outside_a_scope_and_inside_a_manual_region():
    table, ids, dout = _data(66, 16, 8)
    text = jax.jit(take_rows).lower(table, ids).as_text()
    assert "all_to_all" not in text
    mesh = make_mesh({"dp": 4})

    def local(t, i):                  # as the compressed-gradient step is
        return take_rows(t, i)

    f = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P("dp")),
                              out_specs=P("dp"), check_vma=False))
    with activation_sharding(mesh):
        text = f.lower(table, ids).as_text()
        out = f(table, ids)
    assert "all_to_all" not in text
    onp.testing.assert_array_equal(onp.asarray(out), table[ids])
