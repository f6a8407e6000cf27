"""Real-size compiles for a described (not attached) v5e: both train
steps and their lower-precision controls, each printing
``memory_analysis()``.  This is what fixes ``grad_accum`` / ``remat`` in
the cells' files before a chip-minute is spent (run with ``-s`` to see
the numbers).  Nothing runs; a compile
that passes is not a chip run.

The topology is described inside a fixture and everything built from it
is built in the tests (one process may load the TPU library at a time).
The program decides kernel dispatch and block sizes from
``jax.default_backend()``, which here is the CPU, so the tests steer
those two questions themselves (``runtime.on_tpu``, the block table's
family) — no option of the program is involved.
"""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: what a v5e reports as ``bytes_limit`` (my chip run, PR 21)
BYTES_LIMIT = 16.9e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def as_v5e(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu import runtime
    from mxnet_tpu.autotune import kernels
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(kernels, "_device_family", lambda kind=None: "v5e")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _cell(name):
    import run
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return run.resolve(bench, name)


def _report(tag, compiled, resident=0):
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"\n[{tag}] args {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.2f}, aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f}, temp "
          f"{m.temp_size_in_bytes / 1e9:.2f}: program "
          f"{live / 1e9:.2f} GB + resident outside it "
          f"{resident / 1e9:.2f} GB = {(live + resident) / 1e9:.2f} GB of "
          f"{BYTES_LIMIT / 1e9:.1f} GB a chip")
    return live + resident


def _train_compile(cell_name, topo, overrides=None):
    """The cell's ShardedTrainStep, built on the CPU's virtual devices at
    the published size, then lowered and compiled for the described
    chips with the same partition specs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as onp
    import common
    import run
    from drivers import train_steps
    from mxnet_tpu.parallel.mesh import activation_sharding

    entry, cell, cfg, mix = _cell(cell_name)
    cell = common.deep_merge(cell, overrides or {})
    ctx = {"cell": cell, "cfg": cfg, "seed": 1,
           "family": run.load_module("families", cfg["family"])}
    net, train = train_steps.build(ctx)
    names = train.mesh.axis_names
    shape = tuple(train.mesh.shape[a] for a in names)
    n = int(onp.prod(shape))
    tmesh = Mesh(onp.array(topo.devices[:n]).reshape(shape), names)
    train.mesh = tmesh
    if train.zero:
        train._build_zero_update()

    def spec_of(a):
        return NamedSharding(tmesh, a.sharding.spec)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=spec_of(a))

    state = (train.trainable, train.aux, train.states, train.extra)
    rep = NamedSharding(tmesh, P())
    k = train.grad_accum
    b, s = mix["sequences"], mix["seq_len"]
    bshape = (k, b // k, s) if k > 1 else (b, s)
    bsh = tuple(NamedSharding(tmesh, x.spec) for x in train.batch_shardings)
    batch = tuple(jax.ShapeDtypeStruct(bshape, jnp.int32, sharding=x)
                  for x in bsh)
    in_sh = tuple(jax.tree_util.tree_map(spec_of, t) for t in state) \
        + (rep, rep, rep) + bsh
    out_sh = tuple(jax.tree_util.tree_map(spec_of, t) for t in state) \
        + (rep,)
    jitted = jax.jit(train._step.__wrapped__, in_shardings=in_sh,
                     out_shardings=out_sh, donate_argnums=(0, 1, 2, 3))
    args = tuple(jax.tree_util.tree_map(sds, t) for t in state) + (
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)) + batch
    with activation_sharding(tmesh, **train._act_rules):
        lowered = jitted.lower(*args)
    text = lowered.as_text()
    compiled = lowered.compile()
    # what memory_analysis does not count: the Block's own parameter
    # copy, which ShardedTrainStep leaves on the first device
    resident = sum(int(v.size) * 4 for v in train.trainable.values())
    return compiled, text, resident, cfg


@pytest.mark.parametrize("cell_name", ["gpt2m-train-8k", "gpt2l-train-dp4"])
def test_train_step_fits_the_chip(cell_name, topo, as_v5e):
    compiled, text, resident, cfg = _train_compile(cell_name, topo)
    total = _report(cell_name, compiled, resident)
    # forward, dK/dV and dQ kernels in every layer, bf16 operands
    assert text.count("tpu_custom_call") >= 3 * cfg["n_layer"]
    assert "bf16" in text
    assert total < BYTES_LIMIT


@pytest.mark.parametrize("cell_name,fits", [("gpt2m-train-8k", True),
                                            ("gpt2l-train-dp4", False)])
def test_train_control_lowers_for_the_chip(cell_name, fits, topo, as_v5e):
    """The cell's lower-precision control as its file gives it.  On one
    chip the fp8 step needs its own ``grad_accum`` to fit.  Across four
    the fp8 kernel lowers only inside the compressed reduce's
    ``shard_map``, and that step compiles but does not fit GPT-2 large
    (its error-feedback residual is a third copy of the gradients): the
    dp4 control fails at load, which PERF.md section 2 records."""
    control = _cell(cell_name)[1]["control"]
    compiled, text, resident, cfg = _train_compile(cell_name, topo, control)
    total = _report(cell_name + " control", compiled, resident)
    assert "f8e4m3" in text.lower()
    assert (total < BYTES_LIMIT) == fits
