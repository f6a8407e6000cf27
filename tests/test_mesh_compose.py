"""Composed parallelism: MeshConfig dp x tp x pp x sp in one jitted step.

Strategy (same as test_zero.py): every layout must be numerically
invisible — the same GPT trained under dp2xtp2xpp2, dp4xtp2+zero1 and
dp2xsp2 must reproduce single-device per-step losses to fp32 tolerance
with exactly one compilation, and a checkpoint saved under one layout
must restore bitwise under another (docs/PERFORMANCE.md "Composing
parallelism").
"""
import tempfile
import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import (MeshConfig, ShardedTrainStep, make_mesh,
                                mesh_factorizations)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")

VOCAB, UNITS, LAYERS, HEADS, SEQ, BATCH = 64, 16, 2, 2, 8, 8


def _batch(seed=0):
    rs = onp.random.RandomState(seed)
    x = rs.randint(0, VOCAB, (BATCH, SEQ)).astype("int32")
    y = rs.randint(0, VOCAB, (BATCH, SEQ)).astype("int32")
    return x, y


def _loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def _gpt_step(cfg, x, lr=0.01, **kw):
    """Tiny deterministic GPT under ``cfg``.  The eager forward after
    initialize() is load-bearing: GPT weight matrices are deferred-init,
    and the step only shards parameters that already exist."""
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
    mx.random.seed(0)
    net = GPTForCausalLM(vocab_size=VOCAB, units=UNITS, num_layers=LAYERS,
                         num_heads=HEADS, max_length=SEQ, dropout=0.0,
                         embed_dropout=0.0)
    net.initialize()
    net(mx.np.array(x))
    return ShardedTrainStep(
        net, _loss_fn, mx.optimizer.create("adam", learning_rate=lr),
        cfg, batch_specs=cfg.batch_specs(2, 2), n_labels=1, **kw)


# ---------------------------------------------------------------------------
# MeshConfig itself
# ---------------------------------------------------------------------------

def test_mesh_config_validation_and_identity():
    with pytest.raises(MXNetError):
        MeshConfig(dp=0)
    with pytest.raises(MXNetError):
        MeshConfig(tp=2.5)
    with pytest.raises(MXNetError):
        MeshConfig(dp=16, tp=16).build()          # overshoots 8 devices
    assert MeshConfig(dp=2, tp=2) == MeshConfig(tp=2, dp=2)
    assert hash(MeshConfig(dp=2)) == hash(MeshConfig(dp=2))
    assert MeshConfig(dp=2) != MeshConfig(dp=2, pp=2)
    assert MeshConfig(dp=2, tp=2, pp=2).size() == 8


def test_mesh_config_axes_always_present():
    """Size-1 axes stay in the mesh so any dp/tp/pp/sp spec is valid on
    any layout — the property elastic checkpoints rely on."""
    mesh = MeshConfig(dp=2).build()
    assert tuple(mesh.axis_names) == MeshConfig.AXES
    assert mesh.shape["tp"] == 1 and mesh.shape["pp"] == 1


def test_batch_spec_and_activation_rules():
    cfg = MeshConfig(dp=2, sp=2)
    assert cfg.batch_spec(1) == P("dp")
    assert cfg.batch_spec(2) == P("dp", "sp")
    assert MeshConfig(dp=4).batch_spec(2) == P("dp", None)
    assert cfg.activation_rules() == {"residual": P("dp", "sp", None)}
    assert MeshConfig(dp=4).activation_rules() == {}


def test_mesh_factorizations_cover_exactly():
    cfgs = mesh_factorizations(8, max_sp=1)
    assert len(cfgs) == 10                        # ordered (dp,tp,pp) of 2^3
    assert all(c.size() == 8 and c.sp == 1 for c in cfgs)
    assert len(set(cfgs)) == len(cfgs)
    assert MeshConfig(dp=2, tp=2, pp=2) in cfgs
    with_sp = mesh_factorizations(8, max_sp=2)
    assert any(c.sp == 2 for c in with_sp)


def test_make_mesh_strands_warn_and_gauge():
    telemetry.enable()
    telemetry.reset()
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            make_mesh({"dp": 2})                  # 6 of 8 stranded
        assert any("stranded" in str(x.message) for x in w)
        assert telemetry.snapshot()["gauges"]["mesh.unused_devices"] == 6
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            make_mesh({"dp": 8})
        assert not w
        assert telemetry.snapshot()["gauges"]["mesh.unused_devices"] == 0
    finally:
        telemetry.disable()


# ---------------------------------------------------------------------------
# parity oracle: composed layouts vs single-device training
# ---------------------------------------------------------------------------

def test_composed_layouts_match_single_device():
    x, y = _batch()
    base = _gpt_step(MeshConfig(), x)
    ref = [float(base(x, y).asnumpy()) for _ in range(3)]
    for cfg, kw in [
        (MeshConfig(dp=2, tp=2, pp=2), {}),
        (MeshConfig(dp=4, tp=2), dict(zero=1)),
        (MeshConfig(dp=2, sp=2), {}),
    ]:
        step = _gpt_step(cfg, x, **kw)
        got = [float(step(x, y).asnumpy()) for _ in range(3)]
        onp.testing.assert_allclose(got, ref, rtol=0, atol=1e-5,
                                    err_msg=f"{cfg!r} {kw}")
        # zero recompiles after the first step
        assert step._step._cache_size() == 1, cfg


def test_pipeline_microbatching_via_grad_accum():
    """grad_accum IS the pipeline microbatch schedule: K stacked
    microbatches scanned through the pp stages equal one big-batch
    single-device step."""
    x, y = _batch()
    base = _gpt_step(MeshConfig(), x)
    ref = [float(base(x, y).asnumpy()) for _ in range(3)]
    step = _gpt_step(MeshConfig(dp=2, tp=2, pp=2), x, zero=2, grad_accum=2)
    xs, ys = x.reshape(2, 4, SEQ), y.reshape(2, 4, SEQ)
    got = [float(step(xs, ys).asnumpy()) for _ in range(3)]
    onp.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)
    assert step._step._cache_size() == 1


# ---------------------------------------------------------------------------
# ZeRO x TP: tensor-sharded params' state partitions over dp
# ---------------------------------------------------------------------------

def _state_bytes_on(step, device):
    total = 0
    for s in step.states.values():
        for leaf in jax.tree_util.tree_leaves(s):
            for shard in leaf.addressable_shards:
                if shard.device == device:
                    total += shard.data.nbytes
    return total


def test_zero_tp_partitions_tensor_sharded_state():
    from mxnet_tpu.gluon import nn

    def make(zero):
        mx.random.seed(7)
        net = nn.Dense(256, in_units=128)
        net.initialize()
        return ShardedTrainStep(
            net, lambda o, t: jnp.mean((o - t) ** 2),
            mx.optimizer.create("adam", learning_rate=0.01),
            MeshConfig(dp=4, tp=2), batch_specs=(P("dp"), P("dp")),
            n_labels=1, zero=zero,
            param_specs={"weight": P("tp", None), "bias": P("tp")})

    rs = onp.random.RandomState(0)
    x = rs.randn(16, 128).astype("float32")
    t = rs.randn(16, 256).astype("float32")
    dev0 = jax.devices()[0]
    repl = make(0)
    shard = make(1)
    l0 = [float(repl(x, t).asnumpy()) for _ in range(2)]
    l1 = [float(shard(x, t).asnumpy()) for _ in range(2)]
    onp.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    b0 = _state_bytes_on(repl, dev0)
    b1 = _state_bytes_on(shard, dev0)
    assert b1 <= b0 * 0.6, (b0, b1)               # the CI gate is >=40%


# ---------------------------------------------------------------------------
# elastic checkpoints: bitwise across (dp, tp, pp) layouts
# ---------------------------------------------------------------------------

def _assert_bitwise(sd_a, sd_b):
    assert sd_a["n_step"] == sd_b["n_step"]
    assert set(sd_a["arrays"]) == set(sd_b["arrays"])
    for k in sd_a["arrays"]:
        va, vb = sd_a["arrays"][k], sd_b["arrays"][k]
        assert va.shape == vb.shape and va.dtype == vb.dtype, k
        assert onp.array_equal(va, vb), k


def test_checkpoint_portable_across_layouts(tmp_path):
    x, y = _batch()
    a = _gpt_step(MeshConfig(dp=4, tp=2), x, zero=1)
    for _ in range(2):
        a(x, y)
    fname = str(tmp_path / "mesh.safetensors")
    a.save_states(fname)

    b = _gpt_step(MeshConfig(dp=2, tp=2, pp=2), x)
    b.load_states(fname)
    _assert_bitwise(a.state_dict(), b.state_dict())

    # both continue training in lockstep after the elastic restore
    la = [float(a(x, y).asnumpy()) for _ in range(2)]
    lb = [float(b(x, y).asnumpy()) for _ in range(2)]
    onp.testing.assert_allclose(la, lb, rtol=0, atol=1e-5)

    # reverse direction: (dp2,tp2,pp2) -> (dp4,tp2,zero1)
    fname2 = str(tmp_path / "mesh2.safetensors")
    b.save_states(fname2)
    c = _gpt_step(MeshConfig(dp=4, tp=2), x, zero=1)
    c.load_states(fname2)
    _assert_bitwise(b.state_dict(), c.state_dict())


def test_trainstate_bundle_carries_composed_step(tmp_path):
    x, y = _batch()
    a = _gpt_step(MeshConfig(dp=2, tp=2, pp=2), x)
    a(x, y)
    bundle = str(tmp_path / "run.bundle")
    st = mx.resilience.TrainState(sharded_step=a, path=bundle)
    st.step = 1
    st.save()

    b = _gpt_step(MeshConfig(dp=4, tp=2), x, zero=1)
    st2 = mx.resilience.TrainState(sharded_step=b, path=bundle)
    st2.load()
    assert st2.step == 1
    _assert_bitwise(a.state_dict(), b.state_dict())


# ---------------------------------------------------------------------------
# autotune: the mesh is one more search axis
# ---------------------------------------------------------------------------

def test_winner_key_mesh_component():
    from mxnet_tpu.autotune import winner_key
    assert winner_key("abcd", "TPU v4", 8) == "abcd|TPU v4|dp8"
    assert winner_key("abcd", "TPU v4", 8, mesh={"dp": 4, "tp": 2}) \
        == "abcd|TPU v4|dp8|mesh:dp4xtp2"
    assert winner_key("abcd", "TPU v4", 1, mesh=MeshConfig()) \
        == "abcd|TPU v4|dp1|mesh:1"


def test_search_space_mesh_axis():
    from mxnet_tpu import autotune
    meshes = [{"dp": 8}, MeshConfig(dp=4, tp=2)]
    space = autotune.SearchSpace(batch_size=16, steps_per_call=1,
                                 grad_accum=1, zero=0, remat=False,
                                 mesh=meshes)
    assert len(space) == 2
    cands = space.candidates()
    got = {tuple(sorted((a, s) for a, s in c.mesh.items() if s > 1))
           for c in cands}
    assert got == {(("dp", 8),), (("dp", 4), ("tp", 2))}
    c = cands[0]
    assert autotune.Candidate.from_config(c.config()).key() == c.key()
    with pytest.raises(MXNetError):
        autotune.SearchSpace(batch_size=16, mesh=["dp8"])


def test_autotune_searches_mesh_axis(tmp_path):
    from mxnet_tpu import autotune, config
    from mxnet_tpu.gluon import nn
    prior = config.get("autotune.cache_dir")
    config.set("autotune.cache_dir", str(tmp_path / "autotune"))
    try:
        _run_mesh_search(autotune, nn)
    finally:
        config.set("autotune.cache_dir", prior)


def _run_mesh_search(autotune, nn):
    mx.random.seed(0)
    net = nn.Dense(16, in_units=32)
    net.initialize()
    x = onp.random.RandomState(0).randn(16, 32).astype("float32")
    y = onp.random.RandomState(1).randn(16, 16).astype("float32")
    meshes = [m for m in mesh_factorizations(8, max_sp=1)
              if m.pp == 1 and m.tp <= 2][:3]
    assert len(meshes) > 1
    space = autotune.SearchSpace(batch_size=16, steps_per_call=1,
                                 grad_accum=1, zero=0, remat=False,
                                 mesh=meshes)
    res = autotune.search(net, lambda o, t: jnp.mean((o - t) ** 2), "sgd",
                          make_mesh({"dp": 1}), (None, None), (x, y),
                          n_labels=1, space=space)
    assert "|mesh:" in res.key
    assert res.config["mesh"] is not None
    res2 = autotune.search(net, lambda o, t: jnp.mean((o - t) ** 2), "sgd",
                           make_mesh({"dp": 1}), (None, None), (x, y),
                           n_labels=1, space=space)
    assert res2.reused
