"""ZeRO-sharded training: parity oracles, microbatch accumulation,
selective remat, topology-independent resume, memory telemetry.

Strategy (SURVEY §4 style): every optimization must be numerically
invisible — zero=1/2, grad_accum and remat each run against the plain
replicated step on the same seed/virtual CPU mesh and must reproduce
its parameters, not just its loss curve.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import numpy as np
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.train import ShardedTrainStep

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def _make_net(units=10, in_units=8, seed=7):
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    return net


def _loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def _data(n=16, in_units=8, classes=10, seed=1):
    rs = onp.random.RandomState(seed)
    x = rs.randn(n, in_units).astype("float32")
    y = rs.randint(0, classes, (n,)).astype("int32")
    return x, y


def _step(zero=0, mesh=None, opt=None, **kw):
    mesh = mesh or make_mesh({"dp": 4})
    opt = opt or mx.optimizer.create("adam", learning_rate=0.05)
    return ShardedTrainStep(_make_net(), _loss_fn, opt, mesh,
                            batch_specs=(P("dp"), P("dp")), n_labels=1,
                            zero=zero, **kw)


# ---------------------------------------------------------------------------
# parity oracles
# ---------------------------------------------------------------------------

def test_zero1_matches_replicated():
    """zero=1 must be numerically invisible: same seed, same batches,
    fp32-allclose params vs the replicated step after several updates."""
    x, y = _data()
    mx.random.seed(3)
    base = _step(zero=0)
    mx.random.seed(3)
    z1 = _step(zero=1)
    for _ in range(4):
        l0 = float(base(x, y).asnumpy())
        l1 = float(z1(x, y).asnumpy())
        onp.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    for n in base.trainable:
        onp.testing.assert_allclose(
            onp.asarray(z1.trainable[n]), onp.asarray(base.trainable[n]),
            rtol=1e-5, atol=1e-6)


def test_zero1_state_is_dp_sharded():
    """The point of ZeRO-1: optimizer state lives in 1/dp shards -- flat
    ones, or (the 10 x 8 weight, whose rows a flat quarter would cut)
    along the dimension dp divides."""
    z1 = _step(zero=1)
    dp = 4
    want = {"weight": P(None, "dp"), "bias": P("dp")}
    for n, leaves in ((n, jax.tree_util.tree_leaves(s))
                      for n, s in z1.states.items()):
        for leaf in leaves:
            assert leaf.sharding.spec == want[n], (n, leaf.sharding)
            shard = leaf.addressable_shards[0].data
            assert shard.size * dp == leaf.size, (n, shard.shape, leaf.shape)


# a GPT whose vocabulary dp=4 does not divide, head tied to the table: the
# table's flat quarter would cut its rows, so ZeRO lays it along the units
_GPT = dict(vocab_size=66, units=16, num_layers=2, num_heads=2, max_length=8)
_TABLE = "backbone.word_embed.weight"


def _gpt_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def _gpt_step(zero, dp=4, **kw):
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
    mx.random.seed(0)
    net = GPTForCausalLM(dropout=0.0, embed_dropout=0.0, **_GPT)
    net.initialize()
    net(np.array(onp.zeros((8, 8), "int32")))     # materialize the params
    return ShardedTrainStep(
        net, _gpt_loss, mx.optimizer.create("adam", learning_rate=0.01),
        make_mesh({"dp": dp}), batch_specs=(P("dp", None), P("dp", None)),
        n_labels=1, zero=zero, **kw)


def test_zero1_table_with_cut_rows_matches_replicated():
    """The tied table in the DP form under zero=1 x grad_accum=2: three
    updates give the zero=0 step's losses and parameters, and the lowered
    step holds the table's gradient in column blocks, P(None, "dp")."""
    import re
    rs = onp.random.RandomState(2)
    t = rs.randint(0, 66, (3, 8, 9)).astype("int32")
    base = _gpt_step(zero=0)
    z1 = _gpt_step(zero=1, grad_accum=2)
    lay = z1.layout
    assert lay.replicated_dp == [_TABLE]
    assert lay.leaves[_TABLE].state_spec == P(None, "dp")
    for leaf in jax.tree_util.tree_leaves(z1.states[_TABLE]):
        assert leaf.sharding.spec == P(None, "dp")
        assert leaf.addressable_shards[0].data.shape == (66, 4)
    for b in t:
        x, y = b[:, :-1], b[:, 1:]
        l0 = float(base(x, y).asnumpy())
        l1 = float(z1(x.reshape(2, 4, 8), y.reshape(2, 4, 8)).asnumpy())
        onp.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    for n in base.trainable:
        onp.testing.assert_allclose(
            onp.asarray(z1.trainable[n]), onp.asarray(base.trainable[n]),
            rtol=1e-5, atol=1e-5, err_msg=n)
    x = onp.zeros((2, 4, 8), "int32")
    text = z1.lower(x, x).as_text()
    # the update pins the table and its gradient to the state's columns ...
    assert len(re.findall(
        r'sdy\.sharding_constraint %\w+ <@mesh, \[\{\}, \{"dp"\}\]> : '
        r'tensor<66x16xf32>', text)) == 2
    # ... and the lookup hands the gradient over in them: ids gathered once
    # a micro-batch, where the forward uses them, an all-to-all each way
    assert len(re.findall(
        r'"stablehlo\.all_gather"\(%\w+\).*\(tensor<1x8xi32>\) -> '
        r'tensor<4x8xi32>', text)) == 1
    assert text.count('"stablehlo.all_to_all"') == 2
    assert 'out_shardings=[<@mesh, [{}, {"dp"}]>] manual_axes' in text


def test_train_plan_span_counts_the_forms():
    """``mx/train.plan``: leaves and bytes by form, and the replicated
    leaves that took the DP form -- GPT-2's own table, (50257, 1280), is
    one leaf of 257 MB under dp=4 and none under dp=1."""
    from mxnet_tpu.parallel.layout import StateLayout
    was = mx.trace.active()
    mx.trace.enable()
    try:
        mx.trace.clear()
        _gpt_step(zero=1)
        _gpt_step(zero=1, dp=1)
        _gpt_step(zero=0)
        plans = [ev["args"] for ev in mx.trace.spans(category="train")
                 if ev["name"] == "train.plan"]
    finally:
        mx.trace.enable(was)
    assert len(plans) == 3
    z1, one, z0 = plans
    table = 66 * 16 * 4
    assert (z1["replicated_dp_leaves"], z1["replicated_dp_bytes"]) == \
        (1, table)
    assert (z1["dp_leaves"], z1["flat_leaves"], z1["param_leaves"]) == \
        (1, 35, 0)                  # no tp axis: every other leaf is flat
    assert z1["dp_bytes"] == table
    assert (one["replicated_dp_leaves"], one["replicated_dp_bytes"]) == (0, 0)
    assert (one["dp_leaves"], one["flat_leaves"]) == (0, 36)
    assert (z0["param_leaves"], z0["dp_leaves"], z0["flat_leaves"]) == \
        (36, 0, 0)
    for p in plans:
        assert sum(p[f"{f}_bytes"] for f in ("param", "flat", "dp")) == \
            z0["param_bytes"]
    big = StateLayout({"word_embed.weight": (50257, 1280),
                       "position_embed.weight": (1024, 1280)}, {}, {},
                      {"dp": 4}, zero=1)
    census = big.census(dict.fromkeys(big.leaves, 4))
    assert (census["replicated_dp_leaves"],
            census["replicated_dp_bytes"]) == (1, 257315840)
    assert (census["flat_leaves"], census["dp_leaves"]) == (1, 1)


def test_zero2_with_grad_accum_matches_replicated():
    """zero=2 (dp-sharded grads + accumulator) composed with grad_accum
    still reproduces the plain step on the equivalent big batch."""
    x, y = _data(n=16)
    mx.random.seed(5)
    base = _step(zero=0)
    mx.random.seed(5)
    z2 = _step(zero=2, grad_accum=2)
    xs = x.reshape(2, 8, 8)
    ys = y.reshape(2, 8)
    for _ in range(3):
        l0 = float(base(x, y).asnumpy())
        l2 = float(z2(xs, ys).asnumpy())
        onp.testing.assert_allclose(l2, l0, rtol=1e-5, atol=1e-6)
    for n in base.trainable:
        onp.testing.assert_allclose(
            onp.asarray(z2.trainable[n]), onp.asarray(base.trainable[n]),
            rtol=1e-5, atol=1e-5)


def test_grad_accum_matches_one_big_batch():
    """K microbatches + ONE update == one update on the concatenated
    batch (mean loss => grads average; distinct from steps_per_call,
    which applies K updates)."""
    x, y = _data(n=32)
    mx.random.seed(11)
    big = _step()
    mx.random.seed(11)
    accum = _step(grad_accum=4)
    for _ in range(3):
        lb = float(big(x, y).asnumpy())
        la = float(accum(x.reshape(4, 8, 8), y.reshape(4, 8)).asnumpy())
        onp.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    assert accum._n_step == 3  # 3 optimizer updates, not 12
    assert accum.fopt.opt.num_update == 3
    for n in big.trainable:
        onp.testing.assert_allclose(
            onp.asarray(accum.trainable[n]), onp.asarray(big.trainable[n]),
            rtol=1e-5, atol=1e-5)


def test_remat_output_equivalence():
    """jax.checkpoint changes memory, never values: remat='dots' and
    remat=True reproduce the un-remat step bitwise-close."""
    x, y = _data()
    results = {}
    for remat in (None, "dots", True):
        mx.random.seed(13)
        step = _step(remat=remat)
        losses = [float(step(x, y).asnumpy()) for _ in range(3)]
        results[remat] = (losses, {n: onp.asarray(v)
                                   for n, v in step.trainable.items()})
    for remat in ("dots", True):
        onp.testing.assert_allclose(results[remat][0], results[None][0],
                                    rtol=1e-6, atol=1e-7)
        for n, w in results[None][1].items():
            onp.testing.assert_allclose(results[remat][1][n], w,
                                        rtol=1e-6, atol=1e-7)


def test_hybridize_remat_flag_flows_into_step():
    """hybridize(remat=...) is the user-facing knob: the step inherits it
    and bad policy names fail fast at hybridize time."""
    from mxnet_tpu.gluon.block import resolve_remat_policy, _REMAT_OFF
    net = _make_net()
    net.hybridize(remat="dots")
    assert net._flags.get("remat") == "dots"
    mesh = make_mesh({"dp": 4})
    step = ShardedTrainStep(net, _loss_fn, "adam", mesh,
                            batch_specs=(P("dp"), P("dp")), n_labels=1)
    assert step._remat_on
    with pytest.raises(MXNetError):
        resolve_remat_policy("not_a_policy")
    assert resolve_remat_policy(False) is _REMAT_OFF


# ---------------------------------------------------------------------------
# schedules / guards
# ---------------------------------------------------------------------------

def test_lr_schedule_advances_in_compiled_step():
    """Regression: the compiled step used to leave num_update at 0, so
    warmup/decay schedules were frozen at their step-0 value forever."""
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.5)
    opt = mx.optimizer.create("sgd", learning_rate=0.1, lr_scheduler=sched)
    step = _step(opt=opt)
    x, y = _data()
    assert opt.num_update == 0
    seen = []
    for _ in range(3):
        seen.append(float(sched(opt.num_update + 1)))
        step(x, y)
    assert opt.num_update == 3
    onp.testing.assert_allclose(seen, [0.1, 0.05, 0.025], rtol=1e-6)


def test_steps_per_call_advances_update_count():
    opt = mx.optimizer.create("adam", learning_rate=0.05)
    step = _step(opt=opt, steps_per_call=3, zero=1)
    x, y = _data(n=24)
    step(x.reshape(3, 8, 8), y.reshape(3, 8))
    assert step._n_step == 3
    assert opt.num_update == 3


def test_zero_rejects_non_elementwise_optimizer():
    """Norm-based rules (LAMB/LARS: whole-tensor trust ratios) would be
    silently wrong on 1/dp shards — must refuse loudly."""
    with pytest.raises(MXNetError, match="not elementwise"):
        _step(zero=1, opt=mx.optimizer.create("lamb"))
    with pytest.raises(MXNetError, match="zero must be"):
        _step(zero=3)
    mesh = make_mesh({"tp": 4})
    with pytest.raises(MXNetError, match="mesh axis"):
        ShardedTrainStep(_make_net(), _loss_fn, "adam", mesh,
                         batch_specs=(P("tp"), P("tp")), n_labels=1, zero=1)


# ---------------------------------------------------------------------------
# topology-independent checkpoints
# ---------------------------------------------------------------------------

def test_zero_checkpoint_resume_bitwise_other_dp(tmp_path):
    """A zero=1 bundle saved at dp=4 restores bitwise at dp=2 (and into a
    replicated zero=0 step): the canonical gathered layout makes resume
    independent of the saving run's topology."""
    x, y = _data()
    mx.random.seed(21)
    src = _step(zero=1)
    for _ in range(2):
        src(x, y)
    fname = str(tmp_path / "zero.ckpt")
    src.save_states(fname)
    canon = src.state_dict()["arrays"]

    for dp, zero in ((2, 1), (4, 0)):
        mx.random.seed(99)  # different init; load must overwrite all of it
        dst = _step(zero=zero, mesh=make_mesh({"dp": dp}))
        dst.load_states(fname)
        assert dst._n_step == 2
        assert dst.fopt.opt.num_update == 2
        got = dst.state_dict()["arrays"]
        assert set(got) == set(canon)
        for k in canon:
            onp.testing.assert_array_equal(got[k], canon[k])

    # and the continuation matches: one more step on each topology
    mx.random.seed(33)
    cont_src = [float(src(x, y).asnumpy()) for _ in range(2)]
    mx.random.seed(33)
    dst = _step(zero=1, mesh=make_mesh({"dp": 2}))
    dst.load_states(fname)
    cont_dst = [float(dst(x, y).asnumpy()) for _ in range(2)]
    onp.testing.assert_allclose(cont_dst, cont_src, rtol=1e-5, atol=1e-6)


def test_trainstate_bundles_sharded_step(tmp_path):
    """mx.resilience.TrainState carries the sharded step's canonical
    state through its crash-atomic bundle — preemption-safe dp-sharded
    training, resumable at a different dp size."""
    x, y = _data()
    mx.random.seed(41)
    src = _step(zero=1)
    state = mx.resilience.TrainState(sharded_step=src,
                                     path=str(tmp_path / "run.bundle"))
    for _ in range(2):
        src(x, y)
        state.step += 1
    state.save()

    mx.random.seed(77)
    dst = _step(zero=1, mesh=make_mesh({"dp": 2}))
    state2 = mx.resilience.TrainState(sharded_step=dst,
                                      path=str(tmp_path / "run.bundle"))
    state2.load()
    assert state2.step == 2
    assert dst._n_step == 2
    canon, got = src.state_dict()["arrays"], dst.state_dict()["arrays"]
    for k in canon:
        onp.testing.assert_array_equal(got[k], canon[k])


# ---------------------------------------------------------------------------
# telemetry planes
# ---------------------------------------------------------------------------

def test_record_memory_gauges():
    """memory.* plane: backends that report PJRT memory_stats populate
    per-device gauges; stat-less backends (CPU) stay an empty no-op."""
    from mxnet_tpu import telemetry

    class _Dev:
        def __init__(self, i):
            self.id = i

        def memory_stats(self):
            return {"bytes_in_use": 100 + self.id,
                    "peak_bytes_in_use": 200 + self.id,
                    "bytes_limit": 1000}

    class _NoStats:
        id = 9

        def memory_stats(self):
            return None

    telemetry.enable()
    try:
        telemetry.reset()
        out = telemetry.record_memory([_Dev(0), _Dev(1), _NoStats()])
        assert out == {"0": {"live": 100, "peak": 200, "limit": 1000},
                       "1": {"live": 101, "peak": 201, "limit": 1000}}
        snap = telemetry.snapshot()
        assert snap["gauges"]['memory.bytes_in_use{device="1"}'] == 101
        assert snap["gauges"]['memory.peak_bytes_in_use{device="0"}'] == 200
        # CPU path inside a report: no stats, no crash, empty plane
        assert telemetry.record_memory() == {}
    finally:
        telemetry.disable()


def test_training_telemetry_report_has_memory_plane():
    from mxnet_tpu.telemetry import TrainingTelemetry
    tt = TrainingTelemetry()
    with tt:
        pass
    report = tt.report()
    assert "memory" in report
    assert isinstance(report["memory"], dict)
