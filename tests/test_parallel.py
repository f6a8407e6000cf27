"""Distributed/parallel tests on the virtual 8-device CPU mesh.

Reference strategy analog: tests/nightly/dist_sync_kvstore.py runs real
multi-process reduces and asserts exact equality (SURVEY §4) — here the
collectives run on a real 8-device mesh (xla_force_host_platform_device
_count) and are checked against numpy oracles.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import numpy as np
from mxnet_tpu.parallel import (allgather, allreduce, make_mesh,
                                reduce_scatter, ring_attention)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh({"dp": 8})


def test_allreduce_oracle(mesh8):
    x = onp.arange(32, dtype="float32").reshape(8, 4)
    arr = jax.device_put(jnp.asarray(x), NamedSharding(mesh8, P("dp")))
    out = allreduce(arr, mesh8, axis="dp")
    # every shard holds the sum over the dp axis of its own block-row stack
    expect = onp.tile(x.sum(0, keepdims=True), (8, 1))
    onp.testing.assert_allclose(onp.asarray(out), expect, rtol=1e-6)


def test_allgather_reduce_scatter(mesh8):
    x = onp.arange(16, dtype="float32").reshape(8, 2)
    arr = jax.device_put(jnp.asarray(x), NamedSharding(mesh8, P("dp")))
    gathered = allgather(arr, mesh8, axis="dp")
    onp.testing.assert_allclose(onp.asarray(gathered), x)
    # replicated input: every device contributes a full copy, so the
    # reduced+scattered result is 8*x distributed over the axis
    rs = reduce_scatter(jnp.asarray(x), mesh8, axis="dp")
    onp.testing.assert_allclose(onp.asarray(rs), 8 * x)


def test_ring_attention_matches_reference():
    mesh = make_mesh({"sp": 8})
    b, h, s, d = 2, 4, 64, 16
    onp.random.seed(0)
    q = jnp.asarray(onp.random.randn(b, h, s, d).astype("float32"))
    k = jnp.asarray(onp.random.randn(b, h, s, d).astype("float32"))
    v = jnp.asarray(onp.random.randn(b, h, s, d).astype("float32"))

    def ref(causal):
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
        if causal:
            m = jnp.tril(jnp.ones((s, s), bool))
            s_ = jnp.where(m, s_, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s_, -1), v)

    for causal in (False, True):
        out = ring_attention(q, k, v, mesh, axis="sp", causal=causal)
        onp.testing.assert_allclose(onp.asarray(out),
                                    onp.asarray(ref(causal)), atol=2e-5)


@pytest.mark.slow
def test_sharded_train_step_bert_dp_tp_sp():
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretraining
    from mxnet_tpu.parallel.mesh import activation_sharding
    from mxnet_tpu.parallel.train import ShardedTrainStep

    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    net = BERTForPretraining(vocab_size=96, units=64, hidden_size=128,
                             num_layers=2, num_heads=4, max_length=32,
                             dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(np.zeros((4, 16), dtype="int32"))

    def loss_fn(outputs, labels):
        mlm, _ = outputs
        logp = jax.nn.log_softmax(mlm.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    with activation_sharding(mesh, residual=P("dp", "sp", None)):
        step = ShardedTrainStep(net, loss_fn, "adam", mesh,
                                batch_specs=(P("dp", "sp"), P("dp", "sp")),
                                n_labels=1)
        ids = onp.random.randint(0, 96, (8, 16)).astype("int32")
        losses = [float(step(ids, ids).asnumpy()) for _ in range(6)]
    assert losses[-1] < losses[0], losses
    # megatron specs actually applied
    w = step.trainable[
        "backbone.encoder.layer0.attention.query_proj.weight"]
    assert w.sharding.spec == P("tp", None)
    w2 = step.trainable["backbone.encoder.layer0.attention.out_proj.weight"]
    assert w2.sharding.spec == P(None, "tp")
    step.sync_to_block()


def test_sharded_train_step_matches_single_device():
    """dp-sharded compiled step must match the eager Trainer numerically."""
    from mxnet_tpu.gluon import Trainer, nn
    from mxnet_tpu.parallel.train import ShardedTrainStep
    from mxnet_tpu import autograd

    def make_net():
        mx.random.seed(7)
        net = nn.Dense(4, in_units=8)
        net.initialize()
        return net

    mesh = make_mesh({"dp": 8})
    onp.random.seed(1)
    x = onp.random.randn(16, 8).astype("float32")
    y = onp.random.randint(0, 4, (16,)).astype("int32")

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    net1 = make_net()
    step = ShardedTrainStep(
        net1, loss_fn, mx.optimizer.create("sgd", learning_rate=0.1),
        mesh, batch_specs=(P("dp"), P("dp")), n_labels=1)
    for _ in range(3):
        step(x, y)
    step.sync_to_block()
    w_sharded = net1.weight.data().asnumpy()

    net2 = make_net()
    trainer = Trainer(net2.collect_params(), "sgd",
                      {"learning_rate": 0.1})
    from mxnet_tpu import numpy_extension as npx
    for _ in range(3):
        with autograd.record():
            logits = net2(np.array(x))
            loss = -(npx.pick(npx.log_softmax(logits, axis=-1),
                              np.array(y))).mean()
        loss.backward()
        trainer.step(1, ignore_stale_grad=True)
    w_eager = net2.weight.data().asnumpy()
    onp.testing.assert_allclose(w_sharded, w_eager, atol=1e-5)


def test_gpipe_matches_sequential():
    """Pipeline parallelism: fwd and grads equal the unpipelined stack."""
    from mxnet_tpu.parallel.pp import (gpipe, shard_stages,
                                       stack_stage_params)
    mesh = make_mesh({"pp": 4})
    S, M, mb, d = 4, 6, 2, 8
    onp.random.seed(0)
    Ws = [onp.random.randn(d, d).astype("float32") * 0.5 for _ in range(S)]
    params = shard_stages(stack_stage_params(
        [{"w": jnp.asarray(w)} for w in Ws]), mesh)
    xs = jnp.asarray(onp.random.randn(M, mb, d).astype("float32"))

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    ys = gpipe(stage, params, xs, mesh)
    ref = xs
    for w in Ws:
        ref = jnp.tanh(ref @ jnp.asarray(w))
    onp.testing.assert_allclose(onp.asarray(ys), onp.asarray(ref),
                                atol=1e-5)

    g = jax.grad(lambda p: gpipe(stage, p, xs, mesh).sum())(params)
    gref = jax.grad(lambda ws: _seq_loss(ws, xs))(
        jnp.stack([jnp.asarray(w) for w in Ws]))
    onp.testing.assert_allclose(onp.asarray(g["w"]), onp.asarray(gref),
                                atol=1e-4)


def _seq_loss(ws, xs):
    r = xs
    for i in range(ws.shape[0]):
        r = jnp.tanh(r @ ws[i])
    return r.sum()


def test_moe_top1_oracle_and_ep_sharding():
    import math
    from mxnet_tpu.gluon.nn.moe import MoEDense, moe_expert_specs
    from mxnet_tpu.parallel.train import ShardedTrainStep

    mx.random.seed(0)
    onp.random.seed(0)
    moe = MoEDense(16, 32, num_experts=4, num_experts_per_tok=1,
                   capacity_factor=8.0)
    moe.initialize()
    x = np.array(onp.random.randn(2, 6, 16).astype("float32"))
    out, aux = moe(x)
    assert out.shape == (2, 6, 16)

    g = moe.gate.data().asnumpy()
    wi = moe.w_in.data().asnumpy()
    wo = moe.w_out.data().asnumpy()
    toks = x.asnumpy().reshape(-1, 16)
    logits = toks @ g
    probs = onp.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    choice = probs.argmax(-1)
    ref = onp.zeros_like(toks)
    for t in range(toks.shape[0]):
        e = choice[t]
        h = toks[t] @ wi[e]
        h = 0.5 * h * (1 + onp.array([math.erf(v / 2 ** 0.5) for v in h]))
        ref[t] = probs[t, e] * (h @ wo[e])
    onp.testing.assert_allclose(out.asnumpy().reshape(-1, 16), ref,
                                atol=1e-4)

    # expert-parallel training over dp x ep
    mesh = make_mesh({"dp": 2, "ep": 4})

    def loss_fn(outputs, y):
        o, aux = outputs
        return jnp.mean((o - y) ** 2) + 0.01 * aux

    step = ShardedTrainStep(moe, loss_fn, "adam", mesh,
                            batch_specs=(P("dp"), P("dp")), n_labels=1,
                            param_specs=moe_expert_specs())
    xb = onp.random.randn(8, 6, 16).astype("float32")
    losses = [float(step(xb, xb).asnumpy()) for _ in range(5)]
    assert losses[-1] < losses[0]
    assert step.trainable["w_in"].sharding.spec == P("ep", None, None)


def test_moe_aux_loss_penalizes_collapse_under_tight_capacity():
    """Regression: f must come from pre-capacity-drop routing, so the
    balance loss still distinguishes collapse when the hot expert
    overflows (Switch formulation)."""
    from mxnet_tpu.gluon.nn.moe import MoEDense
    mx.random.seed(0)
    onp.random.seed(0)
    moe = MoEDense(8, 16, num_experts=4, num_experts_per_tok=1,
                   capacity_factor=1.0)
    moe.initialize()
    x = np.array(onp.abs(onp.random.randn(2, 8, 8)).astype("float32"))
    # all-positive tokens + one-hot gate column => full collapse to expert 0
    moe.gate.set_data(np.array(onp.concatenate(
        [onp.full((8, 1), 5.0), onp.zeros((8, 3))], 1).astype("float32")))
    _, aux_collapsed = moe(x)
    moe.gate.set_data(np.zeros((8, 4)))
    _, aux_balanced = moe(x)
    assert float(aux_collapsed.asnumpy()) > float(aux_balanced.asnumpy()) + 0.5


def test_gpipe_rejects_stage_count_mismatch():
    from mxnet_tpu.parallel.pp import gpipe, stack_stage_params
    mesh = make_mesh({"pp": 4})
    params8 = stack_stage_params([{"w": jnp.ones((4, 4))}
                                  for _ in range(8)])
    with pytest.raises(ValueError, match="pp axis size"):
        gpipe(lambda p, x: x @ p["w"], params8, jnp.ones((2, 2, 4)), mesh)


def test_moe_topk_validation():
    from mxnet_tpu.gluon.nn.moe import MoEDense
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        MoEDense(8, 16, num_experts=2, num_experts_per_tok=3)


def test_moe_top2_oracle():
    """Top-2 routing with GShard gate renormalization vs a numpy oracle."""
    import math
    from mxnet_tpu.gluon.nn.moe import MoEDense

    mx.random.seed(3)
    onp.random.seed(3)
    moe = MoEDense(8, 16, num_experts=4, num_experts_per_tok=2,
                   capacity_factor=8.0)  # capacity high: no drops
    moe.initialize()
    x = np.array(onp.random.randn(1, 5, 8).astype("float32"))
    out, aux = moe(x)

    g = moe.gate.data().asnumpy()
    wi = moe.w_in.data().asnumpy()
    wo = moe.w_out.data().asnumpy()
    toks = x.asnumpy().reshape(-1, 8)
    logits = toks @ g
    probs = onp.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = onp.zeros_like(toks)
    for t in range(toks.shape[0]):
        top2 = onp.argsort(-probs[t])[:2]
        denom = probs[t, top2].sum() + 1e-9
        for e in top2:
            h = toks[t] @ wi[e]
            h = 0.5 * h * (1 + onp.array(
                [math.erf(v / 2 ** 0.5) for v in h]))
            ref[t] += (probs[t, e] / denom) * (h @ wo[e])
    onp.testing.assert_allclose(out.asnumpy().reshape(-1, 8), ref,
                                atol=1e-4)


def test_scan_steps_matches_sequential():
    """K fused steps (one executable) must equal K sequential step calls."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import scan_steps

    def step(w, m, x, y):
        g = 2 * (w * x - y) * x
        m = 0.9 * m + g
        w = w - 0.1 * m
        return w, m, jnp.mean((w * x - y) ** 2)

    w0 = jnp.asarray(0.5)
    m0 = jnp.zeros(())
    xs = jnp.asarray([1.0, 2.0, 0.5, 1.5])
    ys = jnp.asarray([2.0, 4.0, 1.0, 3.0])

    # sequential oracle
    w, m = w0, m0
    losses = []
    for x, y in zip(xs, ys):
        w, m, l = step(w, m, x, y)
        losses.append(float(l))

    loop = jax.jit(scan_steps(step, n_state=2))
    w2, m2, lmean = loop(w0, m0, xs, ys)
    onp.testing.assert_allclose(float(w2), float(w), rtol=1e-6)
    onp.testing.assert_allclose(float(m2), float(m), rtol=1e-6)
    onp.testing.assert_allclose(float(lmean), onp.mean(losses), rtol=1e-6)


def test_sharded_train_step_steps_per_call():
    """steps_per_call=K over stacked batches matches K single-step calls."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    from jax.sharding import PartitionSpec as P

    def build():
        net = nn.Dense(4, in_units=8)
        net.initialize()
        return net

    rs = onp.random.RandomState(0)
    xs = rs.randn(2, 8, 8).astype("float32")   # K=2 stacked batches
    ys = rs.randn(2, 8, 4).astype("float32")

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    mesh = make_mesh({"dp": min(2, len(jax.devices()))})

    mx.random.seed(7)
    a = build()
    s1 = ShardedTrainStep(a, loss_fn, "sgd", mesh, (P("dp"), P("dp")))
    for i in range(2):
        s1(xs[i], ys[i])

    mx.random.seed(7)   # same init as `a`
    b = build()
    s2 = ShardedTrainStep(b, loss_fn, "sgd", mesh, (P("dp"), P("dp")),
                          steps_per_call=2)
    s2(xs, ys)

    for n in s1.trainable:
        onp.testing.assert_allclose(
            onp.asarray(s2.trainable[n]), onp.asarray(s1.trainable[n]),
            rtol=1e-5, atol=1e-6, err_msg=n)


def test_sharded_train_step_checkpoint_resume(tmp_path):
    """save_states/load_states must make interrupted == uninterrupted
    training (reference: Trainer save/load_states round-trip)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    from jax.sharding import PartitionSpec as P

    rs = onp.random.RandomState(3)
    xs = [rs.randn(8, 6).astype("float32") for _ in range(3)]
    ys = [rs.randn(8, 4).astype("float32") for _ in range(3)]

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    mesh = make_mesh({"dp": 2})

    def build():
        mx.random.seed(11)
        net = nn.Dense(4, in_units=6)
        net.initialize()
        return ShardedTrainStep(net, loss_fn, "adam", mesh,
                                (P("dp"), P("dp")))

    # uninterrupted: 3 steps
    s_full = build()
    for i in range(3):
        s_full(xs[i], ys[i])

    # interrupted: 2 steps -> save -> fresh object -> load -> 1 step
    s_a = build()
    for i in range(2):
        s_a(xs[i], ys[i])
    ckpt = str(tmp_path / "step")
    s_a.save_states(ckpt)
    s_b = build()
    s_b.load_states(ckpt)
    assert s_b._n_step == 2
    s_b(xs[2], ys[2])

    for n in s_full.trainable:
        onp.testing.assert_allclose(
            onp.asarray(s_b.trainable[n]), onp.asarray(s_full.trainable[n]),
            rtol=1e-5, atol=1e-6, err_msg=n)


def test_batchnorm_is_sync_under_dp_mesh():
    """BatchNorm over a dp-sharded batch reduces over the GLOBAL batch
    (GSPMD one-program semantics) — the free SyncBatchNorm: running
    stats after a sharded step equal the single-device full-batch run."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    from jax.sharding import PartitionSpec as P

    rs = onp.random.RandomState(5)
    x = (rs.randn(16, 6) * 3 + 1).astype("float32")
    y = rs.randn(16, 4).astype("float32")

    def loss_fn(out, yy):
        return jnp.mean((out - yy) ** 2)

    def build():
        mx.random.seed(13)
        net = nn.HybridSequential()
        net.add(nn.Dense(4, in_units=6), nn.BatchNorm())
        net.initialize()
        net(mx.np.array(x))   # materialize BN params
        return net

    outs = {}
    for name, axes in [("sharded", {"dp": 8}), ("single", {"dp": 1})]:
        net = build()
        step = ShardedTrainStep(net, loss_fn, "sgd", make_mesh(axes),
                                (P("dp"), P("dp")))
        step(x, y)
        outs[name] = {n: onp.asarray(v) for n, v in step.aux.items()}
    for n in outs["single"]:
        onp.testing.assert_allclose(outs["sharded"][n], outs["single"][n],
                                    rtol=1e-5, atol=1e-6, err_msg=n)


def test_weak_scaling_table():
    """KVStore DP weak-scaling harness (BASELINE.md north star #3): rows at
    n=1/2/4 device-sublist meshes, fixed per-device batch, efficiency
    relative to n=1."""
    from mxnet_tpu.parallel.scaling import weak_scaling_table
    rows = weak_scaling_table(ns=[1, 2], per_device_batch=1, image=16,
                              iters=1, warmup=0)
    assert [r["n"] for r in rows] == [1, 2]
    assert rows[0]["efficiency"] == 1.0
    # rows and batches only: a CPU step time is a count or a check, never
    # a speed (PERF.md), so no bound is put on the ratio of two of them
    for r in rows:
        assert r["ms_per_step"] > 0
        assert r["global_batch"] == r["n"]
