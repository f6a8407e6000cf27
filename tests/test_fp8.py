"""fp8 training with delayed scaling + compressed gradient collectives
(docs/PRECISION.md).

Oracles: the fp8 step against the fp32 reference on the same seed and
batches (loss-curve parity, not bitwise — the format genuinely rounds),
the EF-compressed dp reduction against the uncompressed step (error
feedback telescopes; one collective a bucket in the lowered program),
checkpoint round-trips
bitwise through an elastic dp resize, and the serve/autotune guards
that keep fp8 from shipping where it is unproven.

Note: seed BEFORE ``initialize()`` — Dense with ``in_units`` known
materializes weights immediately, so a seed set after construction
never reaches the initializer.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import config as mxconfig, telemetry
from mxnet_tpu.amp import fp8
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import compressed_allreduce, make_mesh
from mxnet_tpu.parallel.train import ShardedTrainStep

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")

UNITS, IN_UNITS = 32, 16   # weight 32x16 = 512 elems >= amp.fp8_min_elems


def _make_net(units=UNITS, in_units=IN_UNITS, seed=7):
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    return net


def _loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def _data(n=16, in_units=IN_UNITS, classes=UNITS, seed=1):
    rs = onp.random.RandomState(seed)
    x = rs.randn(n, in_units).astype("float32")
    y = rs.randint(0, classes, (n,)).astype("int32")
    return x, y


def _step(precision="fp32", compress="none", mesh=None, opt=None, seed=7,
          **kw):
    mesh = mesh or make_mesh({"dp": 4})
    opt = opt or mx.optimizer.create("adam", learning_rate=0.05)
    return ShardedTrainStep(_make_net(seed=seed), _loss_fn, opt, mesh,
                            batch_specs=(P("dp"), P("dp")), n_labels=1,
                            precision=precision, grad_compress=compress,
                            **kw)


# ---------------------------------------------------------------------------
# the fp8 primitive + delayed-scaling state (no mesh)
# ---------------------------------------------------------------------------

def test_select_sites_filters_shape_and_floor():
    shapes = {"dense0.weight": (32, 16),    # 512 elems: eligible
              "dense0.bias": (32,),         # 1-D: never
              "tiny.weight": (8, 8),        # 64 < min_elems floor
              "emb.weight": (4, 8, 8)}      # not 2-D
    assert fp8.select_sites(shapes) == ["dense0.weight"]


def test_zero_history_means_identity_scales():
    state = fp8.init_state(["s"], history=4)
    xs, ws, gs = fp8.scales_from_state(state)["s"]
    assert float(xs) == 1.0 and float(ws) == 1.0 and float(gs) == 1.0


def test_roll_state_and_scale_formula():
    state = fp8.init_state(["s"], history=3)
    amax = jnp.float32(2.0)
    state = fp8.roll_state(state, {"s": (amax, amax)}, {"s": amax})
    h = state["s"]
    onp.testing.assert_allclose(onp.asarray(h["x"]), [2.0, 0.0, 0.0])
    onp.testing.assert_allclose(onp.asarray(h["g"]), [2.0, 0.0, 0.0])
    xs, ws, gs = fp8.scales_from_state(state, margin=1.0)["s"]
    _, fwd_max = fp8.FP8_FORMATS[fp8.FWD_FORMAT]
    _, bwd_max = fp8.FP8_FORMATS[fp8.BWD_FORMAT]
    onp.testing.assert_allclose(float(xs), fwd_max / 2.0, rtol=1e-6)
    onp.testing.assert_allclose(float(gs), bwd_max / 2.0, rtol=1e-6)
    # a second roll shifts the history window
    state = fp8.roll_state(state, {"s": (jnp.float32(1.0),) * 2},
                           {"s": jnp.float32(1.0)})
    onp.testing.assert_allclose(onp.asarray(state["s"]["x"]),
                                [1.0, 2.0, 0.0])


def test_merge_amax_takes_elementwise_max():
    a = {"s": (jnp.float32(1.0), jnp.float32(3.0))}
    b = {"s": (jnp.float32(2.0), jnp.float32(0.5)), "t": (jnp.float32(9.0),)}
    out = fp8.merge_amax(a, b)
    assert float(out["s"][0]) == 2.0 and float(out["s"][1]) == 3.0
    assert float(out["t"][0]) == 9.0


def test_fp8_linear_value_and_gradient_amax_cotangent():
    """fp8_linear == fp32 dot of fp8-snapped operands, and the g_scale
    slot's cotangent carries max |dy| out of the backward trace."""
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8).astype("float32"))
    w = jnp.asarray(rs.randn(6, 8).astype("float32"))
    b = jnp.asarray(rs.randn(6).astype("float32"))
    one = jnp.float32(1.0)
    y, vjp = jax.vjp(fp8.fp8_linear, x, w, b, one, one, one)
    dt, _ = fp8.FP8_FORMATS[fp8.FWD_FORMAT]
    ref = (x.astype(dt).astype(jnp.float32)
           @ w.astype(dt).astype(jnp.float32).T + b)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(rs.randn(4, 6).astype("float32"))
    dx, dw, db, dxs, dws, g_amax = vjp(dy)
    assert float(g_amax) == pytest.approx(float(jnp.max(jnp.abs(dy))))
    assert float(dxs) == 0.0 and float(dws) == 0.0
    # gradients through the e5m2-snapped dy against the fp32 chain rule
    gdt, _ = fp8.FP8_FORMATS[fp8.BWD_FORMAT]
    qdy = dy.astype(gdt).astype(jnp.float32)
    onp.testing.assert_allclose(
        onp.asarray(dx),
        onp.asarray(qdy @ w.astype(dt).astype(jnp.float32)),
        rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(db), onp.asarray(dy.sum(0)),
                                rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the fp8 training step
# ---------------------------------------------------------------------------

def test_fp8_step_tracks_fp32_loss_curve():
    x, y = _data()
    mx.random.seed(3)
    ref = _step("fp32")
    mx.random.seed(3)
    s8 = _step("fp8")
    assert s8.layout.fp8_sites, "Dense weight must be an eligible fp8 site"
    for _ in range(4):
        l0 = float(ref(x, y).asnumpy())
        l8 = float(s8(x, y).asnumpy())
        assert abs(l8 - l0) / max(abs(l0), 1e-8) < 0.05, (l8, l0)
    assert getattr(s8.block, "_fp8_trained", False)


def test_fp8_amax_history_rolls_per_update():
    s8 = _step("fp8")
    x, y = _data()
    site = s8.layout.fp8_sites[0]
    h0 = {k: onp.asarray(v) for k, v in s8.extra["fp8"][site].items()}
    assert all((v == 0).all() for v in h0.values())
    s8(x, y)
    s8(x, y)
    h = {k: onp.asarray(v) for k, v in s8.extra["fp8"][site].items()}
    for k in ("x", "w", "g"):
        assert h[k][0] > 0.0 and h[k][1] > 0.0, (k, h[k])
        assert (h[k][2:] == 0.0).all(), (k, h[k])


def test_fp8_with_grad_accum_and_steps_per_call():
    """fp8 composes with microbatch accumulation and fused multi-step
    calls: one history roll per OPTIMIZER update, counts advance."""
    opt = mx.optimizer.create("adam", learning_rate=0.05)
    s8 = _step("fp8", opt=opt, grad_accum=2, steps_per_call=2)
    x, y = _data(n=32)
    s8(x.reshape(2, 2, 8, IN_UNITS), y.reshape(2, 2, 8))
    assert s8._n_step == 2
    assert opt.num_update == 2
    site = s8.layout.fp8_sites[0]
    h = onp.asarray(s8.extra["fp8"][site]["x"])
    assert h[0] > 0 and h[1] > 0 and (h[2:] == 0).all()


# ---------------------------------------------------------------------------
# compressed dp collectives (error feedback)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_compressed_step_tracks_uncompressed(mode):
    x, y = _data()
    mx.random.seed(5)
    ref = _step("fp32", "none")
    mx.random.seed(5)
    comp = _step("fp32", mode)
    for _ in range(5):
        l0 = float(ref(x, y).asnumpy())
        lc = float(comp(x, y).asnumpy())
        # EF keeps the trajectory unbiased; per-step drift stays small
        assert abs(lc - l0) / max(abs(l0), 1e-8) < 0.05, (mode, lc, l0)


@pytest.mark.parametrize("bucket_mb,n_buckets", [(25.0, 1), (0.001, 2)])
def test_compressed_step_lowers_one_dp_reduce_per_bucket(bucket_mb,
                                                         n_buckets):
    """What crosses the dp axis is in the lowered program, not in an
    estimate: every bucket's quantized payload is ONE all-reduce over the
    four dp ranks (the independent collectives XLA overlaps with the
    backward), fed by the clip to +-127.  The payload is int8-VALUED but
    its operands are f32 — the sum of four ranks does not fit 8 bits — so
    the program never had an 8-bit wire (PERF.md section 6, PR 30)."""
    import re
    prev = mxconfig.get("comm.bucket_mb")
    mxconfig.set("comm.bucket_mb", bucket_mb)
    try:
        comp = _step("fp32", "int8")
    finally:
        mxconfig.set("comm.bucket_mb", prev)
    sizes = [sum(s for _, _, s in b) for b in comp.layout.buckets]
    assert len(sizes) == n_buckets and sum(sizes) == UNITS * IN_UNITS + UNITS
    text = comp.lower(*_data()).as_text()
    reduces = re.findall(
        r'"stablehlo\.all_reduce"\((%\w+)\).*?replica_groups = dense<'
        r'(\[\[[^>]*\]\])>.*?\}\) : \(tensor<([^>]*)>\)', text, re.S)
    assert all(groups == "[[0, 1, 2, 3]]" for _, groups, _ in reduces)
    payloads = [(v, t) for v, _, t in reduces if "x" in t]
    assert [t for _, t in payloads] == [f"{n}xf32" for n in sizes]
    for v, _ in payloads:
        assert re.search(rf"{v} = func\.call @clip\w*\(", text), v
    plain = _step("fp32", "none").lower(*_data()).as_text()
    assert "stablehlo.all_reduce" not in plain   # GSPMD's own, after lowering


def test_error_feedback_residual_carries_quantization_error():
    comp = _step("fp32", "int8")
    x, y = _data()
    names = sorted(comp.extra["resid"])
    assert names and all(n.startswith("bucket") for n in names)
    before = [onp.asarray(comp.extra["resid"][n]) for n in names]
    assert all((b == 0).all() for b in before)
    comp(x, y)
    after = [onp.asarray(comp.extra["resid"][n]) for n in names]
    assert any(onp.abs(a).max() > 0 for a in after), \
        "int8 rounding error must land in the EF residual"


def test_fp8_plus_int8_compression_converges():
    """The headline config: e4m3/e5m2 matmuls + int8 EF dp reduction,
    loss strictly decreasing over a short run."""
    s = _step("fp8", "int8")
    x, y = _data()
    losses = [float(s(x, y).asnumpy()) for _ in range(6)]
    assert losses[-1] < losses[0], losses


def test_compressed_allreduce_free_function():
    mesh = make_mesh({"dp": 4})
    rs = onp.random.RandomState(2)
    x = jnp.asarray(rs.randn(4, 64).astype("float32"))
    exact = onp.asarray(x).mean(0)
    mean, res = compressed_allreduce(x, mesh, mode="int8")
    s = onp.abs(onp.asarray(x)).max() / 127.0
    onp.testing.assert_allclose(onp.asarray(mean), exact, atol=4 * s)
    assert res.shape == x.shape
    # EF telescopes: two steps' means with the residual carried recover
    # the exact two-step sum to within ONE step's quantization error
    mean2, _ = compressed_allreduce(x, mesh, residual=res)
    tot = onp.asarray(mean) + onp.asarray(mean2)
    onp.testing.assert_allclose(tot, 2 * exact, atol=4 * s)
    # bf16 carries ~8 mantissa bits: much tighter than int8
    mbf, _ = compressed_allreduce(x, mesh, mode="bf16")
    onp.testing.assert_allclose(onp.asarray(mbf), exact, atol=2e-2)
    with pytest.raises(ValueError, match="int8"):
        compressed_allreduce(x, mesh, mode="fp4")


def test_compress_validation_errors():
    with pytest.raises(MXNetError, match="pure-dp"):
        mesh = make_mesh({"dp": 2, "tp": 2})
        ShardedTrainStep(_make_net(), _loss_fn, "adam", mesh,
                         batch_specs=(P("dp"), P("dp")), n_labels=1,
                         grad_compress="int8")
    with pytest.raises(MXNetError, match="sharded over 'dp'"):
        ShardedTrainStep(_make_net(), _loss_fn, "adam", make_mesh({"dp": 4}),
                         batch_specs=(P("dp"), P()), n_labels=1,
                         grad_compress="int8")
    with pytest.raises(MXNetError, match="grad_compress"):
        _step("fp32", "int3")
    with pytest.raises(MXNetError, match="precision"):
        _step("fp16")


def test_zero_post_warmup_recompiles():
    s = _step("fp8", "int8")
    x, y = _data()
    s(x, y)  # trace + compile
    telemetry.enable()
    try:
        telemetry.reset()
        before = sum(telemetry.counters(prefix="compile.",
                                        aggregate=True).values())
        for _ in range(3):
            s(x, y)
        after = sum(telemetry.counters(prefix="compile.",
                                       aggregate=True).values())
        assert after - before == 0
    finally:
        telemetry.disable()


# ---------------------------------------------------------------------------
# checkpoints: amax histories + EF residuals through an elastic resize
# ---------------------------------------------------------------------------

def test_fp8_checkpoint_elastic_dp4_to_dp2_bitwise(tmp_path):
    """fp8 amax histories and EF residuals ride save_states/load_states
    and restore BITWISE at a different dp size (residuals re-enter in
    the canonical summed layout — the telescoped error is the sum)."""
    x, y = _data()
    mx.random.seed(21)
    src = _step("fp8", "int8")
    for _ in range(3):
        src(x, y)
    fname = str(tmp_path / "fp8.ckpt")
    src.save_states(fname)
    canon = src.state_dict()["arrays"]
    assert any(k.startswith("fp8/") for k in canon)
    assert any(k.startswith("efresid/") for k in canon)

    mx.random.seed(99)  # different init; load must overwrite everything
    dst = _step("fp8", "int8", mesh=make_mesh({"dp": 2}), seed=99)
    dst.load_states(fname)
    assert dst._n_step == 3
    got = dst.state_dict()["arrays"]
    assert set(got) == set(canon)
    for k in canon:
        onp.testing.assert_array_equal(got[k], canon[k], err_msg=k)
    assert getattr(dst.block, "_fp8_trained", False), \
        "load_states must re-tag the block from checkpoint metadata"
    # the restored step trains on the new topology
    l = float(dst(x, y).asnumpy())
    assert onp.isfinite(l)


def test_fp8_state_survives_plain_roundtrip_missing_keys_ok(tmp_path):
    """A pre-fp8 (fp32) checkpoint loads into an fp32 step unchanged,
    and an fp8 checkpoint refuses nothing when the dest has no fp8
    state to fill — forward/backward compatible key handling."""
    x, y = _data()
    src = _step("fp32", "none")
    src(x, y)
    fname = str(tmp_path / "fp32.ckpt")
    src.save_states(fname)
    dst = _step("fp32", "none", mesh=make_mesh({"dp": 2}))
    dst.load_states(fname)
    for n in src.trainable:
        onp.testing.assert_array_equal(onp.asarray(dst.trainable[n]),
                                       onp.asarray(src.trainable[n]))


# ---------------------------------------------------------------------------
# serve guard: low-bit serving on fp8-trained checkpoints
# ---------------------------------------------------------------------------

def _tiny_gpt():
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
    mx.random.seed(0)
    net = GPTForCausalLM(vocab_size=97, units=32, hidden_size=64,
                         num_layers=1, num_heads=2, max_length=16,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    return net


def test_serve_int4_refuses_fp8_trained_checkpoint():
    net = _tiny_gpt()
    net._fp8_trained = True   # what ShardedTrainStep(precision="fp8") tags
    with pytest.raises(MXNetError, match="fp8-trained"):
        mx.serve.load(net, max_slots=2, buckets="4,8",
                      quantize="int4_weights")


def test_serve_int8_composes_with_fp8_trained():
    net = _tiny_gpt()
    net._fp8_trained = True
    for q in ("int8_weights", "int8_kv"):
        eng = mx.serve.load(net, max_slots=2, buckets="4,8", quantize=q)
        eng.stop()


def test_serve_int4_override_knob():
    net = _tiny_gpt()
    net._fp8_trained = True
    prev = mxconfig.set("serve.allow_fp8_requant", True)
    try:
        eng = mx.serve.load(net, max_slots=2, buckets="4,8",
                            quantize="int4_weights")
        eng.stop()
    finally:
        mxconfig.set("serve.allow_fp8_requant", prev)


# ---------------------------------------------------------------------------
# autotune: fp8 ships only where the parity probe passes
# ---------------------------------------------------------------------------

def test_autotune_parity_gate_rejects_and_admits_fp8():
    from mxnet_tpu.autotune import SearchSpace, search
    net = _make_net()
    mesh = make_mesh({"dp": 4})
    x, y = _data()
    space = SearchSpace(batch_size=16, steps_per_call=1, grad_accum=1,
                        zero=0, remat=False, precision=("fp32", "fp8"))

    # impossible tolerance: the fp8 trial must die with status "parity"
    # and the fp32 candidate wins
    prev = mxconfig.set("autotune.fp8_parity_tol", 1e-12)
    try:
        res = search(net, _loss_fn, "adam", mesh, (P("dp"), P("dp")),
                     (x, y), n_labels=1, space=space, persist=False,
                     force=True, trial_seconds=0.05, warmup=1)
        by_prec = {t.candidate.precision: t for t in res.trials}
        assert by_prec["fp8"].status == "parity"
        assert "parity probe failed" in by_prec["fp8"].error
        assert res.best.candidate.precision == "fp32"
    finally:
        mxconfig.set("autotune.fp8_parity_tol", prev)

    # generous tolerance: the same fp8 candidate measures cleanly
    prev = mxconfig.set("autotune.fp8_parity_tol", 0.5)
    try:
        res = search(net, _loss_fn, "adam", mesh, (P("dp"), P("dp")),
                     (x, y), n_labels=1, space=space, persist=False,
                     force=True, trial_seconds=0.05, warmup=1)
        by_prec = {t.candidate.precision: t for t in res.trials}
        assert by_prec["fp8"].status == "ok"
        assert by_prec["fp8"].items_per_s > 0
    finally:
        mxconfig.set("autotune.fp8_parity_tol", prev)
