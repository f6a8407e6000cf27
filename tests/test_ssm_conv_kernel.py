"""The mixers' causal convolution's Pallas kernels
(ops/pallas/ssm_conv.py) through the interpreter on the CPU, at small
shapes: the kernel pass against the XLA composition ``_conv`` — values
and the gradients of ``x``, ``weight`` and ``bias`` — and against zeros
before the sequence by hand, ``causal_conv1d``'s dispatch between the two
with its counters, and a mixer's kernels in a lowered step.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
from family_harness import pallas_scopes as _pallas_names
from mxnet_tpu import functional, runtime, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.pallas import ssm_conv

NAMES = ("x", "weight", "bias")


def _operands(batch, seq, channels, taps, dtype="float32", seed=0):
    rs = onp.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    return (f(batch, seq, channels).astype(dtype),
            f(channels, taps) * taps ** -0.5, f(channels))


def _value_and_grads(f, args, ct):
    def loss(*a):
        y = f(*a)
        return jnp.sum(y.astype(jnp.float32) * ct), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(3), has_aux=True))(*args)
    return y, grads


_counted = functools.partial(H.counters, "ssm.conv")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("silu", [True, False], ids=["silu", "plain"])
@pytest.mark.parametrize("taps", [2, 4])
def test_the_kernel_pass_values_and_every_gradient(taps, silu, dtype):
    """Batch 2, three chunks of 128 tokens — the tokens before a chunk
    cross its edge forward, ``g``'s first tokens backward — and three
    blocks of 32 channels, two (bfloat16) or four (float32) sublane tiles
    each.  float32 to rounding; bfloat16 operands to half a unit of
    ``y``'s last place (both sides round one float32 sum)."""
    args = _operands(2, 384, 96, taps, dtype)
    assert ssm_conv._chunk(384) == 128
    assert ssm_conv._block(96, 384, args[0].dtype.itemsize) == 32
    ct = jnp.asarray(onp.random.RandomState(1).randn(2, 384, 96),
                     jnp.float32)
    got, g_got = _value_and_grads(
        lambda *a: ssm._conv_by_kernels(*a, silu), args, ct)
    want, g_want = _value_and_grads(lambda *a: ssm._conv(*a, silu), args, ct)
    assert got.dtype == args[0].dtype and got.shape == args[0].shape
    tol = 1e-5 if dtype == "float32" else 8e-3
    want = onp.asarray(want, onp.float32)
    onp.testing.assert_allclose(onp.asarray(got, onp.float32), want,
                                atol=tol * onp.abs(want).max(), rtol=tol)
    for name, a, r in zip(NAMES, g_got, g_want):
        assert a.dtype == r.dtype and a.shape == r.shape, name
        r = onp.asarray(r, onp.float32)
        onp.testing.assert_allclose(onp.asarray(a, onp.float32), r,
                                    atol=tol * onp.abs(r).max(), rtol=tol,
                                    err_msg=name)


def test_the_first_tokens_read_zeros_and_a_block_its_neighbours():
    """By hand: ``y_t = bias + sum_k w_k x_{t-3+k}`` with zeros before
    the sequence, at the sequence's first tokens and either side of a
    chunk's edge; and a token's gradient reaches the three tokens before
    it across that edge."""
    x, w, bias = _operands(1, 256, 8, 4, seed=2)
    y = jax.jit(lambda *a: ssm._conv_by_kernels(*a, False))(x, w, bias)
    xs, ws, bs = (onp.asarray(t, onp.float64) for t in (x, w, bias))
    for t in (0, 1, 2, 3, 126, 127, 128, 129, 130, 131, 255):
        want = bs.copy()
        for k in range(4):
            if t - 3 + k >= 0:
                want += ws[:, k] * xs[0, t - 3 + k]
        onp.testing.assert_allclose(y[0, t], want, atol=1e-5, err_msg=str(t))
    dx = jax.jit(jax.grad(lambda x_: ssm._conv_by_kernels(
        x_, w, bias, False)[0, 129, 0]))(x)
    want = onp.zeros((256, 8))
    want[126:130, 0] = ws[0]
    onp.testing.assert_allclose(dx[0], want, atol=1e-6)


@pytest.mark.parametrize("what,shape,fits", [
    ("the cell", (8192, 6144, 4, 2), True),
    ("float32 operands", (8192, 6144, 4, 4), True),
    ("one register of tokens, one tile of channels", (128, 16, 2, 2), True),
    ("a sequence of 100 tokens", (100, 6144, 4, 2), False),
    ("a ragged sequence", (8192 + 64, 6144, 4, 2), False),
    ("8 bfloat16 channels", (8192, 8, 4, 2), False),
    ("8 float32 channels", (8192, 8, 4, 4), True),
    ("the tiny configuration", (64, 80, 4, 4), False),
    ("taps further back than a register", (8192, 6144, 130, 2), False),
    ("a sequence VMEM cannot hold a tile of", (1 << 20, 6144, 4, 2), False),
])
def test_fits_takes_whole_registers_only(what, shape, fits):
    assert ssm_conv.fits(*shape) is fits, what


def test_off_the_tpu_causal_conv1d_is_the_composition():
    """On a CPU no kernel is traced, forward or backward, whatever the
    shapes: the tokens are counted, no kernel call is."""
    args = _operands(2, 256, 16, 4)
    assert ssm_conv.fits(256, 16, 4, 4)
    grad = jax.grad(lambda *a: jnp.sum(ssm.causal_conv1d(*a, "silu")))
    _, counts = _counted(jax.jit(grad), *args)
    assert counts == {"ssm.conv_tokens_total": 512}
    assert _pallas_names(grad, *args) == []
    for name in ("ssm.conv_tokens_total", "ssm.conv_kernel_calls_total"):
        assert telemetry.CATALOG[name][0] == "counter"


def test_on_the_tpus_route_causal_conv1d_takes_the_kernels(monkeypatch):
    """A CPU that takes the TPU's route, its kernels interpreted: one
    kernel call counted a traced call, the composition's values and
    gradients, both kernels under the caller's scope; shapes the tiles do
    not fill still take the composition."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    args = _operands(2, 256, 16, 4)
    ct = jnp.asarray(onp.random.RandomState(1).randn(2, 256, 16),
                     jnp.float32)

    def conv(*a):
        return ssm.causal_conv1d(*a, "silu")

    (got, g_got), counts = _counted(_value_and_grads, conv, args, ct)
    want, g_want = _value_and_grads(lambda *a: ssm._conv(*a, True), args, ct)
    assert counts == {"ssm.conv_tokens_total": 512,
                      "ssm.conv_kernel_calls_total": 1}
    onp.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for name, a, r in zip(NAMES, g_got, g_want):
        onp.testing.assert_allclose(a, r, atol=1e-5 * onp.abs(r).max(),
                                    rtol=1e-5, err_msg=name)

    def loss(*a):
        with jax.named_scope("mx.ssm"):
            return jnp.sum(conv(*a) * ct)

    calls = dict(_pallas_names(jax.grad(loss, range(3)), *args))
    assert sorted(calls) == ["mx_ssm_conv_bwd", "mx_ssm_conv_fwd"]
    assert "jvp(mx.ssm)/mx.ssm.conv" in calls["mx_ssm_conv_fwd"]
    assert "transpose(jvp(mx.ssm))/mx.ssm.conv" in calls["mx_ssm_conv_bwd"]
    # shapes the tiles do not fill: the composition, no kernel call counted
    small = _operands(2, 29, 6, 4)
    _, counts = _counted(jax.jit(conv), *small)
    assert counts == {"ssm.conv_tokens_total": 58}
    assert _pallas_names(conv, *small) == []


def test_a_mixer_by_the_kernels_is_the_mixer_by_the_composition(monkeypatch):
    """``Mamba2Mixer`` forward + backward with the convolution (and the
    scan) by the kernels, interpreted, against the same mixer on the
    CPU's own route."""
    mixer = nn.Mamba2Mixer(32, 2, 64, 1, 128, chunk_size=128)
    mixer.initialize()
    params, _ = functional.split_params(mixer)
    rs = onp.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 256, 32), jnp.float32)
    ct = jnp.asarray(rs.randn(2, 256, 32), jnp.float32)

    def loss(p, x_):
        out = functional.functional_call(mixer, p, x_, train=True)[0]
        return jnp.sum(out * ct), out

    def run():
        return _counted(H.traced, jax.value_and_grad(
            loss, (0, 1), has_aux=True), params, x)

    ((_, want), (gp_want, gx_want)), counts = run()
    assert counts == {"ssm.conv_tokens_total": 512}
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    ((_, got), (gp_got, gx_got)), counts = run()
    assert counts == {"ssm.conv_tokens_total": 512,
                      "ssm.conv_kernel_calls_total": 1}
    onp.testing.assert_allclose(got, want, atol=2e-5 * onp.abs(want).max(),
                                rtol=2e-4)
    onp.testing.assert_allclose(gx_got, gx_want, rtol=2e-4,
                                atol=2e-5 * onp.abs(gx_want).max())
    for name, r in gp_want.items():
        onp.testing.assert_allclose(gp_got[name], r, rtol=2e-4, err_msg=name,
                                    atol=2e-5 * onp.abs(r).max())


def test_a_steps_kernels_carry_the_convolutions_scope_both_ways(monkeypatch):
    """A mixer lowered for the TPU: the convolution's two Mosaic calls
    beside the scan's, ``mx.ssm.conv`` on the forward one under
    ``jvp(mx.fwd)`` and on the backward one under
    ``transpose(jvp(mx.fwd))`` — what ``ssm_conv_ms.train`` and
    ``bwd_ms.train`` read; nothing of the composition's is left under
    that scope (no ``jax.checkpoint`` on this route)."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    mixer = nn.Mamba2Mixer(32, 2, 64, 1, 128, chunk_size=128)
    mixer.initialize()
    params, _ = functional.split_params(mixer)

    def loss(p, x):
        with jax.named_scope("mx.fwd"):
            return jnp.sum(functional.functional_call(
                mixer, p, x, train=True)[0])

    text, counts = _counted(lambda: jax.jit(jax.grad(loss)).trace(
        params, jnp.zeros((1, 256, 32), jnp.float32)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True))
    assert counts == {"ssm.conv_tokens_total": 256,
                      "ssm.conv_kernel_calls_total": 1}
    assert text.count("tpu_custom_call") == 4
    assert '/jvp(mx.fwd)/mx.ssm/mx.ssm.conv/mx_ssm_conv_fwd/' in text
    assert ('/transpose(jvp(mx.fwd))/mx.ssm/mx.ssm.conv/mx_ssm_conv_bwd/'
            in text)
    assert "mx.ssm.conv/checkpoint" not in text
    assert "mx.ssm.conv/rematted_computation" not in text
