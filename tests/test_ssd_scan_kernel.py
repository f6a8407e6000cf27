"""The state-space scan's Pallas kernels (ops/pallas/ssd_scan.py) through
the interpreter on the CPU, at small shapes: the kernel pass against the
XLA composition ``_ssd_chunked`` and against the benchmark's reference
recurrence, token by token (chipbench/reference/nemotron_h.py) — values
and every gradient —, and ``ssd_scan``'s dispatch between the two.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import family_harness as H
import mxnet_tpu as mx
from family_harness import pallas_scopes as _pallas_names
from mxnet_tpu import runtime, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.pallas import ssd_scan
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

NAMES = ("x", "dt", "A", "B", "C", "D")
REF = H.load("nemotron_h")[0]


def _operands(batch, seq, heads, dim, groups, state, dtype="float32", seed=0,
              steps=(0.01, 0.5), rates=(1, 4), scale=1.0):
    rs = onp.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    return (f(batch, seq, heads, dim).astype(dtype),
            jnp.asarray(rs.uniform(*steps, (batch, seq, heads)), jnp.float32),
            -jnp.asarray(rs.uniform(*rates, (heads,)), jnp.float32),
            (scale * f(batch, seq, groups, state)).astype(dtype),
            (scale * f(batch, seq, groups, state)).astype(dtype), f(heads))


def _recurrence(x, dt, a, b_mat, c_mat, d_skip):
    """Token by token, float32, on the operands as given."""
    x, b_mat, c_mat = (t.astype(jnp.float32) for t in (x, b_mat, c_mat))
    per = x.shape[2] // b_mat.shape[2]
    one = lambda x_, dt_, b_, c_: REF.recurrence(  # noqa: E731
        x_, dt_, a, jnp.repeat(b_, per, axis=1), jnp.repeat(c_, per, axis=1))
    return jax.vmap(one)(x, dt, b_mat, c_mat) + d_skip[:, None] * x


def _value_and_grads(f, args, ct):
    def loss(*a):
        y = f(*a)
        return jnp.sum(y.astype(jnp.float32) * ct), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(6), has_aux=True))(*args)
    return y, grads


# batch, seq, chunk, heads, dim, groups, state
SHAPES = {
    # two groups of two heads; whole chunks
    "groups": (1, 32, 8, 4, 4, 2, 8),
    # one group of four heads; the last chunk padded
    "ragged": (2, 29, 8, 4, 8, 1, 16),
    # heads wider than the state; one chunk: no state is carried
    "wide-heads": (2, 16, 16, 2, 128, 1, 8),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_pass_values_and_every_gradient(shape, dtype):
    """Against the composition and against the recurrence; bfloat16
    operands at the tolerance the mixer's AMP test uses."""
    batch, seq, chunk, heads, dim, groups, state = SHAPES[shape]
    args = _operands(batch, seq, heads, dim, groups, state, dtype)
    ct = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, g_got = _value_and_grads(
            lambda *a: ssm._ssd_kernels(*a, chunk), args, ct)
        composed, g_composed = _value_and_grads(
            lambda *a: ssm._ssd_chunked(*a, chunk), args, ct)
        plain, g_plain = _value_and_grads(_recurrence, args, ct)
    assert got.dtype == args[0].dtype and got.shape == args[0].shape
    tol = 2e-5 if dtype == "float32" else 0.05
    for want, g_want in ((composed, g_composed), (plain, g_plain)):
        want = onp.asarray(want, onp.float32)
        onp.testing.assert_allclose(onp.asarray(got, onp.float32), want,
                                    atol=tol * max(1, onp.abs(want).max()),
                                    rtol=tol)
        for name, a, r in zip(NAMES, g_got, g_want):
            assert a.dtype == r.dtype or want is plain, name
            assert a.shape == r.shape, name
            r = onp.asarray(r, onp.float32)
            onp.testing.assert_allclose(
                onp.asarray(a, onp.float32), r, err_msg=name,
                atol=tol * max(1, onp.abs(r).max()), rtol=10 * tol)


def test_bf16_products_leave_the_decay_rates_gradient_whole():
    """Long chunks, fast decays, bfloat16 operands: ``d A`` sums ``d (dt
    A)`` over the sequence, so a token's must hold no rounding left over
    from a query's sums cancelling a key's (a form that did read 40 %
    off here, at 0.4 % a token of ``d dt``)."""
    args = _operands(1, 512, 2, 16, 1, 16, "bfloat16", seed=6,
                     steps=(0.001, 0.1), rates=(1, 16), scale=0.3)
    ct = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                     jnp.float32)
    _, g_got = _value_and_grads(
        lambda *a: ssm._ssd_kernels(*a, 128), args, ct)
    with jax.default_matmul_precision("highest"):
        _, g_want = _value_and_grads(
            lambda *a: ssm._ssd_chunked(*a, 128), args, ct)
    for name, a, r in zip(NAMES, g_got, g_want):
        a, r = onp.asarray(a, onp.float32), onp.asarray(r, onp.float32)
        assert onp.abs(a - r).max() < 0.02 * onp.abs(r).max(), name


def test_chunk_changes_no_value_through_the_kernels():
    """29 tokens are the first 29 of 32 whatever follows them, in chunks
    of 8 or of 16; without a gradient no entering state is kept."""
    long = _operands(2, 32, 4, 4, 2, 8, seed=3)
    short = tuple(t[:, :29] if t.ndim > 1 else t for t in long)
    want = H.traced(lambda *a: ssm._ssd_chunked(*a, 8), *long)[:, :29]
    for chunk in (8, 16):
        onp.testing.assert_allclose(
            H.traced(lambda *a: ssm._ssd_kernels(*a, chunk), *short),
            want, atol=2e-5, rtol=2e-5)
    calls = [e for e in jax.make_jaxpr(
        lambda *a: ssm._ssd_kernels(*a, 8))(*short).jaxpr.eqns
        if e.primitive.name == "custom_vjp_call"]
    assert len(calls) == 1 and len(calls[0].outvars) == 1


@pytest.mark.parametrize("what,shape,fits", [
    ("the cell", (8192, 64, 64, 8, 128, 128, 2), True),
    ("float32 operands", (8192, 64, 64, 8, 128, 128, 4), True),
    ("four heads of 128 channels, one group",
     (256, 4, 128, 1, 128, 128, 2), True),
    ("shorter than a chunk", (100, 64, 64, 8, 128, 128, 2), False),
    ("a chunk of 64 lanes", (8192, 64, 64, 8, 128, 64, 2), False),
    ("a state of 16 lanes", (8192, 64, 64, 8, 16, 128, 2), False),
    ("a head of 8 channels", (8192, 8, 8, 1, 128, 128, 2), False),
    ("groups of four heads", (8192, 32, 64, 8, 128, 128, 2), False),
    ("the tiny configuration", (64, 8, 8, 2, 16, 16, 4), False),
    ("a step VMEM cannot hold", (8192, 64, 1024, 1, 128, 128, 4), False),
])
def test_fits_takes_whole_registers_only(what, shape, fits):
    assert ssd_scan.fits(*shape) is fits, what


_counted = functools.partial(H.counters, "ssm.")


def _aligned(seed=0):
    """The smallest shapes the kernels take: 256 tokens in two chunks of
    128, one group of two heads of 64 lanes, a state of 128."""
    return _operands(1, 256, 2, 64, 1, 128, seed=seed)


def test_off_the_tpu_ssd_scan_is_the_composition():
    """On a CPU no kernel is traced, forward or backward, whatever the
    shapes, and the counter of kernel calls stays where it was."""
    args = _aligned()
    assert ssd_scan.fits(256, 2, 64, 1, 128, 128, 4)
    _, counts = _counted(jax.jit(jax.grad(
        lambda *a: jnp.sum(ssm.ssd_scan(*a, chunk=128)))), *args)
    assert counts == {"ssm.scan_tokens_total": 256,
                      "ssm.scan_chunks_total": 4}
    assert _pallas_names(jax.grad(
        lambda *a: jnp.sum(ssm.ssd_scan(*a, chunk=128))), *args) == []
    assert telemetry.CATALOG["ssm.scan_kernel_calls_total"][0] == "counter"


def test_on_the_tpus_route_ssd_scan_takes_the_kernels(monkeypatch):
    """A CPU that takes the TPU's route, its kernels interpreted: one
    kernel call counted a traced call, the composition's values and
    gradients, both kernels under the caller's scope; shapes the tiles do
    not fill still take the composition."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    args = _aligned()
    ct = jnp.asarray(onp.random.RandomState(1).randn(*args[0].shape),
                     jnp.float32)

    def scan(*a):
        return ssm.ssd_scan(*a, chunk=128)

    with jax.default_matmul_precision("highest"):
        (got, g_got), counts = _counted(_value_and_grads, scan, args, ct)
        want, g_want = _value_and_grads(
            lambda *a: ssm._ssd_chunked(*a, 128), args, ct)
    assert counts == {"ssm.scan_tokens_total": 256,
                      "ssm.scan_chunks_total": 4,
                      "ssm.scan_kernel_calls_total": 1}
    onp.testing.assert_allclose(got, want, atol=2e-5 * onp.abs(want).max(),
                                rtol=2e-5)
    for name, a, r in zip(NAMES, g_got, g_want):
        onp.testing.assert_allclose(a, r, atol=2e-5 * onp.abs(r).max(),
                                    rtol=2e-4, err_msg=name)

    def loss(*a):
        with jax.named_scope("mx.ssm"):
            return jnp.sum(scan(*a) * ct)

    calls = dict(_pallas_names(jax.grad(loss, range(6)), *args))
    assert sorted(calls) == ["mx_ssd_bwd", "mx_ssd_fwd"]
    assert "jvp(mx.ssm)/mx.ssm.scan" in calls["mx_ssd_fwd"]
    assert "transpose(jvp(mx.ssm))/mx.ssm.scan" in calls["mx_ssd_bwd"]
    # shapes the tiles do not fill: the composition, nothing counted
    small = _operands(2, 29, 4, 3, 2, 5)
    _, counts = _counted(jax.jit(lambda *a: ssm.ssd_scan(*a, chunk=8)),
                         *small)
    assert "ssm.scan_kernel_calls_total" not in counts
    assert _pallas_names(lambda *a: ssm.ssd_scan(*a, chunk=8), *small) == []


def test_a_steps_kernels_carry_the_scans_scope_both_ways(monkeypatch):
    """A mixer that takes the kernels: lowered for the TPU, the scan's two
    Mosaic calls (beside the convolution's two), ``mx.ssm.scan`` on the
    forward one under ``jvp(mx.fwd)`` and on the backward one under
    ``transpose(jvp(mx.fwd))`` — what
    ``ssm_scan_ms.train`` and ``bwd_ms.train`` read; and in a train step
    (its kernels interpreted) the scan is no loop, counted once."""
    from mxnet_tpu import functional
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    mixer = nn.Mamba2Mixer(32, 2, 64, 1, 128, chunk_size=128)
    mixer.initialize()
    params, _ = functional.split_params(mixer)

    def loss(p, x):
        with jax.named_scope("mx.fwd"):
            return jnp.sum(functional.functional_call(
                mixer, p, x, train=True)[0])

    text = jax.jit(jax.grad(loss)).trace(
        params, jnp.zeros((1, 256, 32), jnp.float32)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") == 4
    assert '/jvp(mx.fwd)/mx.ssm/mx.ssm.scan/mx_ssd_fwd/' in text
    assert '/transpose(jvp(mx.fwd))/mx.ssm/mx.ssm.scan/mx_ssd_bwd/' in text

    monkeypatch.setattr(runtime, "pallas_interpret", lambda: True)
    mesh = MeshConfig(dp=1)
    step = ShardedTrainStep(
        mixer, lambda out, y: ((out - y) ** 2).mean(),
        mx.optimizer.create("adam", learning_rate=1e-3), mesh,
        batch_specs=mesh.batch_specs(3, 3), n_labels=1)
    x = onp.zeros((1, 256, 32), onp.float32)
    (text, counts) = _counted(
        lambda: step.lower(x, x).as_text(debug_info=True))
    assert counts["ssm.scan_kernel_calls_total"] == 1
    for under in (r"jvp\(mx\.fwd\)", r"transpose\(jvp\(mx\.fwd\)\)"):
        assert re.search(under + r'/mx\.ssm/mx\.ssm\.scan/[^"]*dot_general',
                         text), under
