"""One trace: the program's own names on the device timeline (kernel
``name=``s, the step's three ``jax.named_scope``s), its host spans in
any ``jax.profiler`` session, and the compile path's own record of
set-up (``_compile_cache.report()``).  docs/OBSERVABILITY.md "One trace".
"""
import collections
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM, GPTModel
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _loss(logits, labels):
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    return jnp.mean(sparse_softmax_xent(logits, labels))


def _tiny_step(layers=2, grad_accum=1):
    net = GPTForCausalLM(backbone=GPTModel(
        vocab_size=64, units=32, hidden_size=64, num_layers=layers,
        num_heads=2, max_length=16, dropout=0.0, embed_dropout=0.0))
    net.initialize()
    mesh = MeshConfig(dp=1)
    step = ShardedTrainStep(
        net, _loss, mx.optimizer.create("adam", learning_rate=1e-3), mesh,
        batch_specs=mesh.batch_specs(2, 2), n_labels=1,
        grad_accum=grad_accum)
    x = onp.zeros((4, 16), onp.int32)
    if grad_accum > 1:
        x = x.reshape(grad_accum, 4 // grad_accum, 16)
    return step, x


def _scopes(text):
    return set(re.findall(r"mx\.[a-z_]+", text))


# -- names on the device timeline -------------------------------------------

@pytest.mark.parametrize("grad_accum", [1, 2])
def test_lowered_step_names_forward_backward_and_optimizer(grad_accum):
    step, x = _tiny_step(grad_accum=grad_accum)
    text = step.lower(x, x).as_text(debug_info=True)
    assert "jvp(mx.fwd)" in text
    assert "transpose(jvp(mx.fwd))" in text
    assert "mx.optimizer" in text
    assert _scopes(text) == {"mx.fwd", "mx.optimizer", "mx.attn"}


def test_scopes_do_not_grow_with_depth(monkeypatch):
    """What cost PR 24 its set-up: scopes entered once per traced
    operation or named per Block.  The step enters ``mx.fwd`` and
    ``mx.optimizer`` once and ``mx.attn`` once a layer, whatever the
    depth, and the distinct names are the same three."""
    from jax._src import source_info_util
    entered = collections.Counter()
    real = source_info_util.ExtendNameStackContextManager.__enter__

    def counting(self):
        if self.name.startswith("mx"):
            entered[self.name] += 1
        return real(self)

    monkeypatch.setattr(source_info_util.ExtendNameStackContextManager,
                        "__enter__", counting)
    seen = {}
    for layers in (2, 4):
        step, x = _tiny_step(layers=layers)
        entered.clear()
        text = step.lower(x, x).as_text(debug_info=True)
        seen[layers] = (dict(entered), _scopes(text))
    assert seen[2][1] == seen[4][1] == {"mx.fwd", "mx.optimizer", "mx.attn"}
    assert seen[2][0] == {"mx.fwd": 1, "mx.optimizer": 1, "mx.attn": 2}
    assert seen[4][0] == {"mx.fwd": 1, "mx.optimizer": 1, "mx.attn": 4}


def _flash_grads(q):
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    return jax.grad(lambda a, b, c: flash_attention(
        a, b, c, causal=True, interpret=True).sum(), argnums=(0, 1, 2))(
            q, q, q)


def _int8_matmul(x):
    from mxnet_tpu.ops.pallas.quant_matmul import quantized_matmul
    return quantized_matmul(x, jnp.ones((128, 128), jnp.int8),
                            jnp.ones((128,)), 1.0, interpret=True)


def _fp8_matmul(x):
    from mxnet_tpu.ops.pallas.quant_matmul import fp8_matmul
    return fp8_matmul(x, jnp.ones((128, 128), jnp.float8_e4m3fn),
                      jnp.ones((128,)), 1.0, interpret=True)


def _ln_residual_grads(x):
    from mxnet_tpu.ops.pallas.ln_residual import ln_residual_dropout
    g = jnp.ones((128,))
    return jax.grad(lambda a: ln_residual_dropout(
        a, a, g, g, interpret=True).sum())(x)


def _conv3x3_grads(x):
    from mxnet_tpu.ops.pallas_conv_bwd import fused_cbr_train
    w = jnp.ones((3, 3, 8, 8), jnp.float32)
    g = jnp.ones((8,))
    return jax.grad(lambda a: fused_cbr_train(
        a, w, g, g, 1e-5, True)[0].sum())(x)


@pytest.mark.parametrize("fn,shape,name", [
    (_flash_grads, (1, 1, 128, 64), "mx_flash_fwd"),
    (_flash_grads, (1, 1, 128, 64), "mx_flash_bwd_dkv"),
    (_flash_grads, (1, 1, 128, 64), "mx_flash_bwd_dq"),
    (_int8_matmul, (32, 128), "mx_int8_matmul"),
    (_fp8_matmul, (32, 128), "mx_fp8_matmul"),
    (_ln_residual_grads, (16, 128), "mx_ln_residual_fwd"),
    (_ln_residual_grads, (16, 128), "mx_ln_residual_bwd"),
    (_conv3x3_grads, (2, 8, 8, 8), "mx_conv3x3_bwd"),
])
def test_every_pallas_call_carries_its_name(fn, shape, name):
    """``name=`` reaches the call (the jaxpr shows it) and the lowered
    module (the name stack of its operations), in interpret mode."""
    x = jnp.ones(shape, jnp.float32)
    assert f"name={name}" in str(jax.make_jaxpr(fn)(x))
    assert name in jax.jit(fn).lower(x).as_text(debug_info=True)


# -- host spans on the profiler's clock -------------------------------------

def test_any_profiler_session_holds_the_steps_host_spans(tmp_path):
    """Plain ``jax.profiler.start_trace`` — not ``mx.profiler``, and
    ``mx.trace`` disabled — holds ``mx/train.call`` round its three
    parts, and the fetch's ``mx/ndarray.asnumpy``."""
    from jax.profiler import ProfileData
    from mxnet_tpu import trace
    assert not trace.active()
    step, x = _tiny_step()
    step(x, x).asnumpy()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            loss = step(x, x)
        loss.asnumpy()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mx/"):
                    spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    calls = sorted(spans["mx/train.call"])
    assert len(calls) == 2
    for part in ("mx/train.shard_batch", "mx/train.scalars",
                 "mx/train.dispatch"):
        inner = sorted(spans[part])
        assert len(inner) == 2
        for (s, e), (cs, ce) in zip(inner, calls):
            assert cs <= s and e <= ce      # the parent contains it
    assert len(spans["mx/ndarray.asnumpy"]) == 1
    assert spans["mx/ndarray.asnumpy"][0][0] >= calls[-1][1]


def test_recorded_span_is_also_on_the_profilers_timeline():
    """With ``mx.trace`` on, a span lands in the ring as before and
    brackets itself as ``mx/<name>`` (the armed-through-mx.profiler
    special case is gone)."""
    from mxnet_tpu import trace
    trace.enable()
    try:
        with trace.span("one_trace.probe", category="test") as sp:
            assert isinstance(sp._jax, jax.profiler.TraceAnnotation)
        assert [e["name"] for e in trace.spans(category="test")] \
            == ["one_trace.probe"]
    finally:
        trace.disable()
        trace.clear()
    off = trace.span("one_trace.probe")
    assert isinstance(off, jax.profiler.TraceAnnotation)
    assert off.set(x=1) is off


# -- set-up split where it happens -------------------------------------------

_REPORT_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from mxnet_tpu import _compile_cache
_compile_cache.configure(sys.argv[1])

@jax.jit
def one_trace_probe(x):
    return jnp.tanh(x @ x).sum()

one_trace_probe(jnp.ones((32, 32))).block_until_ready()
one_trace_probe(jnp.ones((32, 32))).block_until_ready()
print(json.dumps([r for r in _compile_cache.report()
                  if r["fun_name"] == "jit(one_trace_probe)"]))
"""


def test_report_lists_a_program_once_and_a_second_process_hits(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _REPORT_PROBE, str(tmp_path / "cache")],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    for records in runs:
        assert len(records) == 1            # compiled once, called twice
        r = records[0]
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
        assert r["at"] > 0
    assert runs[0][0]["hit"] is False and runs[0][0]["cache_retrieval_s"] == 0
    assert runs[1][0]["hit"] is True
    assert 0 < runs[1][0]["cache_retrieval_s"] <= runs[1][0]["backend_s"]


# -- set-up seen from inside: the start-up record ----------------------------

_STARTUP_PROBE = """
import json, sys
sys.path.insert(0, sys.argv[1])
import numpy as onp
import test_one_trace as t
from mxnet_tpu import _compile_cache, trace
assert not trace.active()
step, x = t._tiny_step()
for _ in range(3):
    step(x, x).asnumpy()
print(json.dumps({"startup": trace.startup(),
                  "report": _compile_cache.report()}))
"""


@pytest.fixture(scope="module")
def fresh_process():
    """``mx.trace.startup()`` and ``_compile_cache.report()`` of a new
    process that built a tiny step and called it three times, recorder
    off, no profiler, no cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("MXNET_TRACE", None)
    out = subprocess.run(
        [sys.executable, "-c", _STARTUP_PROBE,
         os.path.dirname(os.path.abspath(__file__))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _named(kept, name):
    return [(i, s) for i, s in enumerate(kept) if s["name"] == name]


def test_startup_holds_the_import_first(fresh_process):
    kept = fresh_process["startup"]
    assert kept[0]["name"] == "import" and kept[0]["parent"] is None
    assert kept[0]["attrs"]["modules"] > 100
    assert all(s["start_s"] >= kept[0]["end_s"] for s in kept[1:])
    assert len({s["thread"] for s in kept}) == 1
    assert [s["name"] for s in kept].count("ndarray.asnumpy") == 3


@pytest.mark.parametrize("part,counts", [
    ("train.plan", ("param_leaves", "param_bytes")),
    ("train.place", ("leaves", "bytes")),
    ("train.states", ("leaves", "bytes")),
])
def test_train_init_contains_its_parts_with_their_counts(
        fresh_process, part, counts):
    kept = fresh_process["startup"]
    ((i_init, init),) = _named(kept, "train.init")
    ((_, s),) = _named(kept, part)
    assert s["parent"] == i_init and init["parent"] is None
    assert init["start_s"] <= s["start_s"] <= s["end_s"] <= init["end_s"]
    assert all(s["attrs"][c] > 0 for c in counts)
    if part == "train.states":      # Adam: two moments a parameter
        ((_, place),) = _named(kept, "train.place")
        assert s["attrs"]["leaves"] == 2 * place["attrs"]["leaves"]
        assert s["attrs"]["bytes"] == 2 * place["attrs"]["bytes"]


def test_the_first_dispatch_holds_the_step_program_and_the_third_none(
        fresh_process):
    kept, report = fresh_process["startup"], fresh_process["report"]
    calls, dispatches = _named(kept, "train.call"), \
        _named(kept, "train.dispatch")
    assert len(calls) == len(dispatches) == 3
    for (i_call, call), (_, d) in zip(calls, dispatches):
        assert d["parent"] == i_call and call["parent"] is None
    for part in ("train.shard_batch", "train.scalars"):
        assert [s["parent"] for _, s in _named(kept, part)] \
            == [i for i, _ in calls]
    (step,) = [r for r in report if r["fun_name"] == "jit(base_step)"]
    first, third = dispatches[0][1], dispatches[2][1]
    assert first["start_s"] < step["at"] <= first["end_s"]
    # the first dispatch is the wall time of trace + lower + compile
    assert first["end_s"] - first["start_s"] >= step["backend_s"]
    assert not [r for r in report
                if third["start_s"] <= r["at"] <= third["end_s"]]
    ((_, init),) = _named(kept, "train.init")
    assert init["end_s"] <= calls[0][1]["start_s"]
