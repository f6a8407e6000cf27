#!/bin/sh
# CI entrypoint (the role of the reference's ci/build.py + Jenkinsfile
# stage matrix, minus docker).
#
# Stages:
#   sanity     - compile-check every python file, onnx gencode drift check
#   unit       - pytest tests/ on a virtual 8-device CPU mesh
#   native     - force-rebuild every native/*.cc lib, then run the C-ABI
#                host example as a pure C process
#   contracts  - __graft_entry__.py (jit entry + multichip dryrun), bench
#                smoke on CPU
#   chaos      - fault-injection suite + a small MXNET_FAULT_SPEC matrix
#                + the fleet host-loss drill: degrade dp 2 -> 1 with
#                tp/pp preserved, bitwise bundle restore, loss parity
#                with the uninterrupted oracle, re-expand on rejoin
#                (docs/FAULT_TOLERANCE.md)
#   telemetry  - metrics/observability suite + the disabled-fast-path
#                overhead budget (docs/OBSERVABILITY.md)
#   resilience - elastic-training suite + an e2e preempt -> exit 75 ->
#                restore -> finish chaos run (docs/FAULT_TOLERANCE.md
#                "Preemption & elastic resume")
#   pipeline   - async host<->device overlap suite + the overlap
#                benchmark: prefetch-on must beat the synchronous loop
#                >=1.2x with input-stall below the serial producer wait,
#                and the disabled path must stay <2% on a tight eager
#                loop (docs/PERFORMANCE.md); plus the proc-vs-thread
#                DataLoader gate (spawn pool >= 0.8x threads on the
#                GIL-bound transform)
#   zero       - ZeRO-sharded training suite + the optimizer-state
#                memory benchmark: zero=1 on a 4-way dp mesh must cut
#                per-device state bytes >=40% while staying numerically
#                invisible (docs/PERFORMANCE.md)
#   mesh       - composed-parallelism suite (MeshConfig dp x tp x pp x
#                sp): parity oracle vs the single-device run, elastic
#                (dp,tp,pp)-portable checkpoints, ZeRO x TP state
#                sharding, pp.gpipe backward, mesh-axis autotune — on
#                the virtual 8-device CPU mesh (docs/PERFORMANCE.md
#                "Composing parallelism")
#   serve      - continuous-batching inference suite + the throughput
#                benchmark: >=2x tokens/s vs sequential decode under
#                Poisson arrivals with ZERO post-warmup recompiles
#                (docs/SERVING.md)
#   autotune   - config-search suite + an e2e CPU search: >=50% of the
#                grid pruned analytically, winner >= untuned default,
#                an injected OOM trial survives, and the second run
#                reloads the winner by fingerprint with zero trials
#                (docs/PERFORMANCE.md "Autotuning")
#   trace      - causal-tracing suite + e2e span-tree validation: the
#                acceptance tests export one traced train epoch and one
#                traced serve run (MXNET_TRACE_E2E_DIR), tools/trace.py
#                re-validates both trees from the JSON, and the
#                disabled-fast-path budget (<2%) is re-enforced with the
#                trace probe included (docs/OBSERVABILITY.md "Tracing")
#   quantize   - low-bit inference suite (default route AND the Pallas
#                path forced on via MXNET_QUANTIZE_FUSED_MATMUL=on) +
#                the quantized_inference gates: fused kernel bitwise vs
#                the XLA fallback, int4 weight bytes <=0.15x fp32, zero
#                post-warmup recompiles with quantization enabled
#                (docs/PERFORMANCE.md "Low-bit inference")
#   insight    - performance-attribution suite: XLA cost-capture
#                registry, EWMA+MAD drift-detector oracles, 2-host
#                fleet-snapshot merge, /insight endpoint + drift
#                chaos drill; the disabled-fast-path budget (<2%) is
#                re-enforced with insight compiled in
#                (docs/OBSERVABILITY.md "Performance attribution,
#                fleet view & drift")
#   blackbox   - flight-recorder suite: one drill per trigger class
#                (fault-injected worker crash, SIGTERM/exit-75 preempt,
#                loader-thread exception, fleet WorkerLost, torn
#                bundle) + the e2e fleet crash drill: an injected host
#                loss on the 8-device mesh leaves a valid checksummed
#                postmortem bundle for the dead rank, the supervisor
#                attaches it to the degrade span, and
#                tools/postmortem.py merge names that rank as the
#                first-anomaly host; the disabled-fast-path budget
#                (<2%) is re-enforced with the recorder compiled in
#                (docs/OBSERVABILITY.md "Postmortem forensics")
#   stream     - deterministic sharded streaming data plane suite:
#                exactly-once epoch oracle across host loss + elastic
#                dp resizes, bitwise cursor resume, corrupt-record
#                drills; plus the input-plane benchmark (stall below
#                the serial producer wait, zero recompiles, sync_guard
#                counts unchanged) and the 2-process kill-one-host
#                drill (STREAM_DRILL_OK) (docs/FAULT_TOLERANCE.md
#                "Streaming data plane")
#   goodput    - wall-clock goodput-ledger suite: conservation oracle
#                (sum of badput buckets == elapsed wall clock) under
#                each injected badput class, priority/no-overlap
#                property, 2-host capacity-weighted merge, /goodput
#                endpoint + burn-rate /healthz 503; the 8-device
#                host-loss drill attributes the injected downtime
#                (restart + degraded_capacity) with conservation
#                intact (GOODPUT_DRILL_OK), tools/goodput.py validate
#                re-checks it from the published snapshot, and the
#                disabled-fast-path budget (<2%) is re-enforced with
#                the ledger compiled in (docs/OBSERVABILITY.md
#                "Goodput & SLO budgets")
#   servefleet - multi-replica serving control-plane suite
#                (rendezvous session-affinity routing, crash/stall
#                failover with exactly-once re-dispatch, rolling
#                weight updates with canary auto-rollback, SLO-driven
#                scaling) + the 3-process chaos drill: SIGKILL a
#                replica mid-stream, lease-expiry detection, rolling
#                update under live traffic, bad-canary rollback —
#                gated on SERVEFLEET_DRILL_OK (docs/SERVING.md
#                "Multi-replica serving"); the disabled-fast-path
#                budget (<2%) is re-enforced with the fleet hook
#                compiled in
#   lint       - framework-aware static analysis (tools/mxlint.py):
#                trace-safety, donated-buffer, lock-order and registry
#                drift rules over the whole tree, gated on ZERO new
#                findings against ci/lint_baseline.json
#                (docs/STATIC_ANALYSIS.md)
#   nightly    - the slow bucket (MXNET_TEST_SLOW=1), reference
#                tests/nightly analog
#   tpu        - on a machine with a chip: chip_smoke.py, then the Mosaic
#                kernel checks (no chip = a red stage, never a skip)
#
# The stage x platform matrix (what the reference spreads across
# Jenkinsfiles) is ci/matrix.yaml; 'all' runs the PR-blocking set.
#
# Usage: ci/run.sh [sanity|unit|native|contracts|chaos|telemetry|resilience|pipeline|zero|mesh|serve|autotune|quantize|trace|insight|blackbox|stream|goodput|servefleet|lint|nightly|tpu|all]
set -e
cd "$(dirname "$0")/.."
stage="${1:-all}"

sanity() {
    echo "== sanity: python compile-check =="
    python -m compileall -q mxnet_tpu tools example tests bench.py chip_smoke.py __graft_entry__.py
    echo "== sanity: onnx proto gencode =="
    # byte-diff only when the local protoc matches the version that
    # produced the checked-in gencode (recorded in .protoc-version);
    # otherwise fall back to a functional round-trip so an unrelated
    # protoc bump can't block CI while proto/gencode drift still fails
    # for anyone on the pinned version.
    want=$(cat mxnet_tpu/onnx/.protoc-version)
    have=$(protoc --version | awk '{print $2}')
    if [ "$want" = "$have" ]; then
        tmp=$(mktemp -d)
        protoc --python_out="$tmp" -I mxnet_tpu/onnx mxnet_tpu/onnx/onnx_mxtpu.proto
        diff -q "$tmp/onnx_mxtpu_pb2.py" mxnet_tpu/onnx/onnx_mxtpu_pb2.py
        rm -rf "$tmp"
    else
        echo "protoc $have != pinned $want; functional check only"
    fi
    python - <<'PY'
from mxnet_tpu.onnx import serde
m = serde.make_model(serde.GraphProto(), opset=17)
m2 = serde.ModelProto(); m2.ParseFromString(m.SerializeToString())
assert m2.opset_import[0].version == 17
print("onnx gencode ok")
PY
}

unit() {
    echo "== unit: pytest (virtual 8-device CPU mesh via tests/conftest.py) =="
    python -m pytest tests/ -q
}

native() {
    echo "== native: force-rebuild every helper library =="
    rm -rf native/build
    python - <<'PY'
from mxnet_tpu import native
for name in ("mxtpu_pool", "mxtpu_io", "mxtpu_decode",
             "mxtpu_plugin_example", "mxtpu_capi"):
    lib = native.load(name)
    assert lib is not None, f"build failed: {name}"
    print(f"built lib{name}.so")
PY
    echo "== native: pure-C ABI host =="
    python -m pytest tests/test_capi.py -q
}

contracts() {
    echo "== contracts: driver entrypoints =="
    python __graft_entry__.py
    echo "== contracts: bench smoke (CPU shapes, machine-readable out) =="
    tmp=$(mktemp -d)
    JAX_PLATFORMS=cpu python bench.py --out "$tmp/bench.json"
    # the machine-readability gate: --out and the last stdout line are
    # the same single JSON document (a driver once parsed nothing from it)
    python -c "import json,sys; json.load(open(sys.argv[1]))" "$tmp/bench.json"
    rm -rf "$tmp"
}

chaos() {
    echo "== chaos: fault-injection suite (docs/FAULT_TOLERANCE.md) =="
    python -m pytest tests/test_fault_injection.py -q
    echo "== chaos: MXNET_FAULT_SPEC env matrix =="
    # each spec arms one injection point through the env alias; the
    # env_spec test runs a toy train loop under whatever is armed and
    # asserts it still completes with correct metrics
    for spec in \
        "dataloader.worker_crash:at=2" \
        "invoke.nan_output:at=25,times=1" \
        "serialization.torn_write:at=1,times=1"; do
        echo "-- MXNET_FAULT_SPEC=$spec"
        MXNET_FAULT_SPEC="$spec" python -m pytest \
            tests/test_fault_injection.py -q -k env_spec
    done
    echo "== chaos: fleet host-loss drill (degrade -> bitwise restore -> re-expand) =="
    tmp=$(mktemp -d)
    cat > "$tmp/drill.py" <<'PY'
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import warnings

import jax
import jax.numpy as jnp
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.fleet import FleetSupervisor
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

VOCAB, UNITS, LAYERS, HEADS, SEQ, BATCH = 64, 16, 2, 2, 8, 8


def batch(seed):
    rs = onp.random.RandomState(seed)
    return (rs.randint(0, VOCAB, size=(BATCH, SEQ)).astype(onp.int32),
            rs.randint(0, VOCAB, size=(BATCH, SEQ)).astype(onp.int32))


def loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def make_step(cfg):
    mx.random.seed(0)
    net = GPTForCausalLM(vocab_size=VOCAB, units=UNITS, num_layers=LAYERS,
                         num_heads=HEADS, max_length=SEQ, dropout=0.0,
                         embed_dropout=0.0)
    net.initialize()
    net(mx.np.array(batch(0)[0]))
    opt = mx.optimizer.create("sgd", learning_rate=0.01)
    return ShardedTrainStep(net, loss_fn, opt, cfg,
                            cfg.batch_specs(2, 2), n_labels=1)


telemetry.enable()
cfg = MeshConfig(dp=2, tp=2, pp=2)

oracle_step = make_step(cfg)
oracle = {s: float(oracle_step(*batch(s))) for s in range(1, 9)}

step = make_step(cfg)
bundle = os.path.join(os.environ["DRILL_DIR"], "run.bundle")
state = mx.resilience.TrainState(path=bundle, sharded_step=step)
sup = FleetSupervisor(step, state, n_hosts=2, host_index=0,
                      checkpoint_every=1)
mx.fault.configure("fleet.host_loss:at=4,times=1")
with warnings.catch_warnings():
    warnings.simplefilter("ignore")      # the 4-device mesh strands 4 of 8
    losses = sup.run(batch, 6)
    assert sup.degrades == 1, sup.degrades
    assert sup.current == MeshConfig(dp=1, tp=2, pp=2), sup.current
    sup.restore_hosts()
    losses.update(sup.run(batch, 8))
assert sup.reexpands == 1 and sup.current == cfg, (sup.reexpands, sup.current)
assert sorted(losses) == list(range(1, 9)), sorted(losses)
for s, ref in oracle.items():
    got = float(losses[s])
    assert abs(got - ref) < 1e-5, (s, got, ref)
counts = telemetry.counters(aggregate=True)
assert counts.get("fleet.degrades_total", 0) >= 1, counts
assert counts.get("fleet.reexpands_total", 0) >= 1, counts
print("FLEET_DRILL_OK degrades=%d reexpands=%d" %
      (sup.degrades, sup.reexpands))
PY
    JAX_PLATFORMS=cpu DRILL_DIR="$tmp" \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python "$tmp/drill.py" | grep "FLEET_DRILL_OK"
    rm -rf "$tmp"
}

telemetry() {
    echo "== telemetry: observability suite (docs/OBSERVABILITY.md) =="
    python -m pytest tests/test_telemetry.py -q
    echo "== telemetry: disabled fast-path overhead budget (<2%) =="
    JAX_PLATFORMS=cpu python benchmark/telemetry_overhead.py
}

resilience() {
    echo "== resilience: elastic-training suite (docs/FAULT_TOLERANCE.md) =="
    python -m pytest tests/test_resilience.py -q
    echo "== resilience: e2e preempt -> exit 75 -> restore -> finish =="
    tmp=$(mktemp -d)
    cat > "$tmp/train.py" <<'PY'
import sys
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.contrib import estimator as est
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.gluon.data.dataset import ArrayDataset
from mxnet_tpu.gluon.data.sampler import RandomSampler

bundle = sys.argv[1]
mx.random.seed(11)
rng = onp.random.RandomState(0)
x = rng.randn(32, 4).astype("f")
y = (rng.randn(32) > 0).astype("f")
loader = DataLoader(ArrayDataset(x, y), batch_size=8,
                    sampler=RandomSampler(32, seed=3), num_workers=0)
net = nn.Sequential()
net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
net.initialize()
trainer = gluon.Trainer(net.collect_params(), "adam",
                        {"learning_rate": 0.05})
e = est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                  trainer=trainer)
rh = est.ResilienceHandler(bundle, loader=loader)

def train():
    e.fit(loader, epochs=2, event_handlers=[rh])

mx.resilience.run(train, exit_on_preempt=True)
assert rh.state.step >= 8, rh.state.step
print("E2E_DONE resumed=%s step=%d" % (rh.resumed, rh.state.step))
PY
    # phase 1: injected preemption at step 3 must stop with the resume
    # sentinel (75) and leave a valid bundle behind
    if MXNET_FAULT_SPEC="resilience.preempt:at=3" JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python "$tmp/train.py" "$tmp/run.bundle"; then
        echo "expected resume-sentinel exit, got success"
        rm -rf "$tmp"; return 1
    else
        code=$?
        if [ "$code" -ne 75 ]; then
            echo "expected exit 75 (EX_TEMPFAIL), got $code"
            rm -rf "$tmp"; return 1
        fi
    fi
    test -f "$tmp/run.bundle" && test -f "$tmp/run.bundle.sha256"
    # phase 2: the restarted "job" auto-restores and finishes
    JAX_PLATFORMS=cpu PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python "$tmp/train.py" "$tmp/run.bundle" \
        | grep "E2E_DONE resumed=True"
    rm -rf "$tmp"
}

pipeline() {
    echo "== pipeline: overlap-engine suite (docs/PERFORMANCE.md) =="
    python -m pytest tests/test_pipeline.py tests/test_dataloader_mp.py -q
    echo "== pipeline: overlap benchmark (>=1.2x, stall < serial wait, off-path <2%) =="
    JAX_PLATFORMS=cpu python benchmark/pipeline_overlap.py
    echo "== pipeline: proc-vs-thread loader gate (>=0.8x) =="
    JAX_PLATFORMS=cpu python benchmark/scaling_proc.py --loader-gate
}

autotune() {
    echo "== autotune: config-search suite (docs/PERFORMANCE.md) =="
    python -m pytest tests/test_autotune.py -q
    echo "== autotune: e2e search (>=50% pruned, winner >= default, OOM survival) =="
    tmp=$(mktemp -d)
    # first run: fresh cache, one injected device-OOM mid-search; the
    # search must finish, record the OOM, prune >=50% of the grid before
    # compiling, beat the untuned default, and leak zero RecompileWarnings
    JAX_PLATFORMS=cpu python tools/autotune.py --model mlp \
        --cache-dir "$tmp" --trial-seconds 0.05 \
        --inject-oom-at 2 --assert --out "$tmp/first.json"
    # second run: the winner must come back by fingerprint with ZERO
    # trials re-executed
    JAX_PLATFORMS=cpu python tools/autotune.py --model mlp \
        --cache-dir "$tmp" --trial-seconds 0.05 --expect-reused
    rm -rf "$tmp"
    echo "== autotune: kernel block-shape suite (docs/PERFORMANCE.md) =="
    python -m pytest tests/test_kernel_autotune.py -q
    echo "== autotune: kernel search e2e (winner/bucket, cached 2nd run = 0 trials) =="
    tmp=$(mktemp -d)
    JAX_PLATFORMS=cpu python tools/autotune.py --kernels \
        --cache-dir "$tmp" --trial-seconds 0.02 --assert
    JAX_PLATFORMS=cpu python tools/autotune.py --kernels \
        --cache-dir "$tmp" --trial-seconds 0.02 --expect-reused
    rm -rf "$tmp"
}

quantize() {
    echo "== quantize: low-bit inference suite (docs/PERFORMANCE.md) =="
    python -m pytest tests/test_quantization.py -q
    echo "== quantize: Pallas fused path forced on (interpret parity) =="
    MXNET_QUANTIZE_FUSED_MATMUL=on python -m pytest \
        tests/test_quantization.py tests/test_serve.py -q
    echo "== quantize: inference gates (parity, int4 bytes, 0 recompiles) =="
    JAX_PLATFORMS=cpu python benchmark/quantized_inference.py --assert
}

trace() {
    echo "== trace: causal-tracing suite (docs/OBSERVABILITY.md) =="
    tmp=$(mktemp -d)
    MXNET_TRACE_E2E_DIR="$tmp" python -m pytest tests/test_trace.py -q
    echo "== trace: e2e span trees (tools/trace.py validate) =="
    python tools/trace.py validate "$tmp/e2e_train.json" \
        --expect train.step \
        --expect-child train.step=train.data_wait \
        --expect-child train.step=train.h2d \
        --expect-child train.step=train.dispatch \
        --expect-child train.step=train.drain
    python tools/trace.py validate "$tmp/e2e_serve.json" \
        --expect serve.request \
        --expect-child serve.request=serve.enqueue \
        --expect-child serve.request=serve.prefill \
        --expect-child serve.request=serve.decode_step \
        --expect-child serve.request=serve.drain
    rm -rf "$tmp"
    echo "== trace: disabled fast-path overhead budget (<2%) =="
    JAX_PLATFORMS=cpu python benchmark/telemetry_overhead.py
}

zero() {
    echo "== zero: ZeRO-sharded training suite (docs/PERFORMANCE.md) =="
    python -m pytest tests/test_zero.py -q
    echo "== zero: per-device optimizer-state memory (>=40% cut at dp=4) =="
    JAX_PLATFORMS=cpu python benchmark/zero_memory.py
}

fp8() {
    echo "== fp8: delayed-scaling fp8 training + compressed collectives suite (docs/PRECISION.md) =="
    python -m pytest tests/test_fp8.py -q
    echo "== fp8: parity / recompile / checkpoint gate (<=5% loss delta) =="
    JAX_PLATFORMS=cpu python benchmark/fp8_train.py
}

mesh() {
    echo "== mesh: composed-parallelism suite (docs/PERFORMANCE.md 'Composing parallelism') =="
    python -m pytest tests/test_mesh_compose.py tests/test_parallel.py -q
    echo "== mesh: ZeRO x TP optimizer-state gate (>=40% cut at dp=4, tp=2) =="
    JAX_PLATFORMS=cpu python benchmark/zero_memory.py
}

serve() {
    echo "== serve: continuous-batching inference suite (docs/SERVING.md) =="
    python -m pytest tests/test_serve.py -q
    echo "== serve: prefix-cache / speculative / SLO-class suite (docs/SERVING.md \"Prefix caching\") =="
    # MXNET_TEST_SLOW=1: the quantized/compose/foreign-draft combos are
    # nightly-bucketed out of tier-1 but stay PR-blocking here
    MXNET_TEST_SLOW=1 python -m pytest tests/test_serve_prefix.py -q
    echo "== serve: throughput benchmark (>=2x vs sequential, 0 post-warmup recompiles) =="
    JAX_PLATFORMS=cpu python benchmark/serve_throughput.py --assert
    echo "== serve: multi-tenant benchmark (>=1.5x prefix speedup, hit-rate floor, spec parity, gold<=bronze p99 TTFT) =="
    JAX_PLATFORMS=cpu python benchmark/serve_throughput.py --tenants 3 --assert
}

insight() {
    echo "== insight: performance attribution / fleet merge / drift suite (docs/OBSERVABILITY.md) =="
    python -m pytest tests/test_insight.py -q
    echo "== insight: disabled fast-path overhead budget (<2%) with insight compiled in =="
    JAX_PLATFORMS=cpu python benchmark/telemetry_overhead.py
}

blackbox() {
    echo "== blackbox: flight-recorder suite (docs/OBSERVABILITY.md \"Postmortem forensics\") =="
    python -m pytest tests/test_blackbox.py -q
    echo "== blackbox: fleet crash -> postmortem bundle -> merge drill =="
    tmp=$(mktemp -d)
    cat > "$tmp/drill.py" <<'PY'
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import warnings

import jax
import jax.numpy as jnp
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import blackbox, trace
from mxnet_tpu.fleet import FleetSupervisor
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

VOCAB, UNITS, LAYERS, HEADS, SEQ, BATCH = 64, 16, 2, 2, 8, 8


def batch(seed):
    rs = onp.random.RandomState(seed)
    return (rs.randint(0, VOCAB, size=(BATCH, SEQ)).astype(onp.int32),
            rs.randint(0, VOCAB, size=(BATCH, SEQ)).astype(onp.int32))


def loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


mx.config.set("blackbox.dir", os.environ["DRILL_DIR"])
blackbox.enable()
trace.enable(buffer=4096)

mx.random.seed(0)
cfg = MeshConfig(dp=2, tp=2, pp=2)
net = GPTForCausalLM(vocab_size=VOCAB, units=UNITS, num_layers=LAYERS,
                     num_heads=HEADS, max_length=SEQ, dropout=0.0,
                     embed_dropout=0.0)
net.initialize()
net(mx.np.array(batch(0)[0]))
opt = mx.optimizer.create("sgd", learning_rate=0.01)
step = ShardedTrainStep(net, loss_fn, opt, cfg, cfg.batch_specs(2, 2),
                        n_labels=1)
bundle = os.path.join(os.environ["DRILL_DIR"], "run.bundle")
state = mx.resilience.TrainState(path=bundle, sharded_step=step)
sup = FleetSupervisor(step, state, n_hosts=2, host_index=0,
                      checkpoint_every=1)

with warnings.catch_warnings():
    warnings.simplefilter("ignore")      # the 4-device mesh strands 4 of 8
    # healthy steps first: both hosts' recorders shadow-checkpoint, so
    # the soon-to-die host has evidence on shared storage before it dies
    losses = sup.run(batch, 3)
    for r in (0, 1):
        assert blackbox.dump(trigger="shadow", shadow=True, rank=r, step=3)
    # host 1 crashes: its excepthook leaves a terminal bundle (what the
    # real process would write on its way down) ...
    try:
        raise RuntimeError("XLA device lost (drill)")
    except RuntimeError as e:
        assert blackbox.dump(trigger="excepthook",
                             reason="uncaught RuntimeError (drill)",
                             exc=e, rank=1, step=4)
    # ... and the supervisor observes the loss at step 4
    mx.fault.configure("fleet.host_loss:at=4,times=1")
    losses.update(sup.run(batch, 6))

assert sup.degrades == 1, sup.degrades
assert sup.current == MeshConfig(dp=1, tp=2, pp=2), sup.current
dead = sup.postmortems.get(1)
assert dead and os.path.basename(dead) == "blackbox-1-00000004.json", dead
doc = blackbox.read_bundle(dead)         # checksum + schema verified
assert doc["meta"]["trigger"] == "excepthook", doc["meta"]
assert doc["exception"]["type"] == "RuntimeError", doc["exception"]
degrades = [s for s in trace.spans(category="fleet")
            if s["name"] == "fleet.degrade"]
assert degrades and degrades[-1]["args"]["postmortem"] == dead
assert degrades[-1]["args"]["postmortem_host"] == 1
print("BLACKBOX_DRILL_OK dead_bundle=%s" % os.path.basename(dead))
PY
    JAX_PLATFORMS=cpu DRILL_DIR="$tmp" \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python "$tmp/drill.py" | grep "BLACKBOX_DRILL_OK"
    echo "== blackbox: dead rank's bundle validates + merge names it first-anomaly =="
    dead=$(ls "$tmp"/blackbox-1-*.json | tail -n 1)
    JAX_PLATFORMS=cpu python tools/postmortem.py validate "$dead" \
        --expect excepthook
    JAX_PLATFORMS=cpu python tools/postmortem.py merge "$tmp" \
        | grep '"first_anomaly_host": 1'
    rm -rf "$tmp"
    echo "== blackbox: disabled fast-path overhead budget (<2%) with the recorder compiled in =="
    JAX_PLATFORMS=cpu python benchmark/telemetry_overhead.py
}

stream() {
    echo "== stream: deterministic sharded streaming suite (docs/FAULT_TOLERANCE.md \"Streaming data plane\") =="
    python -m pytest tests/test_stream.py -q
    echo "== stream: input-plane benchmark + 2-process host-loss drill =="
    JAX_PLATFORMS=cpu python benchmark/stream_input.py | tee /dev/stderr \
        | grep -q "STREAM_DRILL_OK"
}

servefleet() {
    echo "== servefleet: multi-replica serving control plane suite (docs/SERVING.md \"Multi-replica serving\") =="
    # the tier-1 sweep keeps a fast core of this file; the dedicated
    # stage runs the whole surface including the slow bucket
    MXNET_TEST_SLOW=1 python -m pytest tests/test_servefleet.py -q
    echo "== servefleet: 3-process chaos drill — SIGKILL failover, rolling update, bad-canary rollback =="
    tmp=$(mktemp -d)
    JAX_PLATFORMS=cpu python tests/servefleet_worker.py drive "$tmp" \
        | tee /dev/stderr | grep -q "SERVEFLEET_DRILL_OK"
    rm -rf "$tmp"
    echo "== servefleet: disabled fast-path overhead budget (<2%) with the fleet hook compiled in =="
    JAX_PLATFORMS=cpu python benchmark/telemetry_overhead.py
}

goodput() {
    echo "== goodput: wall-clock ledger / badput attribution / SLO burn suite (docs/OBSERVABILITY.md \"Goodput & SLO budgets\") =="
    python -m pytest tests/test_goodput.py -q
    echo "== goodput: 8-device host-loss drill — conservation + attribution oracle =="
    tmp=$(mktemp -d)
    cat > "$tmp/drill.py" <<'PY'
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import goodput, telemetry
from mxnet_tpu.fleet import FleetSupervisor
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

VOCAB, UNITS, LAYERS, HEADS, SEQ, BATCH = 64, 16, 2, 2, 8, 8


def batch(seed):
    rs = onp.random.RandomState(seed)
    return (rs.randint(0, VOCAB, size=(BATCH, SEQ)).astype(onp.int32),
            rs.randint(0, VOCAB, size=(BATCH, SEQ)).astype(onp.int32))


def loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


telemetry.enable()
goodput.enable()

mx.random.seed(0)
cfg = MeshConfig(dp=2, tp=2, pp=2)
net = GPTForCausalLM(vocab_size=VOCAB, units=UNITS, num_layers=LAYERS,
                     num_heads=HEADS, max_length=SEQ, dropout=0.0,
                     embed_dropout=0.0)
net.initialize()
net(mx.np.array(batch(0)[0]))
opt = mx.optimizer.create("sgd", learning_rate=0.01)
step = ShardedTrainStep(net, loss_fn, opt, cfg, cfg.batch_specs(2, 2),
                        n_labels=1)
bundle = os.path.join(os.environ["DRILL_DIR"], "run.bundle")
state = mx.resilience.TrainState(path=bundle, sharded_step=step)
sup = FleetSupervisor(step, state, n_hosts=2, host_index=0,
                      checkpoint_every=1)

mx.fault.configure("fleet.host_loss:at=2,times=1")
t0 = time.time()
with warnings.catch_warnings():
    warnings.simplefilter("ignore")      # the 4-device mesh strands 4 of 8
    # the run window is claimed as compute; the supervisor's restart
    # bracket (higher priority) carves the degrade transition out of it
    losses = sup.run(batch, 4)
    sup.restore_hosts()
    losses.update(sup.run(batch, 6))
goodput.note("compute", time.time() - t0)

assert sup.degrades == 1 and sup.reexpands == 1, (sup.degrades,
                                                  sup.reexpands)
s = goodput.summary()
slack = 0.05 + s["late_dropped_s"]
assert s["conservation_error_s"] <= slack, s
assert abs(sum(s["buckets"].values()) - s["elapsed_s"]) <= slack, s
assert s["buckets"]["restart"] > 0, s["buckets"]
assert s["buckets"]["degraded_capacity"] > 0, s["buckets"]
assert s["buckets"]["checkpoint_save"] > 0, s["buckets"]
assert s["capacity_ratio"] == 1.0, s
top = s["badput_top"][0][0]
assert top in ("restart", "degraded_capacity"), s["badput_top"]
goodput.write_snapshot(os.environ["DRILL_DIR"], 0)
print("GOODPUT_DRILL_OK top=%s goodput=%.3f" % (top,
                                                s["goodput_fraction"]))
PY
    out=$(JAX_PLATFORMS=cpu DRILL_DIR="$tmp" \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python "$tmp/drill.py")
    echo "$out" | grep "GOODPUT_DRILL_OK"
    echo "== goodput: tools/goodput.py re-validates conservation + attribution from the snapshot =="
    top=$(echo "$out" | sed -n 's/.*GOODPUT_DRILL_OK top=\([a-z_]*\) .*/\1/p')
    JAX_PLATFORMS=cpu python tools/goodput.py validate "$tmp" \
        --expect-badput "$top"
    rm -rf "$tmp"
    echo "== goodput: disabled fast-path overhead budget (<2%) with the ledger compiled in =="
    JAX_PLATFORMS=cpu python benchmark/telemetry_overhead.py
}

lint() {
    echo "== lint: static-analysis suite (docs/STATIC_ANALYSIS.md) =="
    python -m pytest tests/test_analyze.py -q
    echo "== lint: mxlint over the tree (0 new findings vs baseline) =="
    python tools/mxlint.py --baseline ci/lint_baseline.json --assert-clean
}

nightly() {
    echo "== nightly: slow bucket (reference tests/nightly analog) =="
    MXNET_TEST_SLOW=1 python -m pytest tests/ -q -m slow
}

tpu() {
    # runs on a machine with a chip; with none, chip_smoke.py exits
    # non-zero and the stage is red — a missing chip is never a skip.
    # One process per chip: the two commands run one after the other.
    echo "== tpu: the main path starts on the chip =="
    python chip_smoke.py
    echo "== tpu: every Pallas kernel compiles under Mosaic and matches =="
    python tools/tpu_kernel_check.py
}

case "$stage" in
    sanity) sanity ;;
    unit) unit ;;
    native) native ;;
    contracts) contracts ;;
    chaos) chaos ;;
    telemetry) telemetry ;;
    resilience) resilience ;;
    pipeline) pipeline ;;
    zero) zero ;;
    fp8) fp8 ;;
    mesh) mesh ;;
    serve) serve ;;
    autotune) autotune ;;
    quantize) quantize ;;
    trace) trace ;;
    insight) insight ;;
    blackbox) blackbox ;;
    stream) stream ;;
    goodput) goodput ;;
    servefleet) servefleet ;;
    lint) lint ;;
    nightly) nightly ;;
    tpu) tpu ;;
    all) sanity; unit; native; contracts; chaos; telemetry; resilience; pipeline; zero; fp8; mesh; serve; autotune; quantize; trace; insight; blackbox; stream; goodput; servefleet; lint ;;
    *) echo "unknown stage $stage"; exit 2 ;;
esac
