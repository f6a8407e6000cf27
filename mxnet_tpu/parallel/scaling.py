"""Data-parallel weak-scaling harness (KVStore scaling-efficiency artifact).

BASELINE.md north star #3 is KVStore data-parallel scaling efficiency over
1->32 chips; the reference measures it with tools/bandwidth/measure.py over
kvstore push/pull. Here the measured object is the framework's actual DP
path — ShardedTrainStep (mesh-psum gradient reduction, the KVStore('device')
substrate) — run at n = 1, 2, 4, ... devices with FIXED per-device batch
(weak scaling: ideal = constant step time, efficiency_n = t_1 / t_n).

The same harness serves both regimes:
- virtual CPU mesh (CI / dryrun): meshes are built over sublists of the
  existing devices — honest wall-clock, but all virtual devices share host
  cores, so efficiency UNDERESTIMATES real-chip scaling (collectives are
  simulated serially). The numbers bound overhead, not ICI throughput.
- real hardware: pass ``devices=jax.devices()`` (or any sublist); meshes
  ride the actual ICI and the efficiencies are the headline metric.
"""
from __future__ import annotations

import time


def weak_scaling_table(ns=None, devices=None, per_device_batch=4,
                       image=24, classes=10, iters=8, warmup=3):
    """Run the DP ShardedTrainStep at each n in ``ns``; return a list of
    rows {n, ms_per_step, images_per_s, efficiency}.

    devices: device list to slice (default jax.devices()). ns defaults to
    powers of two up to len(devices).
    """
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax.sharding import Mesh, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    from mxnet_tpu.parallel.train import ShardedTrainStep

    devices = list(devices) if devices is not None else jax.devices()
    if ns is None:
        ns = []
        n = 1
        while n <= len(devices):
            ns.append(n)
            n *= 2

    def ce_loss(logits, y):
        from ..ops.xent import sparse_softmax_xent
        return jnp.mean(sparse_softmax_xent(logits, y))

    rows = []
    t1 = None
    for n in ns:
        mesh = Mesh(onp.array(devices[:n]).reshape(n), ("dp",))
        net = get_resnet(1, 18, classes=classes)
        net.initialize()
        net(mx.np.zeros((2, 3, image, image), dtype="float32"))
        step = ShardedTrainStep(
            net, ce_loss,
            mx.optimizer.create("sgd", learning_rate=0.05, momentum=0.9),
            mesh, batch_specs=(P("dp"), P("dp")), n_labels=1)
        bs = per_device_batch * n
        x = onp.random.RandomState(0).uniform(
            size=(bs, 3, image, image)).astype("float32")
        y = onp.zeros((bs,), "int32")
        for _ in range(max(warmup, 1)):   # >=1: excludes compile from timing
            loss = step(x, y)
        loss.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(x, y)
        loss.wait_to_read()
        dt = (time.perf_counter() - t0) / iters
        if t1 is None:
            t1 = dt
        rows.append({
            "n": n,
            "global_batch": bs,
            "ms_per_step": round(dt * 1e3, 2),
            "images_per_s": round(bs / dt, 1),
            "efficiency": round(t1 / dt, 3),
            # isolated collective cost at this n: a bare jitted psum of a
            # gradient-sized vector over the same mesh. On the virtual
            # mesh this is the number a reader can extrapolate from —
            # step-time growth beyond (compute_n1 + collective) is host
            # core contention, not communication.
            "collective_ms": round(_time_allreduce(mesh, net) * 1e3, 3),
        })
    if rows:
        rows[0]["decomposition"] = (
            "ms_per_step(n=1) is pure compute; collective_ms isolates the "
            "gradient-allreduce at each n; the remainder of the step-time "
            "growth on a virtual mesh is host-core contention")
    return rows


def _time_allreduce(mesh, net, iters=10):
    """Time one jitted gradient-sized psum over the mesh's 'dp' axis."""
    import functools
    import time as _t

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    n = mesh.devices.size
    nparams = sum(int(onp_prod(p.shape)) for p in
                  net.collect_params().values() if p._data is not None)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"))
    def ar(v):
        return jax.lax.psum(v, "dp")

    v = jnp.ones((n, max(nparams // max(n, 1), 1)), jnp.float32)
    v = jax.device_put(v, NamedSharding(mesh, P("dp")))
    ar(v).block_until_ready()  # compile
    t0 = _t.perf_counter()
    for _ in range(iters):
        out = ar(v)
    out.block_until_ready()
    return (_t.perf_counter() - t0) / iters


def onp_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def multiprocess_overhead_table(ns=(2, 4), timeout=420):
    """Launch n real processes (tools/launch.py, one core-set each) and
    measure the DCN-path collective in isolation: per-rank jitted matmul
    compute vs allreduce_across_processes latency at two payload sizes.

    Separates process-collective overhead from the shared-core contention
    that dominates the virtual in-process mesh (reference anchor:
    tests/nightly/dist_sync_kvstore.py launch scheme). Rows come from
    rank 0 of each run; failures degrade to an {'n', 'error'} row.
    """
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    script = os.path.join(repo, "benchmark", "scaling_proc.py")
    rows = []
    for n in ns:
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        try:
            r = subprocess.run(
                [sys.executable, os.path.join(repo, "tools", "launch.py"),
                 "-n", str(n), sys.executable, script],
                capture_output=True, text=True, timeout=timeout, env=env,
                cwd=repo)
        except subprocess.TimeoutExpired:
            rows.append({"n": n, "error": f"timeout {timeout}s"})
            continue
        row = None
        for line in r.stdout.splitlines():
            if line.startswith("PROC_SCALING "):
                cand = json.loads(line[len("PROC_SCALING "):])
                if cand.get("rank") == 0:
                    row = cand
        if row is None:
            rows.append({"n": n, "error":
                         (r.stderr or r.stdout)[-300:] or "no output"})
        else:
            row.pop("rank", None)
            if (os.cpu_count() or 1) < n:
                row["shared_cores"] = True  # pinning impossible: ranks
                # contend for cores, so allreduce_ms includes contention
            rows.append(row)
    return rows
