"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU.  Drives the two main paths once, through the entry
points a user calls, at the published width and depth of GPT-2 124M
(768 x 12 heads x 12 layers, vocab 50257, context 1024) with seeded
random weights:

- train: ``ShardedTrainStep(net, loss, adam, MeshConfig(dp=1))`` on one
  repeated batch of 8 x 1024 tokens — one compile, then steps; the loss
  must be finite and fall, and the Pallas flash kernel (forward, dK/dV,
  dQ: three Mosaic calls a layer) must be in the lowered step;
- serve: ``mx.serve.load(net, max_slots=8, warmup=True)``, twelve greedy
  requests over several prefill buckets, 32 new tokens each — all must
  complete with zero post-warmup compiles, and under a teacher-forced
  full forward of the same net every generated token's logit must lie
  within ``logit_tol`` of that position's max logit (token identity is
  too strict: random weights give near-ties, and fp32 matmuls on the MXU
  default to bf16 passes);
- with four or more devices, the train phase again under dp=4 and
  dp=2 x tp=2 with zero=0 and zero=1: the loss must track the one-chip
  run, every parameter and optimizer-state array must span four devices,
  and no device may hold the bulk of the memory.

No phase is wrapped in try/except: whatever fails ends the run with a
traceback and a non-zero exit code, and no result line.  Anything but a
TPU whose ``device_kind`` is in ``mx.insight.PEAKS`` is refused up front.
The last line of stdout is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the line before it, ``{"report": {...}}``, carries versions, per-phase
compile and run seconds, losses and cache counts.

Numbers printed here are a smoke's: how long start-up took, not how fast
the system is.

CPU rehearsal (tiny, no kernels; what the tests and a git-archive export
run): ``JAX_PLATFORMS=cpu python -c "import chip_smoke;
chip_smoke.run(chip_smoke.TINY)"``.
"""
from __future__ import annotations

import gc
import json
import sys
import time

#: GPT-2 124M as published; batch 8 x 1024 as bench.py sizes its GPT rows
FULL = dict(
    vocab=50257, units=768, layers=12, heads=12, context=1024,
    batch=8, seq=1024, steps=5,
    # forward + dK/dV + dQ kernels per layer, counted in the lowered step
    flash_calls=3 * 12,
    slots=8, new_tokens=32,
    # twelve requests, more than slots, over buckets 16/32/64/128/256/512
    prompt_lens=(5, 12, 17, 30, 40, 60, 70, 100, 130, 200, 300, 9),
    # in logit units; the first v5e run measured a max gap of 0.012 (a
    # wrong cache row would show as ~4: a random token against the max of
    # 50k logits of unit spread)
    logit_tol=0.05,
    # dp>1 changes the reduction order only; tp splits matmuls
    mesh_loss_rtol=2e-2,
)

#: the same program at a size the CPU runs in seconds (no flash kernel:
#: the CPU never takes the Pallas path)
TINY = dict(FULL, vocab=512, units=64, layers=2, heads=4, context=128,
            seq=64, flash_calls=0, slots=4, new_tokens=8,
            prompt_lens=(3, 9, 17, 20, 33, 40), logit_tol=1e-3)


def _build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM, GPTModel
    mx.random.seed(0)
    net = GPTForCausalLM(backbone=GPTModel(
        vocab_size=cfg["vocab"], units=cfg["units"],
        hidden_size=4 * cfg["units"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], max_length=cfg["context"],
        # dropout off: attention is then eligible for the flash kernel
        dropout=0.0, embed_dropout=0.0))
    net.initialize()
    net(mx.np.zeros((1, 2), dtype="int32"))   # deferred shapes
    return net


def _loss(logits, labels):
    import jax.numpy as jnp
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    return jnp.mean(sparse_softmax_xent(logits, labels))


def _cache_counts():
    from mxnet_tpu import telemetry
    c = telemetry.counters(prefix="compile.persistent_cache_",
                           aggregate=True)
    return (int(c.get("compile.persistent_cache_requests_total", 0)),
            int(c.get("compile.persistent_cache_hits_total", 0)))


def _bytes_in_use():
    """Per-device bytes in use; None where the back-end keeps no stats
    (the CPU rehearsal)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    return [s["bytes_in_use"] if s else None for s in stats]


def train_phase(cfg, mesh=None, zero=0):
    """One compile + ``steps`` steps of the sharded train step on one
    repeated batch.  Returns the phase report (losses included)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

    mesh = mesh or MeshConfig(dp=1)
    net = _build_net(cfg)
    train = ShardedTrainStep(
        net, _loss, mx.optimizer.create("adam", learning_rate=1e-4),
        mesh, batch_specs=mesh.batch_specs(2, 2), n_labels=1, zero=zero)
    ids = onp.random.RandomState(0).randint(
        0, cfg["vocab"], (cfg["batch"], cfg["seq"] + 1)).astype("int32")
    x, y = ids[:, :-1], ids[:, 1:]

    # the kernel must be IN the step, not inferred from the sequence
    # length: count Mosaic calls in the lowered module.  Compiling that
    # module is the next line's job, and nothing there swallows a refusal.
    flash_calls = train.lower(x, y).as_text().count("tpu_custom_call")
    if flash_calls != cfg["flash_calls"]:
        raise RuntimeError(
            f"expected {cfg['flash_calls']} Mosaic calls in the train "
            f"step, found {flash_calls}")

    req0, hit0 = _cache_counts()
    t0 = time.perf_counter()
    losses = [float(train(x, y).asnumpy())]          # compile + step 1
    t1 = time.perf_counter()
    for _ in range(cfg["steps"]):
        losses.append(float(train(x, y).asnumpy()))
    t2 = time.perf_counter()
    req1, hit1 = _cache_counts()
    if not all(onp.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")

    arrays = [a for tree in (train.trainable, train.states)
              for a in _leaves(tree)]
    span = sorted({len(a.sharding.device_set) for a in arrays})
    return {
        "mesh": {a: s for a, s in mesh.shape.items() if s > 1} or {"dp": 1},
        "zero": zero,
        "flash_mosaic_calls": flash_calls,
        "compile_and_first_step_s": round(t1 - t0, 2),
        "run_s_per_step": round((t2 - t1) / cfg["steps"], 4),
        "losses": [round(v, 4) for v in losses],
        "sharding_device_set_sizes": span,
        "bytes_in_use_per_device": _bytes_in_use(),
        "cache_requests": req1 - req0, "cache_hits": hit1 - hit0,
    }


def _leaves(tree):
    import jax
    return [a for a in jax.tree_util.tree_leaves(tree)
            if hasattr(a, "sharding")]


def serve_phase(cfg):
    """Warm up the engine, serve the requests, check them against a
    teacher-forced full forward.  Returns the phase report."""
    import numpy as onp

    import mxnet_tpu as mx

    net = _build_net(cfg)
    req0, hit0 = _cache_counts()
    t0 = time.perf_counter()
    eng = mx.serve.load(net, max_slots=cfg["slots"], warmup=True)
    t1 = time.perf_counter()
    req1, hit1 = _cache_counts()

    rng = onp.random.RandomState(1)
    prompts = [rng.randint(1, cfg["vocab"], size=n).tolist()
               for n in cfg["prompt_lens"]]
    buckets = sorted({eng.bucket_for(len(p)) for p in prompts})
    if len(buckets) < 2:
        raise RuntimeError(f"prompts span one prefill bucket: {buckets}")
    reqs = [eng.submit(p, max_new_tokens=cfg["new_tokens"])
            for p in prompts]
    eng.run()
    t2 = time.perf_counter()
    st = eng.stats()
    incomplete = [r.id for r in reqs
                  if not r.finished or r.rejected
                  or len(r.generated) != cfg["new_tokens"]]
    if incomplete:
        raise RuntimeError(f"incomplete requests: {incomplete}")
    if st["post_warmup_compiles"] != 0:
        raise RuntimeError(
            f"{st['post_warmup_compiles']} post-warmup compiles")

    # teacher-forced oracle: one full forward of the same net over
    # prompt + generated (right-padded; causal, so padding cannot reach
    # back), then the generated token's logit against the row's max
    total = max(len(p) for p in prompts) + cfg["new_tokens"]
    pad = -total % 128 + total
    if pad > cfg["context"]:
        raise RuntimeError(f"oracle length {pad} exceeds the context")
    batch = onp.zeros((len(reqs), pad), "int32")
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        batch[i, :len(p) + len(r.generated)] = p + r.generated
    net.hybridize()
    logits = net(mx.np.array(batch, dtype="int32")).asnumpy()
    gaps = []
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + len(r.generated)]
        chosen = rows[onp.arange(len(r.generated)), r.generated]
        gaps.append(float(onp.max(rows.max(axis=-1) - chosen)))
    if not all(onp.isfinite(gaps)):
        raise RuntimeError(f"non-finite oracle logits: {gaps}")
    if max(gaps) > cfg["logit_tol"]:
        raise RuntimeError(
            f"generated token's logit is {max(gaps):.4f} below the "
            f"teacher-forced max (tolerance {cfg['logit_tol']}): {gaps}")
    return {
        "requests": len(reqs), "slots": cfg["slots"],
        "prefill_buckets_used": buckets,
        "tokens_out": st["tokens_out"], "decode_steps": st["steps"],
        "engine_compiles": st["compiles"],
        "post_warmup_compiles": st["post_warmup_compiles"],
        "warmup_compile_s": round(t1 - t0, 2),
        "run_s": round(t2 - t1, 2),
        "max_logit_gap": round(max(gaps), 5),
        "logit_tol": cfg["logit_tol"],
        "bytes_in_use_per_device": _bytes_in_use(),
        "warmup_cache_requests": req1 - req0,
        "warmup_cache_hits": hit1 - hit0,
    }


def mesh_phase(cfg, reference_losses):
    """Four chips: the train phase under dp=4 and dp=2 x tp=2, zero 0
    and 1, against the one-chip loss; placement checked per array and
    per device."""
    import numpy as onp

    from mxnet_tpu.parallel import MeshConfig

    reports = []
    for axes in (dict(dp=4), dict(dp=2, tp=2)):
        for zero in (0, 1):
            rep = train_phase(cfg, MeshConfig(**axes), zero=zero)
            gc.collect()
            if not onp.allclose(rep["losses"], reference_losses,
                                rtol=cfg["mesh_loss_rtol"]):
                raise RuntimeError(
                    f"{axes} zero={zero} losses {rep['losses']} leave the "
                    f"one-chip run {reference_losses}")
            if rep["sharding_device_set_sizes"] != [4]:
                raise RuntimeError(
                    f"{axes} zero={zero}: arrays span "
                    f"{rep['sharding_device_set_sizes']} devices, not 4")
            # device 0 also keeps the Block's own parameter copy (0.5 GB
            # at 124M; four-chip run, PR 21), so "balanced" is: nobody
            # holds less than 0.4 of the fullest device
            used = [b for b in rep["bytes_in_use_per_device"][:4]
                    if b is not None]
            if used and min(used) < 0.4 * max(used):
                raise RuntimeError(
                    f"{axes} zero={zero}: memory piled up: {used}")
            reports.append(rep)
    return reports


def run(cfg):
    """Every phase, in sequence, on whatever back-end JAX has.  ``main``
    is the only caller that may claim a chip result."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import _compile_cache, telemetry

    cache_dir = _compile_cache.configure(_compile_cache.CHECKOUT_CACHE)
    telemetry.enable()   # the compile.persistent_cache_* counters
    report = {"cache_dir": cache_dir, "config": {
        k: cfg[k] for k in ("vocab", "units", "layers", "heads", "context",
                            "batch", "seq", "slots", "new_tokens")}}
    print(f"# train: {cfg['batch']} x {cfg['seq']} tokens, dp=1",
          file=sys.stderr, flush=True)
    report["train"] = train_phase(cfg)
    if len(jax.devices()) >= 4:
        print("# mesh: dp4, dp2 x tp2, zero 0/1", file=sys.stderr,
              flush=True)
        report["mesh"] = mesh_phase(cfg, report["train"]["losses"])
    # one chip holds the train state and the serve cache only in
    # sequence: everything train_phase built is unreferenced by now
    gc.collect()
    report["bytes_in_use_between_phases"] = _bytes_in_use()
    print(f"# serve: {len(cfg['prompt_lens'])} requests, "
          f"{cfg['slots']} slots", file=sys.stderr, flush=True)
    report["serve"] = serve_phase(cfg)
    report["cache_requests"], report["cache_hits"] = _cache_counts()
    mx.waitall()
    return report


def main():
    t0 = time.perf_counter()
    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # no accelerator: no result line, a non-zero exit code
        sys.exit(f"chip_smoke: JAX platform is {dev.platform!r}, need "
                 "'tpu' — this script only ever reports a chip run")
    from mxnet_tpu import insight
    bf16_peak, _, hbm_bw = insight.peaks(dev.device_kind)   # unknown: raises
    from importlib.metadata import version
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"# device: {device}", file=sys.stderr, flush=True)

    report = run(FULL)
    # the report is the line before last; the last line is the verdict
    # alone, with exactly these keys (the driver parses it strictly)
    print(json.dumps({"report": {
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": version("libtpu")},
        "peaks": {"bf16_flops": bf16_peak, "hbm_bytes_per_s": hbm_bw},
        "wall_s": round(time.perf_counter() - t0, 1),
        **report}}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
