"""One benchmark cell's train step built in several forms in ONE process,
each timed (on the chip), compiled for a described v5e (on the CPU,
``--compile``) or only traced and lowered (``--trace``, on the chip's
host: this sandbox's CPU traces regions inside regions four times
faster than that host does): how a builder ranks recomputation policies,
region depths or a cell's own step arguments before a run of the cell
itself.

    python tools/step_variants.py [--cell ouro-train-8k] [--seed n]
        [--updates 8] [--tiny] [--compile [--hlo dir] | --trace [--profile]]
        '<json>' ['<json>' ...]

A variant is a JSON object; every key is optional:

    {"cfg": {"layer_remat": ["pallas_call", "attn.qkv"]},   # over the configuration file
     "cell": {"step": {"remat": "dots"}},                   # over the cell's file
     "depth": 2,      # forms the library does not ship, rebuilt here by
                      # wrapping gluon.block._boundary_call: 1 = one region a
                      # flagged application (PR 42's rule), 2 = its flagged
                      # children too, ... (left out: the shipped rule)
     "reuse": false}  # every application of a flagged block traced anew

``'{}'`` is the cell as the benchmark runs it.  Timed: weights from the
seed, 2 updates (the first holds trace + lower + compile), then
``--updates`` with one fetch at the end; a line a variant with ms an
update, the first call's seconds, the step program's ``trace_s`` /
``lower_s`` (``_compile_cache.report()``), and the ``block.boundary_*``
counters of the trace.  Compiled: ``memory_analysis()`` as
``chipbench/tests/test_compile_v5e.py`` sums it, the Mosaic calls in the
lowered text and its ``optimization_barrier``s, and with ``--hlo dir`` the
optimized HLO as text (two trees' texts, instruction numbering and
``metadata={...}`` stripped, say whether an edit kept the compiled
program: PERF.md section 6, PR 44); nothing runs.  Traced:
seconds of ``jit(step).trace()`` and of lowering it for a TPU, and with
``--profile`` a ``cProfile`` of the trace under ``chiprun_out/prof/``.
Compiles are cold: set no store by a variant's first call; give a variant twice
to read ``first_call_s`` warm.  (~1 chip-minute a variant on
``ouro-train-8k``; PERF.md section 6, PRs 42 and 44.)
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "chipbench"),
          os.path.join(ROOT, "chipbench", "tests")):
    sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _resolved(cell_name, tiny, variant, resolve=None):
    import common
    import run
    bench = run.load_json(ROOT, "BENCHMARK.json")
    entry, cell, cfg, mix = (resolve or run.resolve)(bench, cell_name, tiny)
    return (entry, common.deep_merge(cell, variant.get("cell", {})),
            dict(cfg, **variant.get("cfg", {})), mix)


def _step_programs():
    from mxnet_tpu import _compile_cache
    return [r for r in _compile_cache.report()
            if "step" in r.get("fun_name", "")]


def timed(args, variant):
    import jax
    import run
    from drivers import train_steps
    from mxnet_tpu import telemetry

    entry, cell, cfg, mix = _resolved(args.cell, args.tiny, variant)
    ctx = {"cell": cell, "cfg": cfg, "seed": args.seed,
           "family": run.load_module("families", cfg["family"])}
    seen = len(_step_programs())
    telemetry.reset()
    telemetry.enable()
    net, train = train_steps.build(ctx)
    stream = train_steps.batches(args.seed, cfg["vocab_size"],
                                 mix["sequences"], mix["seq_len"])
    t0 = time.perf_counter()
    loss = float(train_steps.feed(train, *next(stream)).asnumpy())
    first_s = time.perf_counter() - t0
    counters = telemetry.counters("block.boundary")
    telemetry.enable(False)
    float(train_steps.feed(train, *next(stream)).asnumpy())
    batches = [next(stream) for _ in range(args.updates)]
    t0 = time.perf_counter()
    for x, y in batches:
        out = train_steps.feed(train, x, y)
    last = float(out.asnumpy())
    ms = 1e3 * (time.perf_counter() - t0) / args.updates
    prog = _step_programs()[seen:]
    stats = jax.devices()[0].memory_stats() or {}
    row = {"variant": variant, "ms_an_update": round(ms, 2),
           "first_call_s": round(first_s, 1),
           "trace_s": round(sum(r["trace_s"] for r in prog), 2),
           "lower_s": round(sum(r["lower_s"] for r in prog), 2),
           "peak_gb": round(stats.get("peak_bytes_in_use", 0) / 1e9, 2),
           "losses": [round(loss, 4), round(last, 4)], **counters}
    del net, train, out
    gc.collect()
    return row


def compiled(args, variant, topo, name):
    import run
    import test_compile_v5e as t

    real = run.resolve
    run.resolve = lambda *a, **k: _resolved(args.cell, args.tiny, variant,
                                            real)
    seen = len(_step_programs())
    try:
        t0 = time.perf_counter()
        exe, text, resident, cfg = t._train_compile(args.cell, topo)
        took = time.perf_counter() - t0
    finally:
        run.resolve = real
    if args.hlo:
        os.makedirs(args.hlo, exist_ok=True)
        with open(os.path.join(args.hlo, name + ".hlo"), "w") as f:
            f.write(exe.as_text())
    m = exe.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    prog = _step_programs()[seen:]
    return {"variant": variant,
            "compiled_gb": round((live + resident) / 1e9, 2),
            "temp_gb": round(m.temp_size_in_bytes / 1e9, 2),
            "mosaic_calls": text.count("tpu_custom_call"),
            "barriers": text.count("optimization_barrier"),
            "trace_s": round(sum(r["trace_s"] for r in prog), 2),
            "lower_s": round(sum(r["lower_s"] for r in prog), 2),
            "build_and_compile_s": round(took, 1)}


def traced(args, variant, name):
    import cProfile
    import jax
    import jax.numpy as jnp
    import run
    from drivers import train_steps
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel.mesh import activation_sharding

    entry, cell, cfg, mix = _resolved(args.cell, args.tiny, variant)
    ctx = {"cell": cell, "cfg": cfg, "seed": args.seed,
           "family": run.load_module("families", cfg["family"])}
    net, train = train_steps.build(ctx)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    tokens = jax.ShapeDtypeStruct((mix["sequences"], mix["seq_len"]),
                                  jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    step_args = jax.tree_util.tree_map(
        spec, (train.trainable, train.aux, train.states, train.extra)) + (
        jax.ShapeDtypeStruct((2,), jnp.uint32), scalar, scalar, tokens, tokens)
    jitted = jax.jit(lambda *a: train._step.__wrapped__(*a))
    prof = cProfile.Profile() if args.profile else None
    telemetry.reset()
    telemetry.enable()
    with activation_sharding(train.mesh, **train._act_rules):
        t0 = time.perf_counter()
        if prof:
            prof.enable()
        out = jitted.trace(*step_args)
        if prof:
            prof.disable()
        t1 = time.perf_counter()
        out.lower(lowering_platforms=("tpu",))
        t2 = time.perf_counter()
    counters = telemetry.counters("block.boundary")
    telemetry.enable(False)
    if prof:
        os.makedirs("chiprun_out/prof", exist_ok=True)
        prof.dump_stats(f"chiprun_out/prof/{name}.pstats")
    return {"variant": variant, "trace_s": round(t1 - t0, 2),
            "lower_s": round(t2 - t1, 2), **counters}


def _rebuilt(block, variant):
    """``gluon.block._boundary_call`` as the variant wants it: no region
    below ``depth`` boundaries, no trace used again."""
    shipped, plain = block._boundary_call, block.Block.__call__

    def call(blk, args, kwargs):
        tls = block._boundary_tls
        if len(getattr(tls, "open", ())) >= variant.get("depth", 1e9):
            return plain(blk, *args, **kwargs)
        if not variant.get("reuse", True):
            tls.__dict__.pop("traced", None)
        return shipped(blk, args, kwargs)
    return call


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="ouro-train-8k")
    ap.add_argument("--seed", type=int, default=2100004401)
    ap.add_argument("--updates", type=int, default=8)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--hlo", help="with --compile: a directory for each "
                    "variant's optimized HLO")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("variants", nargs="+")
    args = ap.parse_args()
    os.chdir(ROOT)
    topo = None
    if args.compile:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    if args.compile or (args.trace and jax.default_backend() != "tpu"):
        from mxnet_tpu import runtime
        from mxnet_tpu.autotune import kernels
        runtime.on_tpu = lambda: True
        kernels._device_family = lambda kind=None: "v5e"
    if args.compile:
        from jax.experimental import topologies
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    from mxnet_tpu.gluon import block
    shipped = block._boundary_call
    for i, text in enumerate(args.variants):
        variant = json.loads(text)
        block._boundary_call = _rebuilt(block, variant)
        try:
            row = compiled(args, variant, topo, f"v{i}") if args.compile \
                else traced(args, variant, f"v{i}") if args.trace \
                else timed(args, variant)
        finally:
            block._boundary_call = shipped
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
