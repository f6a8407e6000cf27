"""chipbench — one cell, once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is a new process that holds the cell's chips.  It refuses anything
but a TPU whose ``device_kind`` is in ``chipbench/peaks.py`` with at least
the chips the cell asks for (non-zero exit, no result line), builds the
model with weights made on the device from ``--seed``, warms up this
cell's shapes through the checkout's compile cache, measures for
``--seconds``, checks what the window produced against the plain
reference outside the window, and prints one JSON object as the last
line of stdout: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (+ ``breakdown`` when traced).  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics.

Every name resolves to a file: ``BENCHMARK.json`` names the cell, its
configuration and its traffic; ``workloads/<cell>.json`` holds the
cell's own arguments, ``configs/<config>.json`` the sizes,
``traffic/<mix>.json`` the load, whose ``driver`` names
``drivers/<kind>.py``; the configuration's ``family`` names
``families/``, ``reference/`` and ``flops/<family>.py``; each per-layer
metric has a reader ``layer_metrics/<metric>.py``.

``--tiny`` (with ``JAX_PLATFORMS=cpu``) runs a tiny stand-in of the cell
end to end on whatever back-end JAX has and labels its line
``"rehearsal": true``: it proves paths, never speeds.  ``--control``
switches on the cell's ``control`` (the next lower precision), which the
check must then refuse.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json")


def resolve(bench, workload, tiny=False, control=False):
    """Everything one cell is made of, from the names in BENCHMARK.json."""
    from common import deep_merge
    entry = by_name(bench["workloads"], workload, "workload")
    cell = load_json(HERE, "workloads", entry["name"] + ".json")
    cfg_entry = by_name(bench["configs"], entry["config"], "config")
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    if tiny:
        cfg = deep_merge(cfg, cfg.get("tiny", {}))
        cell = deep_merge(cell, cell.get("tiny", {}))
        traffic = deep_merge(traffic, traffic.get("tiny", {}))
    if control:
        cell = deep_merge(cell, cell["control"])
    return entry, cell, cfg, traffic


def metrics_for(bench, group, workload):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    entry, cell, cfg, traffic = resolve(bench, args.workload, args.tiny,
                                        args.control)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import common
    import peaks

    devices = jax.devices()
    chips = int(entry["chips"])
    if args.tiny:
        if len(devices) < chips:
            raise SystemExit(f"chipbench: --tiny needs {chips} devices "
                             f"(XLA_FLAGS=--xla_force_host_platform_"
                             f"device_count={chips}), have {len(devices)}")
        peak = peaks.PEAKS["TPU v5 lite"]     # arithmetic only; no speed
    else:
        if devices[0].platform != "tpu":
            raise SystemExit(
                f"chipbench: JAX platform is {devices[0].platform!r}, need "
                "'tpu': a measuring run never falls back (use --tiny with "
                "JAX_PLATFORMS=cpu for a rehearsal)")
        if len(devices) < chips:
            raise SystemExit(f"chipbench: cell {args.workload} needs "
                             f"{chips} chips, JAX has {len(devices)}")
        peak = peaks.peaks(devices[0].device_kind)

    # the program's own placement rule: JAX_COMPILATION_CACHE_DIR if the
    # machine sets it, else one fixed path inside the checkout
    from mxnet_tpu import _compile_cache
    cache_dir = _compile_cache.configure(os.path.join(ROOT, ".jax_cache"))
    watch = common.CompileWatch()

    family = cfg["family"]
    ctx = {
        "args": args, "seed": args.seed, "seconds": seconds,
        "trace": bool(args.trace), "tiny": args.tiny,
        "entry": entry, "cell": cell, "cfg": cfg, "traffic": traffic,
        "chips": chips, "devices": devices[:chips], "peak": peak,
        "watch": watch,
        "t_process": T_PROCESS, "cache_dir": cache_dir,
        "trace_dir": os.path.join(ROOT, ".chipbench_trace", args.workload),
        "family": load_module("families", family),
        "reference": load_module("reference", family),
        "flops": load_module("flops", family),
    }
    driver = load_module("drivers", traffic["driver"])
    if driver is None:
        raise SystemExit(f"chipbench: no driver chipbench/drivers/"
                         f"{traffic['driver']}.py")
    print(f"# chipbench {args.workload} seed={args.seed} seconds={seconds} "
          f"trace={args.trace} device={common.device_info(devices)} "
          f"cache={cache_dir}", flush=True)
    out = driver.run(ctx)

    # ---- the line -----------------------------------------------------
    obs = out["observations"]
    obs.update(ctx=ctx, end_to_end=out["end_to_end"])
    for row in out["check"].rows:
        print("# check " + json.dumps(row), flush=True)
    print("# info " + json.dumps(out.get("info", {})), flush=True)
    device = common.device_info(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    line = {"correct": out["check"].correct,
            "attempted": int(out["attempted"]), "failed": int(out["failed"])}
    metrics = {}
    if args.trace:
        import xplane
        t_reduce = time.perf_counter()
        trace = xplane.reduce(out["xplane"], len(ctx["devices"]))
        print(f"# trace: reduced {sum(len(d['ops']) for d in trace['devices'])}"
              f" device operations of the trace in "
              f"{time.perf_counter() - t_reduce:.1f} s", flush=True)
        obs["device_trace"] = trace
        for m in metrics_for(bench, "per_layer", args.workload):
            reader = load_module("layer_metrics", m["name"])
            value = reader.read(obs) if reader else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    else:
        for m in metrics_for(bench, "end_to_end", args.workload):
            value = out["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    if args.tiny:
        line["rehearsal"] = True
    if args.control:
        line["control"] = True
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
