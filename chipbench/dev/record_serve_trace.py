"""Record the small serve trace the serve readers' test reads
(``chipbench/tests/data/serve.xplane.pb`` and ``serve_obs.json``): the
tiny stand-in of ``gpt2m-serve-flood`` through ``run.py`` itself, traced
for a few hundredths of a second (the profiler's start takes the first
of them), with what the driver observed on the host and every per-layer
value the run printed.  The trace is kept without its ``/host:metadata``
plane — the compiled programs' HLO, two thirds of the file, which no
reader opens.  Run on the chip.

    python chipbench/dev/record_serve_trace.py chipbench/tests/data
"""
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "gpt2m-serve-flood"
KEEP = ("steps", "requests", "traced", "serve_window", "max_slots",
        "weight_itemsize", "cache_itemsize", "memory_peak_bytes")


def _varint(b, i):
    x = shift = 0
    while True:
        x |= (b[i] & 0x7F) << shift
        shift += 7
        i += 1
        if not b[i - 1] & 0x80:
            return x, i


def _fields(b):
    """``(field, payload or None, the field's own bytes)`` of a
    protobuf message."""
    i = 0
    while i < len(b):
        start = i
        tag, i = _varint(b, i)
        payload = None
        if tag & 7 == 2:
            n, i = _varint(b, i)
            payload, i = b[i:i + n], i + n
        elif tag & 7 == 0:
            _, i = _varint(b, i)
        else:
            i += {1: 8, 5: 4}[tag & 7]
        yield tag >> 3, payload, b[start:i]


def without_plane(xspace, name):
    """The ``XSpace`` bytes less the plane called ``name`` (``planes`` is
    field 1, a plane's ``name`` its field 2)."""
    out = bytearray()
    for field, payload, raw in _fields(xspace):
        if field == 1 and payload is not None and any(
                f == 2 and p == name.encode() for f, p, _ in _fields(payload)):
            continue
        out += raw
    return bytes(out)


def main(out_dir):
    import run as harness

    seen = {}
    real_load, real_resolve = harness.load_module, harness.resolve

    def resolve(*a, **k):
        entry, cell, cfg, traffic = real_resolve(*a, **k)
        return entry, cell, cfg, dict(traffic, trace_seconds=0.06)

    def load(kind, name):
        mod = real_load(kind, name)
        if kind == "drivers":
            real_run = mod.run

            def run(ctx):
                out = real_run(ctx)
                seen.update({k: out["observations"][k] for k in KEEP})
                seen.update(cfg=ctx["cfg"], xplane=out["xplane"],
                            device_kind=ctx["devices"][0].device_kind)
                return out
            mod.run = run
        return mod

    harness.resolve, harness.load_module = resolve, load
    text = io.StringIO()
    with contextlib.redirect_stdout(text):
        harness.main(["--workload", CELL, "--tiny", "--seed", "46",
                      "--seconds", "2", "--trace", "1"])
    line = json.loads(text.getvalue().strip().splitlines()[-1])
    seen["expected"] = {k: v["value"] for k, v in line["metrics"].items()
                        if k.endswith(".serve")}
    with open(seen.pop("xplane"), "rb") as f:
        kept = without_plane(f.read(), "/host:metadata")
    with open(os.path.join(out_dir, "serve.xplane.pb"), "wb") as f:
        f.write(kept)
    with open(os.path.join(out_dir, "serve_obs.json"), "w") as f:
        json.dump(seen, f)
    print(os.path.getsize(os.path.join(out_dir, "serve.xplane.pb")),
          "bytes of trace;", json.dumps(seen["expected"]))


if __name__ == "__main__":
    main(sys.argv[1])
