"""The readers of the program's own names: the scope an op_name falls
under, the set-up split against a recorded ``report()``, and every new
per-layer reader against one small trace recorded on the v5e with the
names in it (``data/named.xplane.pb``, ``dev/record_named_trace.py``:
three updates of a two-layer, 128-wide GPT-2 at 2 x 512 tokens through
``ShardedTrainStep``)."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NAMED = os.path.join(HERE, "data", "named.xplane.pb")
REPORT = os.path.join(HERE, "data", "compile_report.json")


def reader(name):
    import run
    return run.load_module("layer_metrics", name)


def test_scope_of_an_op_name():
    from program_trace import scope_of
    assert scope_of("jit(step)/jit(main)/jvp(mx.fwd)/mx.attn/"
                    "mx_flash_fwd/pallas_call") == "fwd"
    assert scope_of("jit(step)/while/body/transpose(jvp(mx.fwd))/mx.attn/"
                    "jit(_pad)/pad") == "bwd"
    assert scope_of("jit(step)/transpose(jvp(checkpoint(mx.fwd)))/"
                    "dot_general") == "bwd"
    assert scope_of("jit(step)/mx.optimizer/sqrt") == "optimizer"
    assert scope_of("states['backbone.ln.gamma'][0]") is None
    assert scope_of("jit(step)/while/body/add") is None
    assert scope_of("") is None


def test_setup_split_sums_to_what_compile_s_sums():
    """A recorded ``_compile_cache.report()`` of a warm tiny run: 19
    programs before the window, the reference's after it."""
    rec = json.load(open(REPORT))
    obs = {"window": (rec["window_open"], rec["window_open"] + 2.0),
           "compile_report": rec["programs"],
           "compile_setup": {"seconds": rec["compile_s"]}}
    parts = [reader(n).read(obs) for n in
             ("trace_s.setup", "lower_s.setup", "cache_load_s.setup")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(rec["compile_s"], rel=1e-6)
    assert reader("compile_s").read(obs) == rec["compile_s"]
    before = [r for r in rec["programs"] if r["at"] <= rec["window_open"]]
    assert len(before) == rec["programs_in_setup"] < len(rec["programs"])
    assert parts[0] == pytest.approx(sum(r["trace_s"] for r in before))


@pytest.fixture(scope="module")
def named():
    """``obs`` as ``run.py`` hands it to a reader, for the recorded
    trace (its three updates are the whole of the window)."""
    import xplane
    return {"device_trace": xplane.reduce(NAMED, 1), "xplane": NAMED}


def test_the_second_read_keeps_op_names_and_host_spans(named):
    import program_trace
    by_program = program_trace.op_names(NAMED)
    step = max(by_program.values(), key=len)
    assert step["mx_flash_fwd.2"] == (
        "jit(base_step)/jvp(mx.fwd)/mx.attn/mx_flash_fwd/pallas_call:")
    assert "transpose(jvp(mx.fwd))/mx.attn/mx_flash_bwd_dkv" in \
        step["mx_flash_bwd_dkv.2"]
    spans = program_trace.host_spans(NAMED)
    names = [s["name"] for s in spans]
    assert names.count("train.call") == 3
    assert names.count("ndarray.asnumpy") == 1
    calls = [s for s in spans if s["name"] == "train.call"]
    for part in ("train.shard_batch", "train.scalars", "train.dispatch"):
        inner = [s for s in spans if s["name"] == part]
        assert len(inner) == 3
        for s, c in zip(inner, calls):      # the parent contains it
            assert c["start"] <= s["start"] and s["end"] <= c["end"]
    ops, updates = program_trace.update_ops(named)
    assert updates == 3 and len(ops) == 1359
    assert {o["scope"] for o in ops} == {"fwd", "bwd", "optimizer", None}


@pytest.mark.parametrize("metric,value", [
    ("fwd_ms.train", 0.124335), ("bwd_ms.train", 0.115857),
    ("optimizer_ms.train", 0.001443), ("flash_fwd_ms.train", 0.020797),
    ("flash_dkv_ms.train", 0.017590), ("flash_dq_ms.train", 0.012443),
    ("attn_glue_ms.train", 0.007695), ("unscoped_share.train", 1.85644),
    ("host_call_ms.train", 3.40828)])
def test_reader_on_the_recorded_trace(named, metric, value):
    assert reader(metric).read(named) == pytest.approx(value, rel=1e-4)


def test_the_names_cover_the_step(named):
    """Scopes + the unscoped share make up the updates' device time; the
    three flash readers make up the Mosaic time ``flash_roofline.train``
    divides by."""
    import program_trace
    import xplane
    ops, n = program_trace.update_ops(named)
    total = sum(o["end"] - o["start"] for o in ops) / 1e6 / n
    parts = sum(reader(m).read(named) for m in
                ("fwd_ms.train", "bwd_ms.train", "optimizer_ms.train"))
    bare = reader("unscoped_share.train").read(named) / 100 * total
    assert parts + bare == pytest.approx(total, rel=1e-9)
    d0 = named["device_trace"]["devices"][0]
    mosaic = sum(o["end"] - o["start"] for o in d0["ops"]
                 if xplane.is_mosaic(o)
                 and any(m["start"] <= o["start"] and o["end"] <= m["end"]
                         for m in d0["step_modules"])) / 1e6 / n
    flash = sum(reader(m).read(named) for m in
                ("flash_fwd_ms.train", "flash_dkv_ms.train",
                 "flash_dq_ms.train"))
    assert flash == pytest.approx(mosaic, rel=1e-9)
    rows = dict(named["device_trace"]["device_ops"])
    for kernel in ("mx_flash_fwd", "mx_flash_bwd_dkv", "mx_flash_bwd_dq"):
        assert f"mosaic-kernel {kernel} [custom-call]" in rows


def test_a_program_without_the_names_reads_nothing():
    """The parent of the PR that named things, under these readers:
    ``small.xplane.pb`` has no scope, no ``mx_`` kernel and no ``mx/``
    span, and a program without ``report()`` no set-up records.  Every
    reader returns None; none raises."""
    import xplane
    small = os.path.join(HERE, "data", "small.xplane.pb")
    obs = {"device_trace": xplane.reduce(small, 1), "xplane": small,
           "window": (0.0, 1.0)}
    for m in ("fwd_ms.train", "bwd_ms.train", "optimizer_ms.train",
              "flash_fwd_ms.train", "flash_dkv_ms.train",
              "flash_dq_ms.train", "attn_glue_ms.train",
              "unscoped_share.train", "host_call_ms.train"):
        assert reader(m).read(obs) is None, m
    from mxnet_tpu import _compile_cache
    report = _compile_cache.__dict__.pop("report")
    try:
        for m in ("trace_s.setup", "lower_s.setup", "cache_load_s.setup"):
            assert reader(m).read(obs) is None, m
    finally:
        _compile_cache.report = report
