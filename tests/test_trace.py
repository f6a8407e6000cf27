"""mx.trace — causal span API, context propagation (threads + worker
processes), Perfetto export, the live ops endpoint, and the two e2e
acceptance trees (docs/OBSERVABILITY.md "Tracing"):

- one training step: ``train.step`` with data_wait / h2d / dispatch /
  drain children, sync-free loop preserved (sync_guard count unchanged
  vs untraced, zero RecompileWarning with tracing on);
- one serve request: ``serve.request`` with enqueue -> prefill ->
  decode_step x N -> drain children carrying the same request id, zero
  post-warmup compiles.

When ``MXNET_TRACE_E2E_DIR`` is set, the e2e tests also export their
rings (e2e_train.json / e2e_serve.json) so the CI ``trace`` stage can
re-validate the trees with tools/trace.py.
"""
import importlib.util
import json
import os
import threading
import urllib.request
import warnings

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, telemetry, trace
from mxnet_tpu.gluon.data import DataLoader

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trace_cli():
    spec = importlib.util.spec_from_file_location(
        "trace_cli", os.path.join(_REPO, "tools", "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts with the recorder off and an empty ring, and
    leaves the knob-derived defaults behind."""
    trace.disable()
    trace.clear()
    yield
    trace.clear()
    trace.configure()  # restore _active/_capacity from the knobs


def _children(events):
    kids = {}
    for ev in events:
        pid = ev["args"].get("parent_id")
        if pid is not None:
            kids.setdefault(pid, []).append(ev)
    return kids


# -- span API ---------------------------------------------------------------

def test_span_nesting_links_and_attrs():
    trace.enable()
    with trace.span("outer", category="test", step=1) as outer:
        assert trace.current_context() == (outer.trace_id, outer.span_id)
        with trace.span("inner", items=3):
            pass
    assert trace.current_context() is None
    inner, outer_ev = trace.spans()  # inner exits (records) first
    assert inner["name"] == "inner" and outer_ev["name"] == "outer"
    assert inner["ph"] == outer_ev["ph"] == "X"
    assert inner["args"]["parent_id"] == outer_ev["args"]["span_id"]
    assert inner["args"]["trace_id"] == outer_ev["args"]["trace_id"]
    # the root's trace_id is its own span_id
    assert outer_ev["args"]["trace_id"] == outer_ev["args"]["span_id"]
    assert "parent_id" not in outer_ev["args"]
    assert inner["args"]["items"] == 3
    assert outer_ev["args"]["step"] == 1 and outer_ev["cat"] == "test"
    assert inner["dur"] >= 0 and inner["ts"] >= outer_ev["ts"]


def test_disabled_is_a_cheap_noop():
    assert not trace.active()
    sp = trace.span("never", x=1)
    with sp as got:
        assert got.set(y=2) is got  # chainable no-op
    assert trace.begin("never") is None
    trace.emit("never", 0, 0)
    assert trace.spans() == []
    assert trace.stats() == {"active": False, "recorded": 0, "dropped": 0,
                             "capacity": trace.stats()["capacity"]}


def test_begin_end_async_handle_across_threads():
    trace.enable()
    root = trace.begin("req", category="test", request=7)
    child = trace.begin("phase", parent=root.context, request=7)
    # an async span may end on a different thread than it began
    t = threading.Thread(target=child.end, kwargs={"tokens": 3})
    t.start()
    t.join()
    root.end()
    root.end()  # idempotent: no duplicate record
    evs = trace.spans()
    assert [e["name"] for e in evs] == ["phase", "req"]
    phase, req = evs
    assert phase["args"]["parent_id"] == req["args"]["span_id"]
    assert phase["args"]["tokens"] == 3 and phase["args"]["request"] == 7


def test_emit_parents_to_explicit_context():
    trace.enable()
    root = trace.begin("root")
    trace.emit("leaf", trace.clock_us() - 50, 40, parent=root.context,
               category="test", n=1)
    root.end()
    leaf = trace.spans()[0]
    assert leaf["name"] == "leaf" and leaf["dur"] == 40
    assert leaf["args"]["parent_id"] == root.span_id
    assert leaf["cat"] == "test" and leaf["args"]["n"] == 1


def test_ring_eviction_counts_dropped(monkeypatch):
    telemetry.enable()
    telemetry.reset()
    try:
        trace.enable(buffer=8)
        for i in range(20):
            trace.emit(f"ev{i}", i, 1)
        evs = trace.spans()
        assert len(evs) == 8
        assert [e["name"] for e in evs] == [f"ev{i}" for i in range(12, 20)]
        assert trace.stats()["dropped"] == 12
        assert telemetry.counters(aggregate=True)["trace.dropped_total"] == 12
        trace.clear()
        assert trace.stats()["dropped"] == 0
    finally:
        telemetry.reset()
        telemetry.disable()


def test_knobs_arm_configure():
    prior_on, prior_buf = config.get("trace.enable"), config.get("trace.buffer")
    config.set("trace.enable", True)
    config.set("trace.buffer", 32)
    try:
        trace.configure()
        assert trace.active() and trace.stats()["capacity"] == 32
    finally:
        config.set("trace.enable", prior_on)
        config.set("trace.buffer", prior_buf)
        trace.configure()
    assert not trace.active()


# -- start-up record ----------------------------------------------------------

@pytest.fixture
def fresh_record(monkeypatch):
    """The start-up record as a new process has it: empty, all its room
    (this process filled its own long before this file ran)."""
    monkeypatch.setattr(trace, "_startup", [])
    monkeypatch.setattr(trace, "_startup_room", trace.STARTUP_SPANS)


def _recorder(on):
    if on:
        trace.enable(buffer=4 * trace.STARTUP_SPANS)
    assert trace.active() == on


def test_startup_keeps_the_first_spans_with_everything_off_then_stops(
        fresh_record):
    assert not trace.active() and trace.startup() == []
    for i in range(trace.STARTUP_SPANS):
        sp = trace.span(f"s{i}", category="test", i=i)
        assert type(sp) is trace._Kept
        with sp as got:
            assert got.set(twice=2 * i) is got
    # the record is full: the off path is the bare annotation again
    last = trace.span("one_too_many", x=1)
    assert type(last) is trace._Annotation
    with last as got:
        assert got.set(y=2) is got
    trace.emit("one_too_many", trace.clock_us(), 1)
    kept = trace.startup()
    assert [s["name"] for s in kept] \
        == [f"s{i}" for i in range(trace.STARTUP_SPANS)]
    assert kept[7]["attrs"] == {"i": 7, "twice": 14}
    assert all(s["parent"] is None and s["thread"] == threading.get_ident()
               and s["start_s"] <= s["end_s"] for s in kept)
    assert trace.spans() == []              # the ring saw none of it


@pytest.mark.parametrize("recorder", [False, True])
def test_startup_times_are_perf_counter_seconds(fresh_record, recorder):
    import time
    _recorder(recorder)
    t0 = time.perf_counter()
    with trace.span("timed"):
        time.sleep(0.002)
    t1 = time.perf_counter()
    (s,) = trace.startup()
    assert t0 <= s["start_s"] + 1e-6 and s["end_s"] <= t1 + 1e-6
    assert s["end_s"] - s["start_s"] >= 0.002


@pytest.mark.parametrize("recorder", [False, True])
def test_startup_keeps_an_emitted_span(fresh_record, recorder):
    _recorder(recorder)
    t = trace.clock_us()
    with trace.span("outer"):
        pass
    # already timed, stamped before ``outer`` opened: it sorts first
    trace.emit("import", t - 500, 400, category="startup", modules=3)
    first, second = trace.startup()
    assert first["name"] == "import" and second["name"] == "outer"
    assert first["attrs"] == {"modules": 3}
    assert first["end_s"] - first["start_s"] == pytest.approx(400e-6)
    assert first["parent"] is None and second["parent"] is None
    assert len(trace.spans()) == (2 if recorder else 0)


@pytest.mark.parametrize("recorder", [False, True])
def test_startup_parent_is_the_containing_span_of_the_same_thread(
        fresh_record, recorder):
    _recorder(recorder)
    with trace.span("a"):
        with trace.span("b"):
            with trace.span("c"):
                pass
        with trace.span("d"):
            pass
        # another thread's span lies inside ``a``'s interval, not under it
        t = threading.Thread(target=lambda: trace.span("other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    with trace.span("e"):
        pass
    kept = trace.startup()
    names = [s["name"] for s in kept]
    assert names == ["a", "b", "c", "d", "other", "e"]
    parent = {s["name"]: (None if s["parent"] is None
                          else names[s["parent"]]) for s in kept}
    assert parent == {"a": None, "b": "a", "c": "b", "d": "a",
                      "other": None, "e": None}
    assert kept[4]["thread"] != kept[0]["thread"]


@pytest.mark.parametrize("recorder", [False, True])
def test_clear_leaves_the_startup_record(fresh_record, recorder):
    _recorder(recorder)
    with trace.span("kept", category="test"):
        pass
    trace.clear()
    assert trace.spans() == []
    assert [s["name"] for s in trace.startup()] == ["kept"]
    # a copy: the caller cannot edit the record
    trace.startup()[0]["attrs"]["x"] = 1
    assert trace.startup()[0]["attrs"] == {}


def test_recorder_on_fills_ring_and_record_alike(fresh_record):
    trace.enable(buffer=4 * trace.STARTUP_SPANS)
    n = trace.STARTUP_SPANS + 5
    for i in range(n):
        with trace.span(f"s{i}", category="test") as sp:
            sp.set(i=i)
    ring, kept = trace.spans(category="test"), trace.startup()
    assert len(ring) == n and len(kept) == trace.STARTUP_SPANS
    for ev, s in zip(ring, kept):
        assert ev["name"] == s["name"] and ev["args"]["i"] == s["attrs"]["i"]
        assert ev["ts"] == round(s["start_s"] * 1e6)
        assert ev["ts"] + ev["dur"] == round(s["end_s"] * 1e6)
    # async handles are the ring's alone: they end on any thread
    trace.begin("handle").end()
    assert len(trace.startup()) == trace.STARTUP_SPANS


def test_this_process_kept_the_packages_import():
    """The real record, whatever ran since: the package's import is in
    it, from the first line of ``mxnet_tpu/__init__.py`` to its last."""
    kept = trace.startup()
    (imp,) = [s for s in kept if s["name"] == "import"]
    assert imp["parent"] is None and imp["attrs"]["modules"] > 100
    assert 0 < imp["end_s"] - imp["start_s"] < 600
    assert len(kept) <= trace.STARTUP_SPANS


# -- clock + profiler bridge ------------------------------------------------

def test_shared_clock_and_profiler_mirroring():
    from mxnet_tpu import profiler
    assert trace.clock_us is profiler.now_us
    trace.enable()
    profiler.set_state("run")
    try:
        with trace.span("mirrored", category="test"):
            pass
    finally:
        profiler.set_state("stop")
    ev = trace.spans()[-1]
    mirrored = [e for e in profiler._events if e["name"] == "mirrored"]
    assert mirrored and mirrored[-1]["cat"] == "trace:test"
    # same clock: the mirror carries the very same start timestamp
    assert mirrored[-1]["ts"] == ev["ts"]
    rows = json.loads(profiler.dumps(format="json", reset=True))
    assert any(r["name"] == "mirrored" for r in rows["aggregates"])


# -- propagation: prefetcher thread + worker processes ----------------------

def test_prefetcher_thread_spans_share_the_root_trace():
    trace.enable()
    src = [onp.full((4,), i, dtype="float32") for i in range(4)]
    with trace.span("epoch", category="test") as root:
        pf = mx.pipeline.DevicePrefetcher(iter(src))
        out = list(pf)
    assert len(out) == 4
    h2d = [e for e in trace.spans() if e["name"] == "pipeline.h2d"]
    assert len(h2d) == 4
    main_tid = threading.get_ident()
    for ev in h2d:
        assert ev["args"]["trace_id"] == root.trace_id
        assert ev["args"]["parent_id"] == root.span_id
        assert ev["tid"] != main_tid  # recorded on the prefetch thread


class _TraceDataset:
    """Picklable dataset for spawn-based worker processes."""

    def __init__(self, n=16, dim=8):
        rs = onp.random.RandomState(0)
        self.x = rs.rand(n, dim).astype(onp.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i]


def test_worker_process_spans_survive_the_shm_path():
    """Span ids minted in a DataLoader worker process parent back to the
    consumer's context — perf_counter is system-wide on Linux, so the
    timestamps land on the parent timeline unadjusted."""
    ds = _TraceDataset()
    dl = DataLoader(ds, batch_size=8, num_workers=2, thread_pool=False)
    trace.enable()
    with trace.span("epoch", category="test") as root:
        batches = list(dl)
    assert len(batches) == 2
    wspans = [e for e in trace.spans()
              if e["name"] == "dataloader.worker_batch"]
    assert len(wspans) == 2
    for ev in wspans:
        assert ev["pid"] != os.getpid()  # minted in the worker process
        assert ev["args"]["worker_pid"] == ev["pid"]
        assert ev["args"]["trace_id"] == root.trace_id
        assert ev["args"]["parent_id"] == root.span_id
        assert ev["args"]["samples"] == 8
        assert ev["dur"] >= 0


def test_attach_scopes_a_foreign_context():
    trace.enable()
    root = trace.begin("root")
    with trace.attach(root.context):
        with trace.span("under"):
            pass
    assert trace.current_context() is None
    root.end()
    under = next(e for e in trace.spans() if e["name"] == "under")
    assert under["args"]["parent_id"] == root.span_id


# -- export + CLI -----------------------------------------------------------

def test_export_is_a_loadable_chrome_trace(tmp_path):
    trace.enable()
    with trace.span("outer"):
        with trace.span("inner"):
            pass
    path = trace.export(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}

    cli = _trace_cli()
    events = cli.load(path)
    assert cli.has_parent_child(events, "outer", "inner")
    assert not cli.has_parent_child(events, "inner", "outer")
    assert cli.main(["validate", path, "--expect", "outer",
                     "--expect-child", "outer=inner"]) == 0
    with pytest.raises(SystemExit):
        cli.main(["validate", path, "--expect", "missing.span"])
    with pytest.raises(SystemExit):
        cli.main(["validate", str(tmp_path / "nope.json")])
    assert cli.main(["summary", path]) == 0


# -- ops endpoint -----------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=5) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


def test_http_ops_endpoint_serves_metrics_health_and_trace():
    telemetry.enable()
    telemetry.reset()
    trace.enable()
    with trace.span("served", category="test"):
        pass
    telemetry.inc("trace.dropped_total", 0)  # touch the registry
    srv = telemetry.serve_http(port=0)
    try:
        port = srv.server_address[1]
        status, ctype, body = _get(port, "/metrics")
        assert status == 200
        assert ctype == telemetry.EXPOSITION_CONTENT_TYPE
        assert ctype.startswith("text/plain; version=0.0.4")
        assert "scrape_duration" in body

        status, ctype, body = _get(port, "/healthz")
        health = json.loads(body)
        assert status == 200 and health["pid"] == os.getpid()
        assert health["trace"]["active"] and health["trace"]["recorded"] >= 1

        status, _, body = _get(port, "/trace?last=1")
        got = json.loads(body)
        assert status == 200 and got["dropped"] == 0
        assert [e["name"] for e in got["spans"]] == ["served"]

        with pytest.raises(urllib.error.HTTPError) as e:
            _get(port, "/trace?last=bogus")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(port, "/nope")
        assert e.value.code == 404
        assert telemetry.serve_http(port=0) is srv  # idempotent
    finally:
        telemetry.stop_http()
        telemetry.reset()
        telemetry.disable()


# -- lifecycle instrumentation: serve, train, autotune ----------------------

def _tiny_gpt(**kw):
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
    cfg = dict(vocab_size=97, units=32, hidden_size=64, num_layers=2,
               num_heads=2, max_length=32, dropout=0.0, embed_dropout=0.0)
    cfg.update(kw)
    net = GPTForCausalLM(**cfg)
    net.initialize()
    return net


def _maybe_export(name):
    out = os.environ.get("MXNET_TRACE_E2E_DIR")
    if out:
        trace.export(os.path.join(out, name))


def test_e2e_serve_request_span_tree():
    """Acceptance: one ServeEngine.run() with tracing on yields a
    complete serve.request tree (enqueue -> prefill -> decode_step x N ->
    drain) whose children all carry the root's request id, with zero
    post-warmup compiles and per-phase quantiles in stats()."""
    mx.random.seed(0)
    eng = mx.serve.load(_tiny_gpt(), max_slots=4, buckets="4,8",
                        warmup=True)
    trace.enable(buffer=8192)
    rs = onp.random.RandomState(3)
    reqs = [eng.submit(rs.randint(1, 97, (n,)).tolist(), max_new_tokens=4)
            for n in (3, 5)]
    eng.run()
    assert eng.stats()["post_warmup_compiles"] == 0
    _maybe_export("e2e_serve.json")
    trace.disable()

    evs = trace.spans()
    kids = _children(evs)
    roots = {e["args"]["request"]: e for e in evs
             if e["name"] == "serve.request"}
    assert sorted(roots) == sorted(r.id for r in reqs)
    for req in reqs:
        root = roots[req.id]
        assert root["args"]["trace_id"] == root["args"]["span_id"]
        assert root["args"]["prompt_tokens"] == len(req.prompt)
        assert root["args"]["tokens"] == len(req.generated)
        children = kids.get(root["args"]["span_id"], [])
        names = [c["name"] for c in children]
        assert names.count("serve.enqueue") == 1
        assert names.count("serve.prefill") == 1
        assert names.count("serve.drain") >= 1
        # first token comes out of prefill; the rest need one decode
        # step each (more may record: the slot stays live while its
        # final emits sit in the deferred drain window)
        assert names.count("serve.decode_step") >= len(req.generated) - 1
        for c in children:
            assert c["args"]["request"] == req.id
            assert c["args"]["trace_id"] == root["args"]["trace_id"]

    phases = eng.stats()["phases"]
    for key in ("queue_wait", "prefill", "decode_per_token"):
        q = phases[key]
        assert q is not None and 0 <= q["p50"] <= q["p95"] <= q["p99"]


def test_serve_phase_quantiles_absent_when_untraced():
    # with the always-on reservoir off (serve.phase_sampling=0), no
    # tracer means no phase quantiles — the pre-reservoir contract
    prev = mx.config.set("serve.phase_sampling", 0)
    try:
        mx.random.seed(0)
        eng = mx.serve.load(_tiny_gpt(), max_slots=2, buckets="4,8")
        eng.submit([5, 6, 7], max_new_tokens=3)
        eng.run()
        assert all(v is None for v in eng.stats()["phases"].values())
    finally:
        mx.config.set("serve.phase_sampling", prev)


def _toy_data(n=32, d=8, classes=3, bs=16, seed=0):
    rng = onp.random.RandomState(seed)
    x = rng.randn(n, d).astype("float32")
    w = rng.randn(d, classes).astype("float32")
    y = (x @ w).argmax(-1).astype("float32")
    return [(mx.np.array(x[i:i + bs]), mx.np.array(y[i:i + bs]))
            for i in range(0, n, bs)]


def _make_estimator():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.contrib import estimator as est
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.1})
    return est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         trainer=trainer)


def test_e2e_train_step_span_tree():
    """Acceptance: one traced epoch yields a complete train.step tree
    (data_wait / h2d / dispatch / drain children) per batch, with zero
    RecompileWarning and the sync-free loop intact."""
    telemetry.enable()
    telemetry.reset()
    try:
        e = _make_estimator()
        data = _toy_data()
        e.fit(data, epochs=1)  # warmup: compiles happen untraced
        trace.enable(buffer=8192)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            e.fit(data, epochs=1)
        _maybe_export("e2e_train.json")
        trace.disable()
        recompiles = [w for w in caught
                      if issubclass(w.category, telemetry.RecompileWarning)]
        assert not recompiles, [str(w.message) for w in recompiles]
    finally:
        telemetry.reset()
        telemetry.disable()

    evs = trace.spans()
    kids = _children(evs)
    steps = [ev for ev in evs if ev["name"] == "train.step"]
    # the final iteration (the StopIteration pull) records a stub step
    # with only a data_wait child — full steps carry a dispatch
    full = [ev for ev in steps
            if any(c["name"] == "train.dispatch"
                   for c in kids.get(ev["args"]["span_id"], []))]
    assert len(full) == len(data)
    assert len(steps) == len(data) + 1
    for ev in full:
        children = kids[ev["args"]["span_id"]]
        names = {c["name"] for c in children}
        assert {"train.data_wait", "train.h2d", "train.dispatch",
                "train.drain"} <= names, names
        for c in children:
            assert c["args"]["trace_id"] == ev["args"]["trace_id"]
    assert sorted(ev["args"]["step"] for ev in full) == \
        list(range(1, len(data) + 1))


def _epoch_sync_count(traced):
    e = _make_estimator()
    data = _toy_data()
    e.fit(data, epochs=1)  # warmup so both runs are post-compile
    if traced:
        trace.enable(buffer=8192)
    try:
        with mx.pipeline.sync_guard() as g:
            e.fit(data, epochs=1)
    finally:
        trace.disable()
        trace.clear()
    return g.count


def test_tracing_adds_no_host_syncs():
    assert _epoch_sync_count(traced=True) == _epoch_sync_count(traced=False)


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 8, reason="needs 8 (virtual) devices")
def test_autotune_trial_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import autotune
    from mxnet_tpu.autotune import SearchSpace
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import make_mesh

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    prior = config.get("autotune.cache_dir")
    config.set("autotune.cache_dir", str(tmp_path / "autotune"))
    trace.enable(buffer=8192)
    try:
        mx.random.seed(7)
        net = nn.Dense(6, in_units=4)
        net.initialize()
        rs = onp.random.RandomState(1)
        sample = (rs.randn(16, 4).astype("float32"),
                  rs.randint(0, 6, (16,)).astype("int32"))
        autotune.search(net, loss_fn, "adam", make_mesh({"dp": 1}),
                        (P("dp"), P("dp")), sample,
                        space=SearchSpace(batch_size=16), hbm_budget=None,
                        measure=lambda c: 100.0)
    finally:
        config.set("autotune.cache_dir", prior)
        trace.disable()

    evs = trace.spans()
    root = next(e for e in evs if e["name"] == "autotune.search")
    trials = [e for e in evs if e["name"] == "autotune.trial"]
    assert trials and root["args"]["trials"] == len(trials)
    for t in trials:
        assert t["args"]["parent_id"] == root["args"]["span_id"]
        assert t["args"]["status"] in ("ok", "oom", "error")
        assert "batch_size" in t["args"] and "items_per_s" in t["args"]
