"""mx.analyze / tools/mxlint.py — framework-aware static analysis
(docs/STATIC_ANALYSIS.md).

Every rule family gets positive AND negative fixtures (the positive
ones fail if the rule is deleted), plus the machinery tests: inline
waiver parsing, baseline round-trip and multiset semantics, the CLI
--json contract, the telemetry ``analyze`` plane, and the self-check
that the shipped tree is clean against the shipped baseline.
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu import analyze, config, telemetry
from mxnet_tpu.analyze import core

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_as_found():
    """``TrainingTelemetry(...)`` turns telemetry on for the process: put
    it back, or whatever file this worker runs next counts and takes
    gradient norms it did not ask for."""
    was = telemetry.active()
    yield
    telemetry.enable(was)


def _run(tmp_path, tree, paths=None, rules=None):
    """Write a fixture tree and run the suite over it."""
    for rel, src in tree.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src, encoding="utf-8")
    return analyze.run_suite(
        paths=paths or [str(tmp_path / rel) for rel in tree
                        if rel.endswith(".py")],
        root=str(tmp_path), rules=rules)


def _rules(findings):
    return [f.rule for f in findings]


# --- TRC: trace safety ----------------------------------------------------

def test_trc001_host_sync_inside_jit(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item() + 1\n")})
    assert "TRC001" in _rules(bad)
    good = _run(tmp_path, {"b.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    n = x.shape[0]\n"       # static read: no sync
        "    return x * n\n")})
    assert "TRC001" not in _rules(good)


def test_trc002_impure_call_inside_jit(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "import jax\n"
        "import time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x + time.time()\n")})
    assert "TRC002" in _rules(bad)


def test_trc003_python_branch_on_traced_value(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")})
    assert "TRC003" in _rules(bad)
    # static_argnames params are concrete at trace time: branching is fine
    good = _run(tmp_path, {"b.py": (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('mode',))\n"
        "def f(x, mode):\n"
        "    if mode == 'relu':\n"
        "        return x\n"
        "    return -x\n")})
    assert "TRC003" not in _rules(good)


def test_trc004_closure_capture_of_step_varying_value(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "import jax\n"
        "def train(data):\n"
        "    step = 0\n"
        "    out = []\n"
        "    for batch in data:\n"
        "        step += 1\n"
        "        def loss_fn(x):\n"
        "            return x * step\n"
        "        out.append(jax.jit(loss_fn)(batch))\n"
        "    return out\n")})
    assert "TRC004" in _rules(bad)
    good = _run(tmp_path, {"b.py": (
        "import jax\n"
        "SCALE = 2.0\n"
        "def train(data):\n"
        "    def loss_fn(x):\n"
        "        return x * SCALE\n"   # module constant: one trace
        "    return [jax.jit(loss_fn)(b) for b in data]\n")})
    assert "TRC004" not in _rules(good)


def test_trc005_per_batch_sync_in_hot_path(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "class ServeEngine:\n"
        "    def step(self):\n"
        "        return self._last.item()\n")})
    assert "TRC005" in _rules(bad)
    # an emit-interval gate (ancestor `if` computing a modulo) exempts
    good = _run(tmp_path, {"b.py": (
        "class ServeEngine:\n"
        "    def step(self):\n"
        "        if self._n % 10 == 0:\n"
        "            return self._last.item()\n"
        "        return None\n")})
    assert "TRC005" not in _rules(good)


def test_trc005_batch_end_handler(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "class LossLogger(EventHandler):\n"
        "    def batch_end(self, estimator, loss):\n"
        "        self._log(float(loss.item()))\n")})
    assert "TRC005" in _rules(bad)


# --- DON: buffer donation -------------------------------------------------

def test_don001_use_after_donation(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "import jax\n"
        "def _step(s):\n"
        "    return s\n"
        "step_fn = jax.jit(_step, donate_argnums=0)\n"
        "def loop(state):\n"
        "    out = step_fn(state)\n"
        "    return out + state\n")})     # state's buffer is dead here
    assert "DON001" in _rules(bad)
    # the safe idiom: rebind the donated name on the same statement
    good = _run(tmp_path, {"b.py": (
        "import jax\n"
        "def _step(s):\n"
        "    return s\n"
        "step_fn = jax.jit(_step, donate_argnums=0)\n"
        "def loop(state):\n"
        "    state = step_fn(state)\n"
        "    return state\n")})
    assert "DON001" not in _rules(good)


# --- LCK: lock discipline -------------------------------------------------

_LCK_CYCLE = (
    "import threading\n"
    "class Pool:\n"
    "    def __init__(self):\n"
    "        self._a_lock = threading.Lock()\n"
    "        self._b_lock = threading.Lock()\n"
    "    def forward(self):\n"
    "        with self._a_lock:\n"
    "            with self._b_lock:\n"
    "                return 1\n"
    "    def backward(self):\n"
    "        with self._b_lock:\n"
    "            with self._a_lock:\n"
    "                return 2\n")


def test_lck001_lock_order_cycle(tmp_path):
    bad = _run(tmp_path, {"a.py": _LCK_CYCLE})
    assert "LCK001" in _rules(bad)
    good = _run(tmp_path, {"b.py": _LCK_CYCLE.replace(
        "    def backward(self):\n"
        "        with self._b_lock:\n"
        "            with self._a_lock:\n",
        "    def backward(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n")})
    assert "LCK001" not in _rules(good)


def test_lck002_blocking_call_under_lock(tmp_path):
    bad = _run(tmp_path, {"a.py": (
        "import threading\n"
        "import time\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def poke(self):\n"
        "        with self._lock:\n"
        "            time.sleep(0.5)\n")})
    assert "LCK002" in _rules(bad)
    good = _run(tmp_path, {"b.py": (
        "import threading\n"
        "import time\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def poke(self):\n"
        "        with self._lock:\n"
        "            n = 1\n"
        "        time.sleep(0.5)\n"     # sleeps after release: fine
        "        return n\n")})
    assert "LCK002" not in _rules(good)


# --- REG: registry drift --------------------------------------------------

def test_reg001_undeclared_knob_read(tmp_path):
    findings = _run(tmp_path, {
        "mxnet_tpu/config.py":
            "declare('a.b', str, '', 'ENV_AB', 'a documented knob')\n",
        "user.py": (
            "from mxnet_tpu import config\n"
            "config.get('a.b')\n"
            "config.get('missing.knob')\n")})
    hits = [f for f in findings if f.rule == "REG001"]
    assert len(hits) == 1 and "missing.knob" in hits[0].message


def test_reg002_knob_without_doc(tmp_path):
    findings = _run(tmp_path, {"mxnet_tpu/config.py": (
        "declare('doc.ok', str, '', 'ENV_OK', 'documented')\n"
        "declare('doc.missing', str, '', 'ENV_MISS')\n")})
    hits = [f for f in findings if f.rule == "REG002"]
    assert len(hits) == 1 and "doc.missing" in hits[0].message


def test_reg003_undeclared_metric_record(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "declare_metric('ok.total', 'counter', 'declared')\n"
        "telemetry.inc('ok.total')\n"
        "telemetry.inc('nope.total')\n")})
    hits = [f for f in findings if f.rule == "REG003"]
    assert len(hits) == 1 and "nope.total" in hits[0].message


def test_reg004_reg008_fault_point_coverage(tmp_path):
    findings = _run(tmp_path, {
        "mxnet_tpu/fault.py": (
            "POINTS = {\n"
            "    'tested.point': 'covered',\n"
            "    'never.tested': 'not covered',\n"
            "}\n"),
        "tests/test_x.py": "SPEC = 'tested.point:at=2'\n",
        "docs/FAULT_TOLERANCE.md": "| `tested.point` | ... |\n"})
    r4 = [f for f in findings if f.rule == "REG004"]
    r8 = [f for f in findings if f.rule == "REG008"]
    assert len(r4) == 1 and "never.tested" in r4[0].message
    assert len(r8) == 1 and "never.tested" in r8[0].message


def test_reg005_unknown_fault_point_fired(tmp_path):
    findings = _run(tmp_path, {
        "mxnet_tpu/fault.py": "POINTS = {'known.point': 'doc'}\n",
        "tests/test_x.py": "S = 'known.point'\n",
        "docs/FAULT_TOLERANCE.md": "`known.point`\n",
        "user.py": (
            "from mxnet_tpu import fault\n"
            "fault.fire('known.point')\n"
            "fault.fire('unknown.point')\n")})
    hits = [f for f in findings if f.rule == "REG005"]
    assert len(hits) == 1 and "unknown.point" in hits[0].message


def test_reg006_ci_stage_drift(tmp_path):
    findings = _run(tmp_path, {
        "ci/matrix.yaml": (
            "matrix:\n"
            "  - stage: unit\n"
            "    platform: cpu\n"
            "  - stage: ghost\n"
            "    platform: cpu\n"
            "  - stage: nightly\n"
            "    platform: cpu\n"
            "    schedule: nightly\n"),
        "ci/run.sh": (
            'case "$stage" in\n'
            "    unit) unit ;;\n"
            "    extra) extra ;;\n"
            "    nightly) nightly ;;\n"
            "    all) unit ;;\n"
            "esac\n"),
        "m.py": "X = 1\n"})
    msgs = [f.message for f in findings if f.rule == "REG006"]
    assert any("ghost" in m for m in msgs)       # matrix -> no case
    assert any("extra" in m for m in msgs)       # case -> no matrix row
    assert not any("nightly" in m for m in msgs)  # scheduled: exempt


def test_reg007_metric_missing_from_doc(tmp_path):
    findings = _run(tmp_path, {
        "mxnet_tpu/m.py": (
            "declare_metric('doc.metric', 'counter', 'in the doc')\n"
            "declare_metric('ghost.metric', 'counter', 'not in it')\n"),
        "docs/OBSERVABILITY.md": "| `doc.metric` | counter | ... |\n"})
    hits = [f for f in findings if f.rule == "REG007"]
    assert len(hits) == 1 and "ghost.metric" in hits[0].message


# --- waivers --------------------------------------------------------------

def test_waiver_with_reason_suppresses(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('w.one')"
        "  # mxlint: disable=REG003(scratch metric, bench-only)\n")})
    assert _rules(findings) == []


def test_waiver_without_reason_is_its_own_finding(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('w.two')  # mxlint: disable=REG003\n")})
    assert _rules(findings) == ["WVR001"]


def test_waiver_standalone_comment_covers_next_line(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "# mxlint: disable=REG003(scratch)\n"
        "telemetry.inc('w.three')\n")})
    assert _rules(findings) == []


def test_waiver_only_suppresses_named_rule(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('w.four')  # mxlint: disable=TRC001(wrong rule)\n")})
    assert _rules(findings) == ["REG003"]


# --- baseline -------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('b.one')\n"
        "telemetry.inc('b.two')\n")})
    assert sorted(_rules(findings)) == ["REG003", "REG003"]
    bl = tmp_path / "baseline.json"
    core.write_baseline(str(bl), findings)
    new, waived = core.apply_baseline(findings, core.load_baseline(str(bl)))
    assert new == [] and len(waived) == 2
    # a fresh finding is NOT absorbed by the old baseline
    more = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('b.one')\n"
        "telemetry.inc('b.two')\n"
        "telemetry.inc('b.three')\n")})
    new, waived = core.apply_baseline(more, core.load_baseline(str(bl)))
    assert len(new) == 1 and "b.three" in new[0].message
    assert len(waived) == 2


def test_baseline_is_count_based(tmp_path):
    # two identical findings, one baseline entry: one stays new
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "def a():\n"
        "    telemetry.inc('dup.total')\n"
        "def b():\n"
        "    telemetry.inc('dup.total')\n")})
    assert len(findings) == 2
    assert findings[0].key() == findings[1].key()
    new, waived = core.apply_baseline(
        findings, {findings[0].key(): 1})
    assert len(new) == 1 and len(waived) == 1


def test_baseline_survives_line_drift(tmp_path):
    findings = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('drift.total')\n")})
    bl = tmp_path / "baseline.json"
    core.write_baseline(str(bl), findings)
    moved = _run(tmp_path, {"user.py": (
        "from mxnet_tpu import telemetry\n"
        "\n\n\n"
        "telemetry.inc('drift.total')\n")})
    new, waived = core.apply_baseline(moved, core.load_baseline(str(bl)))
    assert new == [] and len(waived) == 1


# --- CLI ------------------------------------------------------------------

_MXLINT = os.path.join(_REPO, "tools", "mxlint.py")


def test_cli_json_contract_and_assert_clean():
    """bench.py contract: the last stdout line is the one JSON doc; the
    shipped tree is clean against the shipped baseline (exit 0)."""
    proc = subprocess.run(
        [sys.executable, _MXLINT, "--baseline",
         os.path.join(_REPO, "ci", "lint_baseline.json"),
         "--assert-clean", "--json"],
        capture_output=True, text=True, cwd=_REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout.strip().rsplit("\n", 1)[-1])
    assert doc["clean"] is True and doc["new"] == []
    assert doc["baselined"] >= 1          # the baseline is not vestigial


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, _MXLINT, "--list-rules"],
        capture_output=True, text=True, cwd=_REPO, timeout=60)
    assert proc.returncode == 0
    for rule in ("TRC001", "DON001", "LCK001", "REG001", "WVR001"):
        assert rule in proc.stdout


def test_cli_rule_filter(tmp_path):
    src = tmp_path / "fix.py"
    src.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    import time\n"
        "    if x > 0:\n"
        "        return x + time.time()\n"
        "    return -x\n", encoding="utf-8")
    proc = subprocess.run(
        [sys.executable, _MXLINT, "--json", "--rule", "TRC003", str(src)],
        capture_output=True, text=True, cwd=_REPO, timeout=60)
    doc = json.loads(proc.stdout.strip().rsplit("\n", 1)[-1])
    assert set(doc["rule_counts"]) == {"TRC003"}


# --- the suite applied to itself ------------------------------------------

def test_shipped_tree_is_clean_against_shipped_baseline():
    """The acceptance gate the CI lint stage enforces, as a unit test:
    zero NEW findings over the whole shipped tree."""
    findings = analyze.run_suite(root=_REPO)
    baseline = core.load_baseline(
        os.path.join(_REPO, "ci", "lint_baseline.json"))
    new, _ = core.apply_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)


# --- telemetry plane ------------------------------------------------------

def test_run_report_carries_analyze_plane(tmp_path):
    src = tmp_path / "m.py"
    src.write_text(
        "from mxnet_tpu import telemetry\n"
        "telemetry.inc('plane.total')\n", encoding="utf-8")
    analyze.run_suite(paths=[str(src)], root=str(tmp_path))
    rep = telemetry.TrainingTelemetry(run_id="lint-plane").report()
    assert rep["analyze"]["total"] == 1
    assert rep["analyze"]["rules"] == {"REG003": 1}


def test_run_report_reads_saved_mxlint_json(tmp_path, monkeypatch):
    monkeypatch.setattr(core, "_last_summary", None)
    out = tmp_path / "lint.json"
    out.write_text(json.dumps(
        {"new": [], "baselined": 5,
         "rule_counts": {"REG003": 2}, "total_new": 2, "clean": False}),
        encoding="utf-8")
    prev = config.set("analyze.report_path", str(out))
    try:
        rep = telemetry.TrainingTelemetry(run_id="lint-file").report()
    finally:
        config.set("analyze.report_path", prev)
    assert rep["analyze"] == {"total": 2, "rules": {"REG003": 2}}
