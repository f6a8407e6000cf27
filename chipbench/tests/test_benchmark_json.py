"""BENCHMARK.json against the contract's own rules, and every name in it
against the file it must resolve to."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_shape_of_the_file():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert b["paths"] == ["chipbench"] and 1 <= b["run_seconds"] <= 51
    # a full check with 24 cells must fit into 43200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith("chipbench/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    names = [w["name"] for w in b["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in b["configs"]}
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)
    assert {c["name"] for c in b["configs"]} \
        == {w["config"] for w in b["workloads"]}


def test_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(b["end_to_end"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells

    def reports(metric):
        return set(e2e[metric].get("workloads", cells))

    for cell in cells:
        assert cell in reports("setup_s")
        assert any(cell in reports(n) for n in e2e if n != "setup_s")
    seen = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert NAME.match(m["name"]) and m["name"] not in seen \
            and m["name"] not in e2e
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", reports(m["moves"]))) \
            <= reports(m["moves"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", m["name"] + ".py"))
    for cell in cells:
        assert any(cell in m.get("workloads", reports(m["moves"]))
                   for m in b["per_layer"])


def test_every_name_resolves_to_its_file():
    import run
    b = bench()
    for w in b["workloads"]:
        entry, cell, cfg, traffic = run.resolve(b, w["name"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", traffic["driver"] + ".py"))
        for kind in ("families", "reference", "flops"):
            assert os.path.exists(os.path.join(
                ROOT, "chipbench", kind, cfg["family"] + ".py"))
        assert "check" in cell and "control" in cell and "tiny" in cell
        run.resolve(b, w["name"], tiny=True, control=True)
