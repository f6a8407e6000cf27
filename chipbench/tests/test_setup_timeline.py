"""The six pieces of ``setup_s`` against a recorded run
(``data/setup_timeline.json``: ``mx.trace.startup()`` and
``_compile_cache.report()`` of a warm ``--tiny`` run of
``gpt2m-train-8k`` in a fresh process, with ``run.py``'s ``T_PROCESS`` and
the window's open)."""
import json
import os

import pytest

import setup_timeline
from setup_timeline import PIECES

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "setup_timeline.json")) as f:
    REC = json.load(f)


def reader(name):
    import run
    return run.load_module("layer_metrics", name)


def obs_of(t_process=REC["t_process"]):
    """``obs`` as ``run.py`` hands it to a reader, for the recording."""
    return {"ctx": {"t_process": t_process},
            "window": (REC["window_open"], REC["window_open"] + 1.0),
            "startup_record": REC["startup"], "compile_report": REC["report"]}


def span_named(line, name, nth=0):
    return [s for s in line["spans"] if s["name"] == name][nth]


def test_the_six_pieces_sum_to_setup_s_to_the_microsecond():
    pieces, stamps, _ = setup_timeline.cut(
        REC["t_process"], REC["window_open"], REC["startup"])
    assert list(pieces) == list(PIECES)
    assert all(v > 0 for v in pieces.values())
    assert stamps == sorted(stamps)
    assert abs(sum(pieces.values()) - REC["setup_s"]) < 1e-6
    assert REC["setup_s"] == REC["window_open"] - REC["t_process"]


@pytest.mark.parametrize("name", PIECES)
def test_each_reader_reads_its_piece(name, capsys):
    pieces, _, _ = setup_timeline.cut(
        REC["t_process"], REC["window_open"], REC["startup"])
    obs = obs_of()
    assert reader(name).read(obs) == pieces[name]
    # the line is printed once a run, whichever reader comes first
    for other in PIECES:
        reader(other).read(obs)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1 and out[0].startswith("# setup {")
    assert json.loads(out[0][len("# setup "):])["pieces"][name] \
        == pieces[name]


IMPORT = next(s for s in REC["startup"] if s["name"] == "import")
INIT = next(s for s in REC["startup"] if s["name"] == "train.init")


@pytest.mark.parametrize("t_process,none", [
    # the clock started inside the package's import, after it, and after
    # the step was built: a piece that opens before it reads None, not 0
    ((IMPORT["start_s"] + IMPORT["end_s"]) / 2, PIECES[:2]),
    (IMPORT["end_s"] + 0.001, PIECES[:3]),
    (INIT["end_s"] + 0.001, PIECES[:5]),
])
def test_a_record_that_starts_before_t_process_reads_none(t_process, none):
    obs = obs_of(t_process)
    got = {name: reader(name).read(obs) for name in PIECES}
    assert [n for n, v in got.items() if v is None] == list(none)
    assert all(v > 0 for v in got.values() if v is not None)


@pytest.mark.parametrize("startup", [
    [],                                                    # nothing kept
    [s for s in REC["startup"] if s["name"] != "import"],  # no import span
])
def test_a_record_without_the_import_span_reads_none(startup):
    pieces, _, spans = setup_timeline.cut(
        REC["t_process"], REC["window_open"], startup)
    assert set(pieces.values()) == {None} and spans == []


def test_without_a_step_the_first_two_pieces_still_read():
    startup = [dict(s, parent=None) for s in REC["startup"]
               if not s["name"].startswith("train.")]
    pieces, _, _ = setup_timeline.cut(
        REC["t_process"], REC["window_open"], startup)
    assert [n for n, v in pieces.items() if v is not None] == list(PIECES[:2])


@pytest.mark.parametrize("name", PIECES)
def test_a_program_without_a_startup_record_reads_none(
        name, monkeypatch, capsys):
    """The parent of the PR that added ``mx.trace.startup``."""
    from mxnet_tpu import trace
    monkeypatch.delattr(trace, "startup")
    assert reader(name).read(obs_of()) is None
    assert capsys.readouterr().out == ""


def test_the_line_names_the_span_that_holds_the_step_program():
    line = setup_timeline.line(REC["t_process"], REC["window_open"],
                               REC["startup"], REC["report"])
    step = next(r for r in REC["report"] if r["fun_name"] == "jit(base_step)")
    holders = [s["name"] for s in line["spans"] if "programs" in s
               and s["at"] < step["at"] - REC["t_process"] <= s["at"] + s["s"]]
    assert holders == ["train.call", "train.dispatch"]
    dispatch = span_named(line, "train.dispatch")
    assert dispatch["programs"][0] == 1
    assert dispatch["programs"][1] == pytest.approx(
        step["trace_s"] + step["lower_s"] + step["backend_s"], abs=1e-6)
    # later dispatches made nothing ready
    assert "programs" not in span_named(line, "train.dispatch", 1)
    assert "programs" not in span_named(line, "train.dispatch", 2)


def test_the_line_accounts_for_every_program_and_every_second():
    line = setup_timeline.line(REC["t_process"], REC["window_open"],
                               REC["startup"], REC["report"])
    before = [r for r in REC["report"] if r["at"] <= REC["window_open"]]
    assert sum(n for n, _ in line["programs"].values()) == len(before)
    assert sum(s for _, s in line["programs"].values()) == pytest.approx(
        sum(r["trace_s"] + r["lower_s"] + r["backend_s"] for r in before),
        abs=1e-4)
    # nothing is made ready before the package is imported
    assert line["programs"]["pre_import_s.setup"] == [0, 0]
    # only set-up's spans, clipped to it; the record went on into the window
    assert line["kept_after_open"] > 0
    assert all(0 <= s["at"] and s["at"] + s["s"] <= REC["setup_s"] + 1e-6
               for s in line["spans"])
    # self time: a span's duration less what its children cover
    init = span_named(line, "train.init")
    parts = [span_named(line, n)
             for n in ("train.plan", "train.place", "train.states")]
    assert init["self_s"] == pytest.approx(
        init["s"] - sum(p["s"] for p in parts), abs=3e-6)
    assert 0 <= init["self_s"] < init["s"]
    assert span_named(line, "import")["counts"]["modules"] > 100
    assert span_named(line, "train.place")["counts"]["leaves"] > 0
    assert span_named(line, "train.states")["counts"]["bytes"] \
        == 2 * span_named(line, "train.place")["counts"]["bytes"]


def test_benchmark_json_lists_the_six_for_every_cell():
    import run
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m["name"] in PIECES]
    assert [m["name"] for m in mine] == list(PIECES)
    for m in mine:
        assert m["moves"] == "setup_s" and m["unit"] == "s"
        assert m["source"] == "program_span" and "workloads" not in m
        assert os.path.exists(os.path.join(
            run.HERE, "layer_metrics", m["name"] + ".py"))
