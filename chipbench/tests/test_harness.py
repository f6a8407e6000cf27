"""A rehearsal of every cell end to end on the CPU (tiny stand-ins):
the system against the plain reference, the strict shape of the last
line, and — with the timed path broken underneath, or the
cell's lower-precision control switched on — ``correct`` coming out
false."""
import json

import pytest

E2E_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def last_line(capsys, argv):
    import run
    run.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def check_rows(out):
    return {r["name"]: r for r in (json.loads(l[len("# check "):])
                                   for l in out if l.startswith("# check "))}


CELLS = ["gpt2m-train-8k", "gpt2l-train-dp4"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_the_line_is_strict(cell, capsys):
    bench = json.load(open("BENCHMARK.json"))
    line, out = last_line(capsys, ["--workload", cell, "--tiny", "--seed",
                                   "3000000019", "--seconds", "3"])
    assert line.pop("rehearsal") is True
    assert set(line) == E2E_KEYS
    assert line["correct"] is True, check_rows(out)
    assert set(line["device"]) == DEVICE_KEYS
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line(cell, capsys):
    bench = json.load(open("BENCHMARK.json"))
    line, out = last_line(capsys, ["--workload", cell, "--tiny", "--seed",
                                   "11", "--seconds", "3", "--trace", "1"])
    line.pop("rehearsal")
    assert set(line) == E2E_KEYS | {"breakdown"}
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    known = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or cell in m["workloads"]}
    assert line["metrics"] and set(line["metrics"]) <= known
    assert "compile_s" in line["metrics"]


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    from mxnet_tpu.parallel import ShardedTrainStep
    real = ShardedTrainStep.__call__

    def frozen(self, *batch):
        keep = (self.trainable, self.aux, self.states, self.extra)
        import jax
        keep = jax.tree_util.tree_map(lambda a: a.copy(), keep)
        loss = real(self, *batch)
        self.trainable, self.aux, self.states, self.extra = keep
        return loss

    monkeypatch.setattr(ShardedTrainStep, "__call__", frozen)
    line, out = last_line(capsys, ["--workload", "gpt2m-train-8k", "--tiny",
                                   "--seed", "5", "--seconds", "2"])
    assert line["correct"] is False
    assert check_rows(out)["change_norm_gap_worst_live_leaf"]["holds"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_the_lower_precision_control_is_not_correct(cell, capsys):
    """fp8 (one chip: with its own ``grad_accum``; four: with the int8
    compressed reduce, inside whose ``shard_map`` the fp8 kernel lowers)
    as the cell's file gives it; the chip runs are in PERF.md."""
    line, out = last_line(capsys, ["--workload", cell, "--tiny", "--seed",
                                   "7", "--seconds", "2", "--control"])
    assert line["control"] is True
    assert line["correct"] is False, check_rows(out)
    rows = check_rows(out)
    assert rows["grad_norm_gap_worst_leaf"]["holds"] is False
    assert rows["change_norm_gap_worst_live_leaf"]["holds"] is False


def test_no_tpu_no_result_line(capsys):
    import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "gpt2m-train-8k", "--seed", "1"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out
