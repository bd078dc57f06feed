"""What PR 52 added to the benchmark: the two reducers that read the
program's start-up log (``trace.phases``) and its compile records, and the
five metrics that move ``setup_s``, rehearsed on the CPU through one cell.
Counts, names and containment: a second is never a chip's here."""

import json
import time

import pytest

from chipbench import harness, run
from chipbench.reducers import program_compile_stat, program_phase_s
from chipbench.tests.test_chipbench import CELLS, check_line

SETUP = ["setup_init_s", "setup_step_build_s", "setup_trace_lower_s",
         "setup_backend_compile_s", "setup_cache_miss_programs"]
IN_ENGINE = ["init/rest", "init/shardings", "init/params", "init/optimizer",
             "init/rest", "init/programs"]


def phase_lines(text):
    out = {}
    for line in text.splitlines():
        if line.startswith("{"):
            row = json.loads(line)
            if "phase" in row:
                out.setdefault(row["phase"], []).append(row)
    return out


@pytest.fixture
def rehearsed(monkeypatch, capsys):
    """One traced rehearsal of ``gpt2m-train`` whose process starts now: the
    start-up log holds this run's engine alone (the package's import is an
    earlier test's, or the collection's)."""
    from shuffle_exchange_tpu.profiling import trace

    monkeypatch.delenv("SXT_FUSED_INTERPRET", raising=False)
    monkeypatch.setattr(run, "_PROCESS_START", time.perf_counter())
    cell = harness.load_cell("gpt2m-train")
    line = run.run_cell("gpt2m-train", 2 ** 31 + 12345, 3.0, True,
                        rehearsal=CELLS["gpt2m-train"]())
    return (check_line(line, cell, True), phase_lines(capsys.readouterr().out),
            trace.phases(since=run._PROCESS_START))


def test_a_rehearsed_cell_reports_the_five_setup_metrics(rehearsed):
    out, lines, _ = rehearsed
    assert out["correct"] is True
    for name in SETUP:
        assert out["metrics"][name]["value"] >= 0.0, name
        assert out["metrics"][name]["unit"] == (
            "count" if name == "setup_cache_miss_programs" else "s")
    table, = lines["setup_by_program_phase"]
    rows = table["rows"]
    names = [r["phase"] for r in rows]
    assert names == (["init/config", "init/engine"] + IN_ENGINE + [
        "train/lower", "train/compile", "train/register", "train/first_step",
        program_phase_s.OUTSIDE])
    assert [r["parent"] for r in rows[2:8]] == ["init/engine"] * 6
    by = {r["phase"]: r for r in rows}
    # the metrics are the table's rows, and the records' columns
    assert out["metrics"]["setup_init_s"]["value"] == pytest.approx(
        by["init/config"]["seconds"] + by["init/engine"]["seconds"])
    assert out["metrics"]["setup_step_build_s"]["value"] == pytest.approx(
        sum(by[n]["seconds"] for n in ("train/lower", "train/compile",
                                       "train/first_step")))
    assert out["metrics"]["setup_trace_lower_s"]["value"] == pytest.approx(
        sum(r["trace_s"] + r["lower_s"] for r in rows))
    assert out["metrics"]["setup_backend_compile_s"]["value"] == pytest.approx(
        sum(r["backend_s"] for r in rows))
    assert out["metrics"]["setup_cache_miss_programs"]["value"] == sum(
        r["cache_misses"] for r in rows)
    # where each part of the step's program was paid
    assert by["train/lower"]["trace_s"] > 0 and by["train/lower"]["lower_s"] > 0
    assert by["train/lower"]["programs"] <= 1        # an eager constant, or none
    assert by["train/compile"]["programs"] == 1
    assert by["train/compile"]["backend_s"] > 0
    assert by["init/params"]["programs"] >= 1         # the jitted init
    # every program the cache did not serve is named
    assert len(table["missed"]) == sum(r["cache_misses"] for r in rows)
    assert all(row in by and name.startswith("jit(") and seconds > 0
               for row, name, seconds in table["missed"])
    # the reference's programs are the benchmark's own
    assert by[program_phase_s.OUTSIDE]["programs"] >= 1
    assert by[program_phase_s.OUTSIDE]["seconds"] is None


def test_the_rows_lie_before_the_window(rehearsed):
    _, lines, phases = rehearsed
    table, = lines["setup_by_program_phase"]
    top = [r for r in table["rows"] if r["parent"] is None and r["seconds"]]
    assert table["in_phases_s"] == pytest.approx(sum(r["seconds"] for r in top))
    assert 0 < table["in_phases_s"] <= table["before_window_s"]
    # what the records took was spent inside that time too, on one thread
    spent = sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                for r in table["rows"])
    assert 0 < spent <= table["before_window_s"]
    # a first step is the last phase before the window, and the only one
    firsts = [r for r in phases if r["name"] == "train/first_step"]
    assert len(firsts) == 1
    assert firsts[0]["t1"] - run._PROCESS_START <= table["before_window_s"]


class Spans:
    def __init__(self, rows):
        self.rows = rows

    def named(self, name):
        return self.rows if name == "train_step" else []


def test_a_program_without_phases_reports_nothing(monkeypatch):
    """The parent of PR 52: a tracer with compile events and no start-up log."""
    from shuffle_exchange_tpu.profiling import trace

    ctx = {"cell": {"name": "gpt2m-train"},
           "spans": Spans([(time.perf_counter(), time.perf_counter())])}
    assert program_phase_s.reduce(ctx, ["init/engine"]) is not None
    monkeypatch.delattr(trace, "phases")
    cell = harness.load_cell("gpt2m-train")
    ctx["cell"] = cell
    setup = [m for m in cell["per_layer"] if m["name"] in SETUP]
    assert [m["name"] for m in setup] == SETUP
    assert all(m["moves"] == "setup_s" for m in setup)
    assert harness.layer_metrics(dict(cell, per_layer=setup), ctx) == {}


def test_a_run_without_a_window_reports_nothing():
    ctx = {"cell": {"name": "gpt2m-train"}, "spans": Spans([])}
    assert program_phase_s.reduce(ctx, ["init/engine"], table=True) is None
    assert program_compile_stat.reduce(ctx, "seconds") is None


def test_records_go_to_the_innermost_phase_that_holds_them():
    rows = [{"name": "init/engine", "parent": None, "t0": 1.0, "t1": 9.0},
            {"name": "init/params", "parent": "init/engine", "t0": 2.0, "t1": 5.0}]

    def record(at, span, **kw):
        return {"span": span, "at": at, "trace_s": 0.5, "lower_s": 0.25,
                "seconds": 1.0, "compiled": True, "cache_hit": True, **kw}

    table, missed = program_phase_s.phase_table(rows, [
        record(3.0, "init/params"),
        record(4.0, "init/params", cache_hit=False, fun_name="jit(init_master)"),
        record(8.0, "init/engine", compiled=False, seconds=0.0),
        record(20.0, None), record(21.0, "train/dispatch")])
    assert missed == [["init/params", "jit(init_master)", 1.0]]
    by = {r["phase"]: r for r in table}
    assert list(by) == ["init/engine", "init/params",
                        program_phase_s.OUTSIDE, "train/dispatch"]
    assert by["init/params"] == {
        "phase": "init/params", "parent": "init/engine", "seconds": 3.0,
        "trace_s": 1.0, "lower_s": 0.5, "backend_s": 2.0, "programs": 2,
        "cache_misses": 1}
    assert (by["init/engine"]["programs"], by["init/engine"]["trace_s"]) == (0, 0.5)
    assert by[program_phase_s.OUTSIDE]["programs"] == 1
    assert by["train/dispatch"]["seconds"] is None
    # every record once
    assert sum(r["trace_s"] for r in table) == 2.5
