"""What PR 69 added to the benchmark: the reducer that reads the program's
own log of its steps (``trace.steps``) and the three metrics of the window's
step intervals, on hand-made records and through one rehearsed cell. Counts,
names and ratios of made-up clocks: a millisecond is never a chip's here."""

import json
import os
import time

import pytest

from chipbench import harness, run
from chipbench.reducers import program_step_log as steplog
from chipbench.tests.test_chipbench import CELLS, check_line
from chipbench.tests.test_setup_phases import Spans, phase_lines

METRICS = ["slow_step_share.train", "step_interval_max_over_p50.train",
           "trainer_host_ms_max.train"]


def records(interval_s, first_n=10, host_s=0.004, spans=None):
    """Step records whose ``t0`` lie ``interval_s[i]`` apart; one more record
    than intervals."""
    out, t = [], 100.0
    for i, gap in enumerate(list(interval_s) + [0.0]):
        out.append({"kind": "train", "n": first_n + i, "t0": t,
                    "t1": t + host_s, "compiles": 0,
                    "spans": dict(spans or {"train/dispatch": 0.003}),
                    "numbers": {"samples": 8}})
        t += gap
    return out


def test_a_steady_window_reads_zero():
    log = steplog.log_line(records([0.100, 0.101, 0.099, 0.100, 0.1005]))
    assert log["count"] == 6 and log["intervals"] == 5
    assert (log["first_n"], log["last_n"]) == (10, 15)
    assert log["slow_step_share"] == 0.0 and log["slow_steps"] == []
    assert log["interval_ms_p50"] == pytest.approx(100.0)
    assert log["interval_max_over_p50"] == pytest.approx(1.01)
    assert log["host_ms_max"] == pytest.approx(4.0)
    assert log["interval_ms"] == [100.0, 101.0, 99.0, 100.0, 100.5]
    assert log["host_ms"] == [4.0] * 6
    assert log["span_ms_sum"] == {"train/dispatch": 18.0}
    assert log["left_out"] == log["compiled_in"] == log["traced"] == []
    json.dumps(log)


def test_one_stall_reads_k_of_n_and_names_its_step():
    rows = records([0.1] * 4 + [0.3] + [0.1] * 5)
    rows[4]["t1"] = rows[4]["t0"] + 0.25          # it sat inside train_batch
    rows[4]["spans"] = {"train/fetch": 0.24, "train/dispatch": 0.003}
    rows[2]["compiles"] = 1
    log = steplog.log_line(rows)
    assert log["slow_step_share"] == pytest.approx(100.0 * 1 / 10)
    assert log["slow_steps"] == [14]
    assert log["interval_max_over_p50"] == pytest.approx(3.0)
    assert log["host_ms_max"] == pytest.approx(250.0)
    assert log["over_median_ms"] == pytest.approx(200.0)
    assert log["longest"]["n"] == 14
    assert log["longest"]["interval_ms"] == pytest.approx(300.0)
    assert log["longest"]["host_ms"] == pytest.approx(250.0)
    assert log["longest"]["spans_ms"]["train/fetch"] == pytest.approx(240.0)
    assert log["compiled_in"] == [12]


def test_a_window_slow_throughout_reads_zero_beside_a_raised_median():
    log = steplog.log_line(records([0.105] * 9))
    assert log["slow_step_share"] == 0.0
    assert log["interval_max_over_p50"] == pytest.approx(1.0)
    assert log["interval_ms_p50"] == pytest.approx(105.0)
    assert log["over_median_ms"] == pytest.approx(0.0, abs=1e-6)


def test_a_late_wake_up_with_a_step_in_flight_costs_nothing():
    """The untraced window: the caller wakes late, the step in flight hides
    it, the next interval is as much shorter."""
    log = steplog.log_line(records([0.1] * 4 + [0.13, 0.07] + [0.1] * 4))
    assert log["slow_steps"] == [14]
    assert log["over_median_ms"] == pytest.approx(0.0, abs=1e-6)


def table(step_nums, step_ns=100_000_000, device_ns=95_000_000):
    """A trace table with one ``sxt:train`` event and one ``jit_train_step``
    execution a traced step."""
    host = [["cb:window", 0.0, step_ns * (len(step_nums) + 1), 0, {}]]
    modules = []
    for i, n in enumerate(step_nums):
        start = 1000.0 + i * step_ns
        host.append(["sxt:train", start, 4_000_000.0, 5, {"step_num": float(n)}])
        host.append(["sxt:train/dispatch", start + 10, 3_000_000.0, 5, {}])
        modules.append(["jit_convert_element_type", start + 500, 600.0])
        modules.append(["jit_train_step", start + 2_000_000, float(device_ns + i)])
    return {"host": host, "devices": [{"name": "/device:TPU:0", "ops": [],
                                       "modules": modules}]}


def test_the_profilers_two_intervals_are_left_out_by_step_number():
    # the profiler starts in the interval that ends at step 14 and stops in
    # the one that begins at step 16: both read long, neither counts
    rows = records([0.1] * 3 + [0.9] + [0.1] * 2 + [0.5] + [0.1] * 3)
    traced = steplog.traced_steps(table([14, 15, 16]))
    assert [n for n, _ in traced] == [14, 15, 16]
    assert [ms for _, ms in traced] == pytest.approx([95.0] * 3, rel=1e-6)
    log = steplog.log_line(rows, traced)
    assert log["left_out"] == [13, 16]
    assert log["count"] == 11 and log["intervals"] == 8
    assert log["slow_step_share"] == 0.0
    assert log["interval_max_over_p50"] == pytest.approx(1.0)
    assert len(log["interval_ms"]) == 10            # every interval is printed
    assert log["traced"] == [[14, 100.0, 95.0], [15, 100.0, 95.0],
                             [16, 500.0, 95.0]]
    # without the table nothing is left out and both count
    assert steplog.log_line(rows)["slow_step_share"] == pytest.approx(20.0)
    assert steplog.traced_steps(None) == []


def test_the_reducer_cuts_the_log_to_the_window(monkeypatch):
    from shuffle_exchange_tpu.profiling import trace

    rows = records([0.1] * 4 + [0.2] + [0.1] * 3)
    monkeypatch.setattr(
        trace, "steps",
        lambda kind, since=0.0: [r for r in rows if r["t0"] >= since]
        if kind == "train" else [])
    window = [(rows[2]["t0"], rows[2]["t0"] + 0.09),
              (rows[7]["t0"], rows[7]["t0"] + 0.09)]
    ctx = {"cell": {"name": "gpt2m-train", "root": "/nonexistent"},
           "spans": Spans(window)}
    assert steplog.reduce(ctx, "slow_step_share") == pytest.approx(20.0)
    assert steplog.reduce(ctx, "interval_max_over_p50") == pytest.approx(2.0)
    assert steplog.reduce(ctx, "host_ms_max") == pytest.approx(4.0)
    assert ctx[steplog._KEY]["count"] == 6          # steps 12 .. 17
    assert (ctx[steplog._KEY]["first_n"], ctx[steplog._KEY]["last_n"]) == (12, 17)


def test_a_program_without_a_step_log_reports_nothing(monkeypatch):
    """The parent of PR 69: ``trace.step`` is an annotation and keeps nothing."""
    from shuffle_exchange_tpu.profiling import trace

    cell = harness.load_cell("gpt2m-train")
    mine = [m for m in cell["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == METRICS
    assert all(m["moves"] == "train_tokens_per_s_chip"
               and m["layer"] == "trainer (runtime/engine.py)" for m in mine)
    now = time.perf_counter()
    ctx = {"cell": cell, "spans": Spans([(now, now)])}
    monkeypatch.delattr(trace, "steps")
    assert harness.layer_metrics(dict(cell, per_layer=mine), ctx) == {}
    # nor does a run without a window
    monkeypatch.undo()
    assert steplog.reduce({"cell": cell, "spans": Spans([])},
                          "slow_step_share") is None


def test_every_training_cell_lists_the_three_metrics():
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"][-3:]:
        assert m["name"] in METRICS and m["workloads"] == cells
        assert m["source"] == "program_span" and m["better"] == "lower"


def test_a_rehearsed_cell_prints_its_step_log(monkeypatch, capsys):
    monkeypatch.delenv("SXT_FUSED_INTERPRET", raising=False)
    cell = harness.load_cell("gpt2m-train")
    line = run.run_cell("gpt2m-train", 2 ** 31 + 6900, 3.0, True,
                        rehearsal=CELLS["gpt2m-train"]())
    out = check_line(line, cell, True)
    lines = phase_lines(capsys.readouterr().out)
    log, = lines["program_step_log"]
    assert log["cell"] == "gpt2m-train"
    assert log["count"] == out["attempted"]
    assert log["last_n"] - log["first_n"] + 1 == log["count"]
    assert len(log["host_ms"]) == log["count"]
    assert len(log["interval_ms"]) == log["count"] - 1
    assert log["compiled_in"] == []
    for name in ("train/batch", "train/fetch", "train/place",
                 "train/dispatch", "train/post"):
        assert log["span_ms_sum"][name] >= 0.0, name
    # the steps inside the profiler's session, by the number both carry
    traced = [n for n, _, _ in log["traced"]]
    assert traced and traced == list(range(traced[0], traced[0] + len(traced)))
    assert log["first_n"] < traced[0] and traced[-1] <= log["last_n"]
    assert log["left_out"] == [traced[0] - 1, traced[-1]]
    assert log["intervals"] == log["count"] - 1 - (
        2 if traced[-1] < log["last_n"] else 1)
    for name, key in zip(METRICS, ("slow_step_share", "interval_max_over_p50",
                                   "host_ms_max")):
        assert out["metrics"][name]["value"] == pytest.approx(log[key])
    assert out["metrics"]["step_interval_max_over_p50.train"]["value"] >= 1.0
