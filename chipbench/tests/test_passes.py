"""What PR 37 added to the benchmark: the three reducers that read the pass an
op ran in (``trace.phase_of``), the device's own duration of a step (the ``XLA
Modules`` line) and the compiler's sizing of it (``trace.registered_memory``),
pinned on the two scoped tables recorded on the chip (PR 26), held to a parent
without them, and rehearsed at tiny size through ``run_cell``."""

import json
import os

import pytest

from chipbench import harness, run, xtrace
from chipbench.tests.test_chipbench import CELLS, DATA, check_line

RECORDED = {"train_one_step": "gpt2m-train",
            "zero3_x4_one_step": "mistral7b-zero3-x4"}
READERS = ("pass_share", "module_ms", "program_memory")
PASSES = ["pass_share." + p for p in
          ("forward", "recompute", "backward", "update", "other")]


def reduce_new(table, workload, capsys):
    """Every metric of ``workload`` that one of the three reducers reads,
    and the phase lines they print."""
    cell = harness.load_cell(workload)
    ctx = {"cell": cell, "_xscope": table}
    values = {}
    for m in cell["per_layer"]:
        spec = harness.read_json(os.path.join(
            cell["bench_dir"], "layer_metrics", m["name"] + ".json"))
        if spec["reducer"] in READERS:
            reducer = harness.named_module("reducers", spec["reducer"], "test")
            values[m["name"]] = reducer.reduce(ctx, **spec.get("args", {}))
    phases = {}
    for line in capsys.readouterr().out.splitlines():
        row = json.loads(line)
        phases[row["phase"]] = row
    return values, phases


@pytest.fixture
def nothing_registered(monkeypatch):
    from shuffle_exchange_tpu.profiling import trace

    monkeypatch.setattr(trace, "_programs", {})
    return trace


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_passes_of_a_recorded_scoped_trace(name, capsys, nothing_registered):
    table = xtrace.load_table(os.path.join(DATA, name + ".scoped.json.gz"))
    want = harness.read_json(os.path.join(DATA, name + ".passes.expect.json"))
    old = harness.read_json(os.path.join(DATA, name + ".scoped.expect.json"))
    values, phases = reduce_new(table, RECORDED[name], capsys)
    assert set(values) == set(PASSES) | {
        "recompute_ms_per_step", "device_step_ms_p50", "train_step_peak_gb"}
    assert values.pop("train_step_peak_gb") is None     # nothing registered
    assert values.keys() == want["metrics"].keys()
    for key, value in want["metrics"].items():
        assert values[key] == pytest.approx(value, rel=1e-9), key
    assert sum(values[p] for p in PASSES) == pytest.approx(100.0, abs=0.01)
    # two readers of the same ops
    assert values["pass_share.update"] == pytest.approx(
        old["metrics"]["scope_share.optimizer"], abs=1e-9)
    by = phases["device_time_by_scope_and_pass"]
    assert by["steps"] == 1
    assert sum(s for *_, s in by["rows"]) == pytest.approx(by["op_self_s"],
                                                          rel=1e-9)
    assert [[scope, p] for scope, p, _ in by["rows"][:5]] == want["top_rows"]
    assert {p for _, p, _ in by["rows"]} == set(want["passes_seen"])
    # a scope's rows sum to the scope: the replay's share, by scope
    replay = sum(s for _, p, s in by["rows"] if p == "recompute")
    assert 1e3 * replay == pytest.approx(values["recompute_ms_per_step"])
    assert 100.0 * replay / by["op_self_s"] == pytest.approx(
        values["pass_share.recompute"])
    # the step on the device, inside the host's span around it
    (_, s, d, *_), = [h for h in table["host"] if h[0] == "cb:train_step"]
    assert values["device_step_ms_p50"] <= d * 1e-6
    assert values["device_step_ms_p50"] == pytest.approx(
        want["module_ms"], rel=1e-12)
    assert "program_memory" not in phases


def test_a_parent_without_phase_of_reports_nothing(monkeypatch, capsys):
    """The parent of PR 37 has the tracer and no ``phase_of`` /
    ``registered_memory``: the metrics are left out, nothing raises."""
    from shuffle_exchange_tpu.profiling import trace

    monkeypatch.delattr(trace, "phase_of")
    monkeypatch.delattr(trace, "registered_memory")
    table = xtrace.load_table(os.path.join(DATA, "train_one_step.scoped.json.gz"))
    values, phases = reduce_new(table, "gpt2m-train", capsys)
    for name in PASSES + ["recompute_ms_per_step", "train_step_peak_gb"]:
        assert values[name] is None, name
    # the modules line needs nothing of the program but its tracer
    assert values["device_step_ms_p50"] == pytest.approx(195.796726)
    assert not phases


def test_a_run_without_a_trace_reports_nothing(tmp_path, capsys):
    cell = dict(harness.load_cell("gpt2m-train"), root=str(tmp_path))
    for reducer, args in (("pass_share", {"phase": "recompute"}),
                          ("module_ms", {"program": "train_step"})):
        module = harness.named_module("reducers", reducer, "test")
        assert module.reduce({"cell": cell}, **args) is None
    assert capsys.readouterr().out == ""


class _Compiled:
    """An executable as ``register_program`` sees it."""

    def as_text(self):
        return "HloModule m\n"

    def memory_analysis(self):
        class Sizes:
            argument_size_in_bytes = 6911811584
            output_size_in_bytes = 6911753728
            alias_size_in_bytes = 6911745024
            temp_size_in_bytes = 7515780096
            generated_code_size_in_bytes = 101641728
            peak_memory_in_bytes = 12161965568

        return Sizes()


@pytest.mark.parametrize("field, want", [("peak", 12.161965568),
                                         ("temp", 7.515780096)])
def test_program_memory_reads_what_the_program_registered(
        field, want, nothing_registered, capsys):
    """The sizes are ``kanana2-train``'s (AOT compile for a described v5e,
    PR 37): GB are 1e9 bytes, to the byte."""
    from chipbench.reducers import program_memory

    ctx = {"cell": {"name": "kanana2-train"}}
    assert program_memory.reduce(ctx, "train_step", field) is None
    nothing_registered.register_program("train_step", _Compiled())
    assert program_memory.reduce(ctx, "train_step", field) == want
    assert program_memory.reduce(ctx, "eval_step", field) is None
    line, = capsys.readouterr().out.splitlines()
    row = json.loads(line)
    assert row["phase"] == "program_memory" and row["peak"] == 12161965568
    assert {"argument", "output", "alias", "temp", "generated_code",
            "peak"} <= set(row)


def test_the_new_metrics_at_tiny_size(capsys, nothing_registered):
    """Through ``run_cell``'s rehearsal, traced: the CPU's trace has no
    device plane, so the trace's readers leave their metrics out (and do not
    raise); the compiler's sizing is the program's own and is there."""
    cell = harness.load_cell("gpt2m-train")
    out = check_line(run.run_cell("gpt2m-train", 2 ** 31 + 37, 3.0, True,
                                  rehearsal=CELLS["gpt2m-train"]()), cell, True)
    assert out["correct"] is True
    sizes = nothing_registered.registered_memory("train_step")
    assert out["metrics"]["train_step_peak_gb"] == {
        "value": sizes["peak"] / 1e9, "unit": "GB"}
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    memory, = [r for r in rows if r["phase"] == "program_memory"]
    assert {k: memory[k] for k in sizes} == sizes
    for name in PASSES + ["recompute_ms_per_step", "device_step_ms_p50"]:
        assert name not in out["metrics"]
