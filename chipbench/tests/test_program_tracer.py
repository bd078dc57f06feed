"""What PR 26 added to the benchmark: the reducers that read the program's
own tracer (``sxt:`` spans, named scopes through ``program_ops``, compile
events), pinned on traces recorded on the chip with the scoped table kept,
and the serving spans' metrics rehearsed at tiny size through the withheld
chat cell."""

import json
import os
import shutil

import pytest

from chipbench import harness, run, xscope, xtrace
from chipbench.tests.test_chipbench import (DATA, SERVE, check_line,
                                            copy_benchmark, tiny_mistral)

SERVING = ["queue_wait_p95_ms", "sched_admit_ms_per_tick",
           "engine_readback_ms_per_tick"]
RECORDED = {"train_one_step": "gpt2m-train",
            "zero3_x4_one_step": "mistral7b-zero3-x4"}


class OneSpan:
    """The benchmark's own spans, as far as a recorded table needs them."""

    def named(self, name):
        return [(0.0, 1.0)]


def reduce_all(table, workload, capsys):
    """Every metric of ``workload`` whose reducer reads the scoped table, and
    the phase lines they print."""
    cell = harness.load_cell(workload)
    ctx = {"cell": cell, "_xscope": table, "spans": OneSpan()}
    values = {}
    for m in cell["per_layer"]:
        spec = harness.read_json(os.path.join(
            cell["bench_dir"], "layer_metrics", m["name"] + ".json"))
        if spec["reducer"] in ("scope_share", "program_host_ms",
                               "collective_op_share", "idle_by_program_span"):
            reducer = harness.named_module("reducers", spec["reducer"], "test")
            values[m["name"]] = reducer.reduce(ctx, **spec.get("args", {}))
    phases = {}
    for line in capsys.readouterr().out.splitlines():
        row = json.loads(line)
        phases[row["phase"]] = row
    return values, phases


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_reduction_of_a_recorded_scoped_trace_is_stable(name, capsys):
    table = xtrace.load_table(os.path.join(DATA, name + ".scoped.json.gz"))
    want = harness.read_json(os.path.join(DATA, name + ".scoped.expect.json"))
    values, phases = reduce_all(table, RECORDED[name], capsys)
    again, _ = reduce_all(table, RECORDED[name], capsys)
    assert values == again
    assert values.keys() == want["metrics"].keys()
    for key, value in want["metrics"].items():
        assert values[key] == pytest.approx(value, rel=1e-9), key
    shares = [v for k, v in values.items() if k.startswith("scope_share.")]
    assert sum(shares) == pytest.approx(100.0, abs=1e-6)
    # the idle table's rows sum to the traced idle time: window - busy of
    # the first device, as the old reduction has it
    idle = phases["idle_by_program_span"]
    assert sum(s for _, s in idle["rows"]) == pytest.approx(idle["idle_s"],
                                                             rel=1e-9)
    assert idle["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9)
    assert [n for n, _ in idle["rows"]] == want["idle_rows"]
    top = phases["device_time_by_scope"]["rows"]
    assert [[s, op] for s, op, _ in top[:5]] == want["top_scoped_ops"]
    if "collective_op_share" in values:
        rows = phases["collective_ops"]["rows"]
        assert [[s, op] for s, op, _ in rows[:4]] == want["top_collective_ops"]


def test_a_trace_without_program_spans_reports_nothing(tmp_path, capsys):
    """The parent of PR 26 has no tracer: the old recorded tables stand in
    for its trace (``cb:`` host events only, no scope, no ``program_ops``)."""
    old = xtrace.load_table(os.path.join(DATA, "train_one_step.json.gz"))
    table = {"devices": [{"name": p["name"],
                          "ops": [[n, s, d, 0] for n, s, d in
                                  p["lines"][xtrace.OPS_LINE]],
                          "modules": p["lines"].get(xtrace.MODULES_LINE, [])}
                         for p in old["devices"]],
             "scopes": [""], "program_ops": {},
             "host": [[n, s, d, 0, {}] for n, s, d in old["host"]]}
    values, _ = reduce_all(table, "gpt2m-train", capsys)
    assert values["trainer_host_ms_per_step"] is None
    assert values["scope_share.attn"] == 0.0          # no scope anywhere
    assert values["scope_share.none"] == 100.0
    # and ``xscope.table`` gives such a run no table at all
    cell = dict(harness.load_cell("gpt2m-train"), root=str(tmp_path))
    assert xscope.table({"cell": cell}) is None


def test_timeline_cut_by_innermost_span():
    spans = [("batch", 0, 100, 0), ("fetch", 5, 10, 0), ("place", 10, 30, 0),
             ("dispatch", 40, 90, 0), ("batch", 200, 300, 0)]
    assert xscope.innermost_segments(spans) == [
        (0, 5, "batch"), (5, 10, "fetch"), (10, 30, "place"),
        (30, 40, "batch"), (40, 90, "dispatch"), (90, 100, "batch"),
        (200, 300, "batch")]
    path = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/mlp/mul"
    assert xscope.components(path) == ("train_step", "layers", "while", "body",
                                       "closed_call", "mlp", "mul")
    assert xscope.innermost(path, ["layers", "mlp"]) == "mlp"
    assert xscope.innermost("jit(f)/mul", ["mlp"]) == ""
    assert xscope.instruction("%fusion.39 = (bf16[4]) fusion(x)") == "fusion.39"


def test_serving_spans_are_read_at_tiny_size(tmp_path):
    """The three metrics of the program's serving spans wait with the chat
    cell; here they run, traced, through a copy that lists them."""
    root = copy_benchmark(tmp_path)
    added = os.path.join(DATA, "chat_cell")
    entries = harness.read_json(os.path.join(added, "BENCHMARK.add.json"))
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(added, sub),
                        os.path.join(root, "chipbench", sub),
                        dirs_exist_ok=True)
    bench_path = os.path.join(root, "BENCHMARK.json")
    bench = harness.read_json(bench_path)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += entries[key]
    for name in SERVING:
        spec = harness.read_json(os.path.join(added, "layer_metrics",
                                              name + ".json"))
        bench["per_layer"].append(
            {"name": name, "unit": spec["unit"], "better": "lower",
             "source": "program_span", "layer": spec["layer"],
             "moves": spec["moves"], "workloads": ["mistral7b-chat"]})
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    cell = harness.load_cell("mistral7b-chat", root)
    out = check_line(run.run_cell(
        "mistral7b-chat", 2 ** 31 + 999, 3.0, True, root=root,
        rehearsal={"model_cfg": tiny_mistral(), **SERVE}), cell, True)
    assert out["correct"] is True
    for name in SERVING:
        assert out["metrics"][name]["value"] >= 0.0, name
    assert out["metrics"]["sched_admit_ms_per_tick"]["value"] > 0.0
    assert out["metrics"]["engine_readback_ms_per_tick"]["value"] > 0.0
