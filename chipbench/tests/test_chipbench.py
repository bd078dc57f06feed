"""The harness without the chip: the drivers at ``tiny()`` size through
``run_cell``'s rehearsal argument (the serving driver through the withheld
``mistral7b-chat`` cell of ``data/chat_cell``, which also shows that a new cell
is added files), the last line's keys, the refusal to run without a TPU, the
errors for a wrong driver or an unknown device, and the trace reduction on a
recorded trace."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, run, xtrace

ROOT = harness.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAST_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def tiny_gpt():
    from shuffle_exchange_tpu.models.transformer import tiny

    return tiny()


def tiny_mistral():
    from shuffle_exchange_tpu.models.transformer import tiny

    return tiny(vocab=256, d=64, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)


# a short ladder: two table widths, one chunk length
SERVE = {"inference": {"dtype": "float32", "max_seq_len": 128,
                       "kv_block_size": 64, "num_kv_blocks": 17,
                       "serving": {"token_budget": 32, "max_running": 4,
                                   "chunk_bins": [32]}},
         "traffic": {"rate": 3.0, "max_prefills": 2, "gap_tol_sigma": 0.0,
                     "trace_seconds": 1.0,
                     "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 80},
                     "answer": {"median": 8, "sigma": 0.5, "min": 2, "max": 24}}}

CELLS = {
    "gpt2m-train": lambda: {"model_cfg": tiny_gpt(),
                            "traffic": {"seq": 32, "batch_per_chip": 4}},
    "mistral7b-zero3-x4": lambda: {"model_cfg": tiny_mistral(),
                                   "traffic": {"seq": 64, "batch_per_chip": 2}},
}


def check_line(line, cell, trace):
    out = json.loads(line)
    assert set(out) == LAST_KEYS | ({"breakdown"} if trace else set())
    assert out["device"]["platform"] == "cpu"       # named for what it is
    want = cell["per_layer"] if trace else cell["end_to_end"]
    names = {m["name"] for m in want}
    assert set(out["metrics"]) <= names
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
    else:
        assert "setup_s" in out["metrics"]
        assert set(out["metrics"]) == names
    return out


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_at_tiny_size(workload, trace, monkeypatch):
    # the XLA decode route: the fused kernels' interpreted rehearsal is
    # tests/test_chip_smoke.py's, and the whole ladder of them overruns
    # the CPU compiler
    monkeypatch.delenv("SXT_FUSED_INTERPRET", raising=False)
    cell = harness.load_cell(workload)
    out = check_line(run.run_cell(workload, 2 ** 31 + 12345, 3.0, trace,
                                  rehearsal=CELLS[workload]()), cell, trace)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is True
    assert out["device"]["count"] == cell["chips"]


def test_run_py_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "gpt2m-train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert '"correct"' not in p.stdout


def copy_benchmark(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def test_unknown_driver_and_unknown_device_are_errors(tmp_path):
    root = copy_benchmark(tmp_path)
    path = os.path.join(root, "chipbench", "traffic", "pretrain-s1024.json")
    spec = harness.read_json(path)
    spec["driver"] = "no_such_driver"
    with open(path, "w") as f:
        json.dump(spec, f)
    with pytest.raises(harness.BenchError, match="no_such_driver"):
        run.run_cell("gpt2m-train", 1, 1.0, False, root=root,
                     rehearsal=CELLS["gpt2m-train"]())
    with pytest.raises(harness.BenchError, match="not in chipbench/peaks"):
        harness.chip_peaks("TPU v9 imaginary")
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell("no-such-cell")


def digest(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base or os.sep + ".cache" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_is_added_files_only(tmp_path):
    """The withheld serving cell arrives as a configuration, a traffic mix,
    six per-layer metrics and new entries of BENCHMARK.json; no file that
    was there is edited. Then it runs, untraced and traced, at tiny size."""
    root = copy_benchmark(tmp_path)
    bench_path = os.path.join(root, "BENCHMARK.json")
    before = digest(root)
    added = os.path.join(DATA, "chat_cell")
    entries = harness.read_json(os.path.join(added, "BENCHMARK.add.json"))
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(added, sub),
                        os.path.join(root, "chipbench", sub),
                        dirs_exist_ok=True)
    bench = harness.read_json(bench_path)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += entries[key]
    for m in bench["end_to_end"]:
        m.setdefault("bound", 0.1)
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    after = digest(root)
    assert {k for k in before if before[k] != after.get(k)} == {"BENCHMARK.json"}
    new = {os.path.relpath(os.path.join(base, name), added)
           for base, _, files in os.walk(added) for name in files}
    assert {k[len("chipbench/"):] for k in set(after) - set(before)} == \
        new - {"BENCHMARK.add.json"}

    cell = harness.load_cell("mistral7b-chat", root)
    assert len(cell["per_layer"]) == len(entries["per_layer"])
    rehearsal = {"model_cfg": tiny_mistral(), **SERVE}
    for trace in (False, True):
        out = check_line(run.run_cell("mistral7b-chat", 2 ** 31 + 12345, 3.0,
                                      trace, root=root, rehearsal=rehearsal),
                         cell, trace)
        assert out["attempted"] > 0 and out["failed"] == 0
        assert out["correct"] is True
    assert digest(root) == after          # a run edits no file either


# ---------------------------------------------------------------------------
# The trace reduction, on traces recorded on the chip (PR 25)
# ---------------------------------------------------------------------------


def test_interval_arithmetic():
    assert xtrace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xtrace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert xtrace.length([(0, 3), (5, 6)]) == 4
    # a while spanning two ops keeps only its own time
    rows = [("while", 0, 10), ("a", 1, 4), ("b", 5, 9), ("c", 12, 13)]
    assert dict(xtrace.self_times(rows)) == {"while": 3, "a": 3, "b": 4, "c": 1}
    assert xtrace.short_name("%fusion.399 = (bf16[4]) fusion(x)") == "fusion"


@pytest.mark.parametrize("name", ["train_one_step", "zero3_x4_one_step"])
def test_reduction_of_a_recorded_trace_is_stable(name):
    path = os.path.join(DATA, name + ".json.gz")
    want = harness.read_json(os.path.join(DATA, name + ".expect.json"))
    table = xtrace.load_table(path)
    first, second = xtrace.summarize_table(table), xtrace.summarize_table(table)
    assert first == second
    assert first["devices"] == want["devices"]
    for key in ("window_s", "busy_s", "collective_sync_s"):
        assert first[key] == pytest.approx(want[key], rel=1e-9), key
    assert [n for n, _ in first["breakdown"]["device_ops"]] == want["top_ops"]
    assert first["idle_by_span"].keys() == set(want["idle_spans"])
    idle = 100.0 * (1.0 - first["busy_s"] / first["window_s"])
    assert idle == pytest.approx(want["idle_share_pct"], rel=1e-9)
