#!/usr/bin/env python
"""One cell of the benchmark, one run, one process.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` (configuration, traffic file, metrics),
fails as its first act with JAX unless the machine holds exactly the cell's
TPU chips (there is no CPU branch and no size option), loads, warms up,
measures for ``--seconds`` and checks the result against the plain reference.
Earlier lines are free-form phase lines; the last line of standard output is
the one JSON object ``correct / attempted / failed / metrics / device`` (and,
traced, ``breakdown``). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics.

``tests/`` rehearses ``run_cell`` at ``tiny()`` size on the CPU through its
``rehearsal`` argument; the command line has no such option.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time

_PROCESS_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, rehearsal=None) -> str:
    """Run one cell and return its last line. ``rehearsal`` (tests only)
    swaps in a tiny model configuration and traffic overrides and lifts the
    TPU requirement; the line then names the CPU it ran on."""
    cell = harness.load_cell(workload, root)          # no JAX before this
    driver = harness.load_driver(cell["traffic"].get("driver", ""))

    import jax

    if rehearsal is None:
        devices = harness.require_chips(cell["chips"])
    else:
        devices = jax.devices()[:cell["chips"]]
        if len(devices) < cell["chips"]:
            raise harness.BenchError(f"rehearsal needs {cell['chips']} devices")
        if cell["chips"] == 1:
            jax_devices = jax.devices
            jax.devices = lambda *a, **k: devices   # the program's mesh
    peaks = harness.chip_peaks(
        devices[0].device_kind if rehearsal is None
        else rehearsal.get("device_kind", "TPU v5 lite"), cell["bench_dir"])

    # a rehearsal keeps JAX's threshold: the CPU backend cannot serialise
    # interpreted kernels
    cache_dir = harness.cache_programs(every=rehearsal is None)
    trace_dir = os.path.join(root, ".cache", "chipbench_trace", workload)
    harness.emit(phase="start", cell=workload, chips=cell["chips"],
                 seed=seed, seconds=seconds, trace=int(trace),
                 jax=jax.__version__, compile_cache_dir=cache_dir,
                 device={"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)})

    window = {}

    note = []

    def start_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        note.append(jax.profiler.TraceAnnotation("cb:window"))
        note[0].__enter__()

    def stop_trace():
        note.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()

    ctx = {
        "cell": cell, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "devices": devices, "peaks": peaks,
        "meter": harness.CompileMeter(), "spans": harness.Spans(annotate=bool(trace)),
        "rehearsal": rehearsal,
        "window_start": lambda t: window.setdefault("t0", t),
        "start_trace": start_trace, "stop_trace": stop_trace,
    }
    try:
        result = driver.run(ctx)
    finally:
        if rehearsal is not None and cell["chips"] == 1:
            jax.devices = jax_devices
    done = time.perf_counter()
    # set-up: everything before the window, and the checks after it
    setup_s = (window["t0"] - _PROCESS_START) + (
        done - window["t0"] - result["window_s"])

    device = harness.describe_device(devices)
    # memory_stats() misses a program's temporaries on this chip: a driver
    # that knows the compiler's own sizing of its program gives it
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"],
                                      int(result.get("program_bytes", 0)))
    if not trace:
        metrics = {name: {"value": float(result["end_to_end"][name]),
                          "unit": unit}
                   for name, unit in ((m["name"], m["unit"])
                                      for m in cell["end_to_end"])
                   if name in result["end_to_end"]}
        metrics["setup_s"] = {"value": float(setup_s), "unit": "s"}
        breakdown = None
    else:
        from chipbench import xtrace

        summary = xtrace.summarize(trace_dir)
        ctx.update(result=result, trace_summary=summary)
        metrics = harness.layer_metrics(cell, ctx)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.emit(phase="end", setup_s=setup_s, window_s=result["window_s"],
                 counters=result.get("counters"))
    return harness.last_line(correct=result["correct"],
                             attempted=result["attempted"],
                             failed=result["failed"], metrics=metrics,
                             device=device, breakdown=breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"chipbench: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
