"""Seconds the program's own start-up phases took before the window: the
rows of ``trace.phases()`` (``init/import``, ``init/config``, ``init/engine``
and its sections, ``train/lower``, ``train/compile``, ``train/first_step``:
``SETUP.md``) that closed before the first ``since_span`` span the benchmark
recorded, summed over the names in ``phases``. Host time on ``perf_counter``,
the clock of the benchmark's own spans.

With ``table`` prints the phase line ``setup_by_program_phase``: EVERY phase
row, oldest first (a parent before its children), with the compile records
(``trace.compile_events(every=True)``) whose last part fell inside it and
inside no phase nested in it: seconds of tracing, of lowering and in the
backend (compiling, or reading the persistent cache), programs that reached
the backend, and those of them the cache did not serve (``missed`` names each:
row, ``fun_name``, seconds). Records under no
phase go to a row named for their span, or ``(outside the program)`` where no
``sxt:`` span was open either: the benchmark's reference and readings. A
column summed over the rows counts every record once. A program without
``trace.phases`` (the parent of PR 52) reports nothing.

args: ``phases`` (names), ``since_span`` ("train_step"), ``table`` (print).
"""

import sys

from chipbench import harness

OUTSIDE = "(outside the program)"


def process_start() -> float:
    """``run.py``'s first line on ``perf_counter``; 0 where it is not the
    entry point and was not imported either."""
    for name in ("__main__", "chipbench.run"):
        t0 = getattr(sys.modules.get(name), "_PROCESS_START", None)
        if t0 is not None:
            return float(t0)
    return 0.0


def before_window(ctx, since_span):
    """(process start, window start, phase rows, compile records) of this
    process before the window, or None: no tracer with phases, no window."""
    try:
        from shuffle_exchange_tpu.profiling import trace

        read = trace.phases
    except (ImportError, AttributeError):
        return None
    spans = sorted(ctx["spans"].named(since_span))
    if not spans:
        return None
    start, end = process_start(), spans[0][0]
    rows = [r for r in read(since=start) if r["t1"] <= end]
    records = [e for e in trace.compile_events(since=start, every=True)
               if e["at"] < end]
    return start, end, rows, records


def _row(name, parent, seconds):
    return {"phase": name, "parent": parent, "seconds": seconds,
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "programs": 0,
            "cache_misses": 0}


def phase_table(rows, records):
    """(the phase rows with their own compile records, then the rows of the
    records no phase holds; [row, ``fun_name``, backend seconds] of every
    program the persistent cache did not serve)."""
    out = [_row(r["name"], r["parent"], r["t1"] - r["t0"]) for r in rows]
    loose, missed = {OUTSIDE: _row(OUTSIDE, None, None)}, []
    for e in records:
        inside = [i for i, r in enumerate(rows) if r["t0"] <= e["at"] <= r["t1"]]
        if inside:
            row = out[max(inside, key=lambda i: rows[i]["t0"])]   # innermost
        else:
            name = e["span"] or OUTSIDE
            row = loose.setdefault(name, _row(name, None, None))
        row["trace_s"] += e["trace_s"]
        row["lower_s"] += e["lower_s"]
        row["backend_s"] += e["seconds"]
        row["programs"] += bool(e["compiled"])
        if e["compiled"] and not e["cache_hit"]:
            row["cache_misses"] += 1
            missed.append([row["phase"], e["fun_name"], e["seconds"]])
    return out + [loose[k] for k in sorted(loose)], missed


def reduce(ctx, phases, since_span="train_step", table=False):
    found = before_window(ctx, since_span)
    if found is None:
        return None
    start, end, rows, records = found
    if table:
        table_rows, missed = phase_table(rows, records)
        harness.emit(phase="setup_by_program_phase", cell=ctx["cell"]["name"],
                     before_window_s=(end - start) if start else None,
                     in_phases_s=sum(r["t1"] - r["t0"] for r in rows
                                     if r["parent"] is None),
                     rows=table_rows, missed=missed)
    wanted = set(phases)
    return sum(r["t1"] - r["t0"] for r in rows if r["name"] in wanted)

