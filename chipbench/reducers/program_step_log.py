"""The trainer's own log of the window's steps: the records that the
program's ``trace.step`` keeps of every ``train_batch`` (``trace.steps``:
``n``, ``t0``, ``t1`` on ``perf_counter``, the summed ``spans`` closed inside
the step, ``compiles``: the tracer's compile events that fall inside it; with
or without a profiler session) at or after the
first ``since_span`` span the benchmark recorded in the window: the two clocks
are one, as ``program_recompiles`` finds its window. ``STEPLOG.md``.

A window's **step intervals** are ``t0`` of step n+1 less ``t0`` of step n,
named for the step that begins them. In a traced run the two intervals that
hold the profiler's start and its stop are left out by step number: the one
that ends at the first step with an ``sxt:train`` event in the trace table,
and the one that begins at the last.

``metric``:
  ``slow_step_share``        % of the intervals longer than ``slow`` x their
                             median: 0 on a steady window, k of N on one that
                             stalled, 0 (beside a raised median) on one that
                             is slow throughout
  ``interval_max_over_p50``  the longest interval over the median
  ``host_ms_max``            the longest ``t1 - t0`` of the window's records,
                             every step of the window, in milliseconds

With ``line`` prints the phase line ``program_step_log`` (``log_line``). A
program without ``trace.steps`` (the parent of PR 69) reports nothing.

args: ``metric``, ``since_span`` ("train_step"), ``slow`` (1.05), ``line``.
"""

import statistics

from chipbench import harness, xscope

_KEY = "_program_step_log"


def window_records(ctx, since_span, kind="train"):
    """The program's step records inside the window, oldest first, or None:
    no tracer with a step log, no window."""
    try:
        from shuffle_exchange_tpu.profiling import trace

        read = trace.steps
    except (ImportError, AttributeError):
        return None
    spans = sorted(ctx["spans"].named(since_span))
    if not spans:
        return None
    return [r for r in read(kind, since=spans[0][0]) if r["t0"] <= spans[-1][1]]


def traced_steps(tab, program="train_step"):
    """[(n, device ms)] of the steps inside the profiler's session, oldest
    first: the step number each ``sxt:train`` event of the trace table
    carries, and the time of the ``jit_<program>`` executions on the first
    device that began before the next step's event did."""
    if not tab:
        return []
    events = sorted((s, int(numbers["step_num"]))
                    for name, s, _, _, numbers in tab["host"]
                    if name == xscope.PROGRAM_PREFIX + "train"
                    and "step_num" in numbers)
    runs = [(s, d) for name, s, d in (tab["devices"][0]["modules"]
                                      if tab["devices"] else ())
            if name == "jit_" + program]
    ends = [s for s, _ in events[1:]] + [float("inf")]
    return [(n, sum(d for s, d in runs if a <= s < b) * 1e-6)
            for (a, n), b in zip(events, ends)]


def intervals(records, left_out=()):
    """[(n, seconds)]: ``t0`` of the next record less ``t0`` of record n,
    without the intervals that begin at a step number in ``left_out``."""
    return [(a["n"], b["t0"] - a["t0"]) for a, b in zip(records, records[1:])
            if a["n"] not in left_out]


def profiler_intervals(records, traced):
    """The step numbers whose intervals hold the profiler's start and stop."""
    if not traced:
        return []
    first, last = traced[0][0], traced[-1][0]
    before = [r["n"] for r in records if r["n"] < first]
    return sorted(set(before[-1:] + [last]))


def log_line(records, traced=(), slow=1.05):
    """What a window's records say, as one flat dict: the count, the first and
    last ``n``, the median interval, ``over_median_ms`` (the kept intervals'
    sum less as many medians: what the window lost to long intervals, net of
    the short ones that follow a late wake-up with a step in flight),
    ``interval_ms`` (one an interval, named
    for the step that begins it; the last step begins none) and ``host_ms``
    (``t1 - t0``) of every step to 0.01 ms, the per-span sums over the window,
    the ``n`` of the steps that compiled, the ``n`` of the intervals left out
    and, for the steps inside the profiler's session, [n, the ring's interval,
    the device's execution] in milliseconds. The three metrics are its
    ``slow_step_share``, ``interval_max_over_p50`` and ``host_ms_max``."""
    left_out = profiler_intervals(records, traced)
    kept = intervals(records, left_out)
    every = dict(intervals(records))
    took = [s for _, s in kept]
    median = statistics.median(took) if took else None
    span_ms = {}
    for r in records:
        for name, s in r["spans"].items():
            span_ms[name] = span_ms.get(name, 0.0) + 1e3 * s
    longest = max(kept, key=lambda row: row[1], default=None)
    by_n = {r["n"]: r for r in records}
    slow_steps = [n for n, s in kept if s > slow * median]
    return {
        "count": len(records),
        "first_n": records[0]["n"] if records else None,
        "last_n": records[-1]["n"] if records else None,
        "intervals": len(kept),
        "interval_ms_p50": 1e3 * median if took else None,
        "slow_steps": slow_steps,
        "slow_step_share": 100.0 * len(slow_steps) / len(took) if took else None,
        "interval_max_over_p50": max(took) / median if took else None,
        "over_median_ms": 1e3 * (sum(took) - len(took) * median) if took else None,
        "longest": ({"n": longest[0], "interval_ms": 1e3 * longest[1],
                     "host_ms": 1e3 * (by_n[longest[0]]["t1"]
                                       - by_n[longest[0]]["t0"]),
                     "spans_ms": {k: round(1e3 * v, 3) for k, v in
                                  by_n[longest[0]]["spans"].items()}}
                    if longest else None),
        "host_ms_max": max((1e3 * (r["t1"] - r["t0"]) for r in records),
                           default=None),
        "interval_ms": [round(1e3 * s, 2) for s in every.values()],
        "host_ms": [round(1e3 * (r["t1"] - r["t0"]), 2) for r in records],
        "span_ms_sum": {k: round(v, 3) for k, v in sorted(span_ms.items())},
        "compiled_in": [r["n"] for r in records if r["compiles"] > 0],
        "left_out": left_out,
        "traced": [[n, round(1e3 * every[n], 3) if n in every else None,
                    round(ms, 3)] for n, ms in traced],
    }


def reduce(ctx, metric, since_span="train_step", slow=1.05, line=False):
    if _KEY not in ctx:
        records = window_records(ctx, since_span)
        ctx[_KEY] = None if not records else log_line(
            records, traced_steps(xscope.table(ctx)), slow)
    log = ctx[_KEY]
    if log is None:
        return None
    if line:
        harness.emit(phase="program_step_log", cell=ctx["cell"]["name"], **log)
    return log[metric]
