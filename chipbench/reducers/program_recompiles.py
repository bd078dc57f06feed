"""Compilations the program itself saw inside the window: its own compile
events (``trace.compile_events``: program, span, seconds, cache hit) at or
after the first ``since_span`` span the benchmark recorded in the window.
Prints a phase line ``recompile`` for each, naming program and span. A program
without the tracer reports nothing.

args: ``since_span`` (one of the benchmark's own spans, on ``perf_counter``).
"""

from chipbench import harness


def reduce(ctx, since_span):
    try:
        from shuffle_exchange_tpu.profiling import trace

        read = trace.compile_events
    except (ImportError, AttributeError):
        return None
    rows = sorted(ctx["spans"].named(since_span))
    if not rows:
        return None
    events = read(since=rows[0][0])
    for e in events:
        harness.emit(phase="recompile", cell=ctx["cell"]["name"], **e)
    return len(events)
