"""A statistic over the program's compile records before the window: one
record per program built (``trace.compile_events(every=True)``: ``trace_s``
tracing, ``lower_s`` lowering, ``seconds`` in the backend, compiling or
reading the persistent cache, ``cache_hit``), those whose last part ended
before the first ``since_span`` span the benchmark recorded. Everything this
process built is there: the program's own programs and the benchmark's
(reference, readings). A program without ``trace.phases`` (the parent of PR
52) reports nothing.

args: ``field`` (a record's key, or several to add up a record; also
``cache_miss``: 1 for a record that reached the backend and was not served
by the persistent cache), ``stat`` ("sum", "median", ...), ``since_span``.
"""

from chipbench import harness
from chipbench.reducers.program_phase_s import before_window


def _value(record, field):
    if field == "cache_miss":
        return float(record["compiled"] and not record["cache_hit"])
    return float(record[field])


def reduce(ctx, field, stat="sum", since_span="train_step"):
    found = before_window(ctx, since_span)
    if found is None:
        return None
    records = found[3]
    fields = [field] if isinstance(field, str) else list(field)
    return harness.stat([sum(_value(e, f) for f in fields) for e in records],
                        stat)
