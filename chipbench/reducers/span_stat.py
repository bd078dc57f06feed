"""A statistic of the benchmark's own spans inside the window.

args: ``span`` (name), ``stat`` ("median", "mean", "sum", "p95"), ``minus``
(optional inner span: each ``span`` is taken less the ``minus`` spans it
contains, i.e. its self time), ``scale`` (1000 for milliseconds).
"""

from chipbench import harness


def reduce(ctx, span, stat="median", minus=None, scale=1000.0):
    rows = sorted(ctx["spans"].named(span))
    values = [b - a for a, b in rows]
    if minus:
        inner, j, values = sorted(ctx["spans"].named(minus)), 0, []
        for a, b in rows:
            while j < len(inner) and inner[j][0] < a:
                j += 1
            covered = 0.0
            while j < len(inner) and inner[j][1] <= b:
                covered += inner[j][1] - inner[j][0]
                j += 1
            values.append((b - a) - covered)
    value = harness.stat(values, stat)
    return None if value is None else value * scale
