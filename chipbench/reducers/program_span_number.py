"""A statistic of a number the program's spans carry in the trace
(``trace.span(name, wait_ms=...)`` writes it as a stat of the ``sxt:`` event),
over the spans of that name inside the traced window.

args: ``span`` (name without the prefix), ``number`` (the stat's key),
``stat`` ("median", "mean", "p95"), ``scale``.
"""

from chipbench import harness, xscope


def reduce(ctx, span, number, stat="median", scale=1.0):
    tab = xscope.table(ctx)
    if not tab:
        return None
    lo, hi = xscope.window(tab)
    values = [nums[number] for n, s, d, _, nums in tab["host"]
              if n == xscope.PROGRAM_PREFIX + span and lo <= s <= hi
              and number in nums]
    value = harness.stat(values, stat)
    return None if value is None else value * scale
