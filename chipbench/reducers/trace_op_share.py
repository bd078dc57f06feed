"""Share of the first device's op time that ops matching ``pattern`` (a
regular expression on the op's name in the trace) took, in percent. Ops run
one at a time on a TPU core, so the sum of op durations is its busy time.

args: ``pattern``.
"""

import re


def reduce(ctx, pattern):
    ops = ctx["trace_summary"]["ops"]
    total = sum(ops.values())
    if total <= 0:
        return None
    rx = re.compile(pattern)
    hit = sum(s for n, s in ops.items() if rx.search(n))
    return 100.0 * hit / total
