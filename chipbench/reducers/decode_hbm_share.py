"""Share of the HBM roofline the decode program reaches, in percent: the
least time a decode-only tick could take (the bytes it must read,
``arith.decode_bytes``: weights once + live keys and values of its rows,
median over the traced ticks, over the chip's published bytes/s) divided by
the median device time of the decode program's executions in the trace
(``XLA Modules`` events whose name matches ``module_pattern``).

args: ``module_pattern``.
"""

import re
import statistics


def reduce(ctx, module_pattern):
    need = ctx["result"]["facts"].get("decode_bytes")
    rx = re.compile(module_pattern)
    took = [s for n, s in ctx["trace_summary"]["modules"] if rx.search(n)]
    if not need or not took:
        return None
    least = statistics.median(need) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(took)
