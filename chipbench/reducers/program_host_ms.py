"""Host time the program spends per step, in milliseconds: for each step
annotation of the program (``sxt:<step>``), the summed self time of the named
``sxt:`` spans inside it; ``stat`` over the traced steps. A span's self time
is its duration less the program spans directly inside it.

args: ``step`` ("train", "serve"), ``spans`` (names without the prefix),
``stat`` ("median").
"""

from chipbench import harness, xscope, xtrace


def reduce(ctx, step, spans, stat="median"):
    tab = xscope.table(ctx)
    if not tab:
        return None
    lo, hi = xscope.window(tab)
    steps = sorted((a, b) for n, a, b, _ in xscope.program_spans(tab, steps=True)
                   if n == step and a >= lo and b <= hi)
    rows = xscope.program_spans(tab)
    selfs = xtrace.self_times([(i, a, b) for i, (_, a, b, _) in enumerate(rows)])
    wanted = set(spans)
    values = [sum(d for i, d in selfs if rows[i][0] in wanted
                  and a <= rows[i][1] < b) * 1e-6 for a, b in steps]
    return harness.stat(values, stat)
