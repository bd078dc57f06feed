"""Where the first device's idle time of the traced window goes, and the
share of it the program's own spans account for, in percent.

Every idle gap (the window less the union of the device's op intervals) is
split three ways: ``in_step`` (the part inside a module execution on the
``XLA Modules`` line: a bubble on the device, not the host's doing), by the
innermost ``sxt:`` span of the program open on the host at that time, and
``outside_program`` (between executions, no program span open: the caller's
own code, e.g. a driver's ``next(data)`` or the return from
``block_until_ready``). The metric is the span-covered part over all idle
between executions; the whole table goes out as the phase line
``idle_by_program_span`` (seconds; the rows sum to ``idle_s``).
"""

from chipbench import harness, xscope, xtrace


def reduce(ctx):
    tab = xscope.table(ctx)
    if not tab or not tab["devices"]:
        return None
    lo, hi = xscope.window(tab)
    first = tab["devices"][0]
    busy = xtrace.union((max(s, lo), min(s + d, hi))
                        for _, s, d, _ in first["ops"])
    gaps = xtrace.subtract([(lo, hi)], busy)
    runs = xtrace.union((s, s + d) for _, s, d in first["modules"])
    between = xtrace.subtract(gaps, runs)
    rows = {"in_step": xtrace.length(gaps) - xtrace.length(between)}
    left = between
    for a, b, name in xscope.innermost_segments(xscope.program_spans(tab)):
        rest = xtrace.subtract(left, [(a, b)])
        piece = xtrace.length(left) - xtrace.length(rest)
        if piece > 0:
            rows[name] = rows.get(name, 0.0) + piece
            left = rest
    rows["outside_program"] = xtrace.length(left)
    idle = xtrace.length(gaps)
    harness.emit(phase="idle_by_program_span", cell=ctx["cell"]["name"],
                 idle_s=idle * 1e-9, window_s=(hi - lo) * 1e-9,
                 rows=[[n, s * 1e-9] for n, s in
                       sorted(rows.items(), key=lambda kv: -kv[1])])
    outside = xtrace.length(between)
    if outside <= 0:
        return None
    return 100.0 * (outside - rows["outside_program"]) / outside
