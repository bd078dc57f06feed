"""Share of the traced window in which the first device's core is inside an
op that is, or holds, a collective, in percent. An op counts if the trace
names it a collective (as ``collective_sync_share`` does), or the program's
``program_ops`` says the instruction or a computation it calls holds one (a
``fusion`` around a reduce-scatter; control flow is not counted, its body's
ops are events of their own), or it runs under one of ``scopes`` (the
program's names for its explicit gathers and reduce-scatters). Self time on
the ops line, so what an asynchronous collective overlaps is still not seen.
Prints the phase line ``collective_ops``: seconds by (scope, op), top 12.

args: ``scopes``, ``table`` (scopes to show as rows).
"""

from chipbench import harness, xscope, xtrace


def reduce(ctx, scopes=(), table=()):
    tab = xscope.table(ctx)
    if not tab or not tab["devices"]:
        return None
    lo, hi = xscope.window(tab)
    if hi <= lo:
        return None
    holds = {name for name, (_, opcode, has) in tab["program_ops"].items()
             if has and opcode not in xscope.CONTROL_FLOW}
    wanted = set(scopes)
    by, total = {}, 0.0
    for name, path, d in xscope.op_self_times(tab):
        short = xtrace.short_name(name)
        if not (xtrace.COLLECTIVE.search(short) or name in holds
                or wanted.intersection(xscope.components(path))):
            continue
        total += d
        key = (xscope.innermost(path, table) or "(none)", short)
        by[key] = by.get(key, 0.0) + d * 1e-9
    top = sorted(by.items(), key=lambda kv: -kv[1])[:12]
    harness.emit(phase="collective_ops", cell=ctx["cell"]["name"],
                 collective_s=total * 1e-9, window_s=(hi - lo) * 1e-9,
                 rows=[[scope, op, s] for (scope, op), s in top])
    return 100.0 * total / (hi - lo)
