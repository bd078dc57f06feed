"""Share of the first device's op self time that ran under the program's
named scopes, in percent (the program's ``jax.named_scope``s, read through
``xscope``). Ops run one at a time on a TPU core, so self times add up to its
busy time; an op belongs to a scope when the scope is a component of its
``op_name`` path.

args: ``scopes`` (the share under any of these), or ``none_of`` (the share
under none of them: what no scope of the model or the optimizer reaches).
With ``table`` (the scopes to show as rows) the reducer also prints the phase
line ``device_time_by_scope``: seconds by (innermost scope, op), top 20.
"""

from chipbench import harness, xscope, xtrace


def reduce(ctx, scopes=None, none_of=None, table=None):
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    total = sum(d for *_, d in rows)
    if total <= 0:
        return None
    wanted = set(scopes or none_of)
    inside = sum(d for _, path, d in rows
                 if wanted.intersection(xscope.components(path)))
    if table:
        by = {}
        for name, path, d in rows:
            key = (xscope.innermost(path, table) or "(none)",
                   xtrace.short_name(name))
            by[key] = by.get(key, 0.0) + d * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:20]
        harness.emit(phase="device_time_by_scope", cell=ctx["cell"]["name"],
                     op_self_s=total * 1e-9,
                     rows=[[scope, op, s] for (scope, op), s in top])
    share = 100.0 * inside / total
    return share if scopes else 100.0 - share
