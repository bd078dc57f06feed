"""Share of the first device's op self time whose INNERMOST named scope is
``scope``, in percent: ``scope_share`` counts an op under a scope wherever the
scope is a component of its ``op_name`` path, which for a scope that wraps a
whole scan (a looped stack's ``loop`` around its outer scan) is every op of
the layers inside; what has ``loop`` for its innermost scope among ``table``
is the outer scan's own work. None where the trace has no op at all or none
whose path holds ``scope`` (a program without it).

args: ``scope``, ``table`` (the scopes among which the innermost is sought:
the program's registry, ``scope`` among them).
"""

from chipbench import xscope


def reduce(ctx, scope, table):
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    total = sum(d for *_, d in rows)
    if total <= 0 or not any(scope in xscope.components(path) for _, path, _ in rows):
        return None
    mine = sum(d for _, path, d in rows if xscope.innermost(path, table) == scope)
    return 100.0 * mine / total
