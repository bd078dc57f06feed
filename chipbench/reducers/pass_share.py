"""Share of the first device's op self time that ran in one pass of the train
step, in percent: ``forward``, ``recompute`` (what ``jax.checkpoint`` replays
inside the backward pass), ``backward``, ``update`` (the optimizer's scopes)
or ``other``. The pass is the program's own reading of an op's ``op_name``
path (``trace.phase_of``); the rows are ``scope_share``'s
(``xscope.op_self_times``), so the five shares sum to 100. A fusion counts as
the pass of the one ``op_name`` XLA left on it. A program without
``phase_of`` (the parent of PR 37) reports nothing.

args: ``phase`` (one of ``trace.PHASES``); ``per_step_ms`` (true: the
milliseconds of that pass a traced step, not the share: the self time over
the executions of ``jit_<program>`` in the window); ``program``
("train_step"). With ``table`` (the scopes to show as rows) the reducer also
prints the phase line ``device_time_by_scope_and_pass``: seconds by (innermost
scope, pass), EVERY row, with ``op_self_s`` (the rows sum to it) and
``steps``.
"""

from chipbench import harness, xscope
from chipbench.reducers.module_ms import executions

_KEY = "_pass_share"


def split(ctx):
    """[(scope path, pass, self ns)] of the traced ops, cut once per run and
    kept in ``ctx`` (six metrics read it); None without the tracer's table or
    the program's ``phase_of``."""
    if _KEY not in ctx:
        try:
            from shuffle_exchange_tpu.profiling import trace

            phase_of = trace.phase_of
        except (ImportError, AttributeError):
            phase_of = None
        tab = xscope.table(ctx) if phase_of else None
        ctx[_KEY] = [(path, phase_of(path), d) for _, path, d in
                     xscope.op_self_times(tab)] if tab else None
    return ctx[_KEY]


def reduce(ctx, phase, table=None, per_step_ms=False, program="train_step"):
    rows = split(ctx)
    total = sum(d for *_, d in rows or ())
    if total <= 0:
        return None
    steps = len(executions(xscope.table(ctx), program))
    inside = sum(d for _, pass_, d in rows if pass_ == phase)
    if table:
        by = {}
        for path, pass_, d in rows:
            key = (xscope.innermost(path, table) or "(none)", pass_)
            by[key] = by.get(key, 0.0) + d * 1e-9
        harness.emit(phase="device_time_by_scope_and_pass",
                     cell=ctx["cell"]["name"], op_self_s=total * 1e-9,
                     steps=steps,
                     rows=[[scope, pass_, s] for (scope, pass_), s in
                           sorted(by.items(), key=lambda kv: -kv[1])])
    if per_step_ms:
        return inside * 1e-6 / steps if steps else None
    return 100.0 * inside / total
