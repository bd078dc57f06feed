"""How long the device took for one execution of a program, in milliseconds:
the durations of the ``jit_<program>`` events on the first device's ``XLA
Modules`` line that lie inside ``cb:window``, ``stat`` over them. The inside
twin of a host span around the call: it leaves out the host's dispatch and
the wait for the result. A program without the tracer reports nothing.

args: ``program`` ("train_step"), ``stat`` ("median", "mean", "p95").
"""

from chipbench import harness, xscope


def executions(tab, program):
    """Durations (ns) of the whole executions of ``jit_<program>`` on the
    first device inside the window."""
    lo, hi = xscope.window(tab)
    return [d for n, s, d in tab["devices"][0]["modules"]
            if n == "jit_" + program and s >= lo and s + d <= hi]


def reduce(ctx, program, stat="median"):
    tab = xscope.table(ctx)
    if not tab or not tab["devices"]:
        return None
    return harness.stat([d * 1e-6 for d in executions(tab, program)], stat)
