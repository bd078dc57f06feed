"""Share of the traced window in which the first device's core is inside a
collective op, in percent: the self time, on the ops line, of the ops the
trace names all-gather / reduce-scatter / all-reduce / collective-permute /
all-to-all (and the ``-done`` halves of asynchronous ones). A core runs one
op at a time, so this is time in which it computes nothing. It is not the
whole cost of communication: what an asynchronous collective overlaps is not
on this line, and a collective inside an op named ``fusion`` is not seen."""


def reduce(ctx):
    s = ctx["trace_summary"]
    if not s["devices"] or s["window_s"] <= 0:
        return None
    return 100.0 * s["collective_sync_s"] / s["window_s"]
