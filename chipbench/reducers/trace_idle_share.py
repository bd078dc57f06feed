"""Device idle share of the traced window, in percent: 1 minus the union of
the device-op intervals over the window, mean over the chips used."""


def reduce(ctx):
    s = ctx["trace_summary"]
    if not s["devices"] or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
