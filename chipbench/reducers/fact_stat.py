"""A statistic of a list of numbers the driver kept (``facts``).

args: ``fact`` (key), ``stat`` ("median", "mean", "sum", "p95"), ``scale``.
"""

from chipbench import harness


def reduce(ctx, fact, stat="median", scale=1.0):
    value = harness.stat(ctx["result"].get("facts", {}).get(fact) or [], stat)
    return None if value is None else value * scale
