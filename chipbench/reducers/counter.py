"""A count the driver read from the program over the window.

args: ``counter`` (a key of the driver's ``counters``).
"""


def reduce(ctx, counter):
    return ctx["result"].get("counters", {}).get(counter)
