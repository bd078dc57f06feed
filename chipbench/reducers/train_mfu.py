"""Model FLOP/s utilization of a training cell, in percent: operations the
forward and backward passes require per token (``arith.train_flops_per_token``:
6 per matmul parameter plus causal attention; recomputation not counted),
times tokens per second per chip from the median blocked step of the traced
run, over the chip's published bf16 peak."""

import statistics


def reduce(ctx):
    f = ctx["result"]["facts"]
    if not f.get("step_s"):
        return None
    rate = f["tokens_per_step"] / statistics.median(f["step_s"]) / f["chips"]
    return 100.0 * f["flops_per_token"] * rate / ctx["peaks"]["bf16_flops_per_s"]
