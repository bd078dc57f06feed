"""Model FLOP/s utilization of an Olmo Hybrid training cell, in percent: the
operations one token's forward and backward passes require
(``arith_olmohybrid.train_flops_per_token`` at the cell's sequence length),
times tokens per second per chip from the median blocked step of the traced
run, over the chip's published bf16 peak: the cell's share of the whole step's
peak. None where the driver kept no steps or the model has no DeltaNet layer
(a program without the configuration)."""

import statistics

from chipbench import arith_olmohybrid


def reduce(ctx):
    f = ctx["result"].get("facts", {})
    cfg = f.get("model_cfg")
    if not f.get("step_s") or not getattr(cfg, "gdn_value_heads", 0):
        return None
    rate = f["tokens_per_step"] / statistics.median(f["step_s"]) / f["chips"]
    flops = arith_olmohybrid.train_flops_per_token(cfg, f["seq"])
    return 100.0 * flops * rate / ctx["peaks"]["bf16_flops_per_s"]
