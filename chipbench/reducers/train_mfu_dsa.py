"""Model FLOP/s utilization of a learned-sparse-attention training cell, in
percent: the operations one token's forward and backward passes require
(``arith_dsa.train_flops_per_token``, which the driver computed from the last
step's held expert rows: ``facts["dsa_flops_per_token"]``: the indexer's scores
over the causal pairs and the cores over the SELECTED pairs), times tokens per
second per chip from the median blocked step of the traced run, over the
chip's published bf16 peak. None where the driver kept no steps or no such
count."""

import statistics


def reduce(ctx):
    f = ctx["result"].get("facts", {})
    if not f.get("step_s") or not f.get("dsa_flops_per_token"):
        return None
    rate = f["tokens_per_step"] / statistics.median(f["step_s"]) / f["chips"]
    return 100.0 * f["dsa_flops_per_token"] * rate / ctx["peaks"]["bf16_flops_per_s"]
