"""Active-parameter model FLOP/s utilization of a sparse-expert training cell,
in percent: the operations one token's forward and backward passes require
(``arith_moe.train_flops_per_token``: 6 per matmul parameter the token
actually multiplies by - attention, router, its ``moe_top_k`` experts, the head -
plus causal attention; recomputation and the dispatch not counted), times
tokens per second per chip from the median blocked step of the traced run,
over the chip's published bf16 peak. None where the driver kept no steps or
the model has no experts."""

import statistics

from chipbench import arith_moe


def reduce(ctx):
    f = ctx["result"].get("facts", {})
    cfg = f.get("model_cfg")
    if not f.get("step_s") or cfg is None or not getattr(cfg, "n_experts", 0):
        return None
    rate = f["tokens_per_step"] / statistics.median(f["step_s"]) / f["chips"]
    return (100.0 * arith_moe.train_flops_per_token(cfg, f["seq"]) * rate
            / ctx["peaks"]["bf16_flops_per_s"])
