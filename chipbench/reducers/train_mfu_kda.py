"""Model FLOP/s utilization of a KDA / latent-attention training cell, in
percent: the operations one token's forward and backward passes require
(``arith_kda.train_flops_per_token``, which the driver computed from the last
traced step's held expert rows: ``facts["kda_flops_per_token"]``), times
tokens per second per chip from the median blocked step of the traced run,
over the chip's published bf16 peak. None where the driver kept no steps or no
such count."""

import statistics


def reduce(ctx):
    f = ctx["result"].get("facts", {})
    if not f.get("step_s") or not f.get("kda_flops_per_token") or \
            not getattr(f.get("model_cfg"), "kda_heads", 0):
        return None
    rate = f["tokens_per_step"] / statistics.median(f["step_s"]) / f["chips"]
    return 100.0 * f["kda_flops_per_token"] * rate / ctx["peaks"]["bf16_flops_per_s"]
