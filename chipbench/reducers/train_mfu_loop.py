"""Model FLOP/s utilization of a LOOPED dense training cell (Ouro shaped), in
percent: the operations one token's forward and backward passes require
(``arith_loop.train_flops_per_token`` at the cell's sequence length and loop
steps, every visit of every block and every exit's reading of the head
counted, remat's replay not; the driver computed it:
``facts["loop_flops_per_token"]``), times tokens per second per chip from the
median blocked step of the traced run, over the chip's published bf16 peak:
the cell's share of the whole step's peak. None where the driver kept no steps
or no such count (a program without the configuration)."""

import statistics


def reduce(ctx):
    f = ctx["result"].get("facts", {})
    if not f.get("step_s") or not f.get("loop_flops_per_token"):
        return None
    rate = f["tokens_per_step"] / statistics.median(f["step_s"]) / f["chips"]
    return 100.0 * f["loop_flops_per_token"] * rate / ctx["peaks"]["bf16_flops_per_s"]
