"""Share of its roofline that the attention cores of ONE kind of layer reach in
a stack that has window ("swa") and full ("attn") kinds, in percent: the least
time the chip could take for the operations and bytes those cores REQUIRE in a
training step (``arith_swa.core_flops_per_step`` / ``core_bytes_per_step``:
the visible (query, key) pairs only, so a block the kernel skips and the masked
half of a block it visits are NOT required; forward once, backward at its own
count; remat's second forward and a flash backward's recomputed scores not
counted; the larger of operations over the bf16 peak and bytes over the HBM
peak), over the self time per traced step of the first device's ops of that
kind's cores: for ``mixer`` "swa" the ops under the scope ``swa_core``, for
"attn" the ops under ``attn_core`` that are NOT under ``swa_core`` (the window
layers nest their own scope inside the attention layer's). Prints the phase
line ``attn_core_roofline`` with the kind, both counts, the milliseconds a
step and which peak binds. None where the trace has no such op (a program
without the scopes, a CPU trace) or the driver kept no facts of a model with a
window kind.

args: ``mixer`` ("swa" or "attn").
"""

from chipbench import arith_swa, harness, xscope


def reduce(ctx, mixer):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "swa_window", 0):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []

    def mine(path):
        parts = xscope.components(path)
        if mixer == "swa":
            return "swa_core" in parts
        return "attn_core" in parts and "swa_core" not in parts

    scope_s = sum(d for _, path, d in rows if mine(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    batch, seq = facts["batch"], facts["seq"]
    flops = arith_swa.core_flops_per_step(cfg, mixer, batch, seq)
    nbytes = arith_swa.core_bytes_per_step(cfg, mixer, batch, seq)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="attn_core_roofline", cell=ctx["cell"]["name"], mixer=mixer,
                 layers=arith_swa.layers_of(cfg, mixer), heads=cfg.heads_of(mixer),
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
