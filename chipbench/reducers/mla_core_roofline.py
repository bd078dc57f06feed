"""Share of its roofline that latent attention's core reaches, in percent: the
least time the chip could take for the operations and bytes causal attention
REQUIRES in a training step at scores ``head_dim`` wide and values
``mla_v_dim`` wide (``arith_mla.mla_core_flops_per_step`` /
``mla_core_bytes_per_step``: forward once, backward at its own count; remat's
second forward, a flash backward's recomputed scores and any padding not
counted; the larger of operations over the bf16 peak and bytes over the HBM
peak), over the self time per traced step of the first device's ops under the
program's scope ``scope``. Prints the phase line ``mla_core_roofline`` with
both counts, the milliseconds a step and which peak binds. None where the
trace has no op under the scope (a program without it, a CPU trace) or the
driver kept no facts of a model with latent attention.

args: ``scope`` (a named scope of the program).
"""

from chipbench import arith_mla, harness, xscope


def reduce(ctx, scope):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "mla_kv_rank", 0):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope_s = sum(d for _, path, d in rows
                  if scope in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    batch, seq = facts["batch"], facts["seq"]
    flops = arith_mla.mla_core_flops_per_step(cfg, batch, seq)
    nbytes = arith_mla.mla_core_bytes_per_step(cfg, batch, seq)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="mla_core_roofline", cell=ctx["cell"]["name"],
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
