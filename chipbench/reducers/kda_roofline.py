"""Share of its roofline the chunked delta rule with a decay a key channel
(KDA) reaches, in percent: the least time the chip could take for the
operations and bytes the rule REQUIRES in a training step
(``arith_kda.kda_scan_flops_per_step`` / ``kda_scan_bytes_per_step``:
per-channel terms and g's float32 bytes counted, recomputation not; the larger
of operations over the bf16 peak and bytes over the HBM peak), over the self
time per traced step of the first device's ops under the program's scope
``scope``. Prints the phase line ``kda_scan_roofline`` with both counts, the
milliseconds a step and which peak binds. None where the trace has no op under
the scope (a program without it, a CPU trace) or the driver kept no facts of a
model with KDA layers.

args: ``scope`` (a named scope of the program).
"""

from chipbench import arith_kda, harness, xscope


def reduce(ctx, scope):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "kda_heads", 0):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope_s = sum(d for _, path, d in rows
                  if scope in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    tokens = facts["tokens_per_step"] // facts["chips"]
    flops = arith_kda.kda_scan_flops_per_step(cfg, tokens)
    nbytes = arith_kda.kda_scan_bytes_per_step(cfg, tokens)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="kda_scan_roofline", cell=ctx["cell"]["name"],
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
