"""Share of its roofline that the causal attention cores of a LOOPED one-kind
stack reach (plain multi-head attention, every block visited ``loop_steps``
times a pass), in percent: the least time the chip could take for the
operations and bytes those cores REQUIRE in a training step
(``arith_loop.core_flops_per_step`` / ``core_bytes_per_step``: the visible
(query, key) pairs only, forward once and backward at its own count over the
steps x layers visits; remat's second forward and a flash backward's
recomputed scores not counted; the larger of operations over the bf16 peak and
bytes over the HBM peak), over the self time per traced step of the first
device's ops under the scope ``attn_core``. Prints the phase line
``loop_attn_core_roofline`` with both counts, the milliseconds a step and which
peak binds. None where the trace has no such op (a program without the scope,
a CPU trace) or the driver kept no facts of a looped model."""

from chipbench import arith_loop, harness, xscope


def reduce(ctx):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    loops = facts.get("loop_steps")
    if cfg is None or not steps or not loops:
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope_s = sum(d for _, path, d in rows
                  if "attn_core" in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    batch, seq = facts["batch"], facts["seq"]
    flops = arith_loop.core_flops_per_step(cfg, loops, batch, seq)
    nbytes = arith_loop.core_bytes_per_step(cfg, loops, batch, seq)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="loop_attn_core_roofline", cell=ctx["cell"]["name"],
                 layer_visits=arith_loop.layer_visits(cfg, loops), heads=cfg.n_heads,
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
