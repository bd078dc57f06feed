"""Share of its roofline that one stage of a learned sparse attention (mixer
"dsa") reaches in a training step, in percent: the least time the chip could
take for the operations and bytes the MODEL requires of that stage
(``arith_dsa``: for ``stage`` "core" the softmax cores over the SELECTED (query,
key) pairs, ``core_flops_per_step`` / ``core_bytes_per_step``; for "index" the
indexer's scores over EVERY causal pair, ``index_flops_per_step`` /
``index_bytes_per_step``; forward once, backward at its own count; remat, a
flash backward's recomputed scores, unselected pairs a masked kernel computes,
the mask and the selection's compares not counted: the same work whatever
implements it, so neither can read over 100; the larger of operations over the
bf16 peak and bytes over the HBM peak), over the self time per traced step of
the first device's ops under the stage's scope (``dsa_core`` / ``dsa_index``).
Prints the phase line ``dsa_roofline`` with both counts, the milliseconds a
step and which peak binds. None where the trace has no such op (a program
without the scopes: the parent of PR 61; a CPU trace) or the driver kept no
facts of a model with the mixer.

args: ``stage`` ("core" or "index").
"""

from chipbench import arith_dsa, harness, xscope


def reduce(ctx, stage):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "dsa_topk", 0):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope = "dsa_" + stage
    scope_s = sum(d for _, path, d in rows
                  if scope in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    batch, seq = facts["batch"], facts["seq"]
    flops = getattr(arith_dsa, stage + "_flops_per_step")(cfg, batch, seq)
    nbytes = getattr(arith_dsa, stage + "_bytes_per_step")(cfg, batch, seq)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="dsa_roofline", cell=ctx["cell"]["name"], stage=stage,
                 layers=cfg.n_layers, required_flops_per_step=flops,
                 required_bytes_per_step=nbytes, scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3, least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
