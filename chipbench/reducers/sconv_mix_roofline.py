"""Share of its roofline that the pass between a short-convolution mixer's two
projections reaches (the gate before, the causal depthwise taps, the gate
after: ``ops/short_conv.sconv_mix``), in percent: the least time the chip could
take for the bytes and operations the pass REQUIRES in a training step
(``arith_sconv.mix_bytes_per_step`` / ``mix_flops_per_step``: forward once,
the backward's own reads and writes, the replay under remat NOT required; the
larger of operations over the bf16 peak and bytes over the HBM peak: the bytes
bind), over the self time per traced step of the first device's ops under the
scope ``sconv_mix``, in every pass. The counts read the work, not the
implementation: an XLA body and a kernel are judged on the same yardstick.
Prints the phase line ``sconv_mix_roofline`` with both counts, the
milliseconds a step, which peak binds and the route the program says the pass
took. None where the trace has no such op (a program without the scope, a CPU
trace) or the driver kept no facts of a model with convolution layers.
"""

from chipbench import arith_sconv, harness, xscope


def reduce(ctx):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "sconv_taps", 0) \
            or not arith_sconv.layers_of(cfg, "sconv"):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope_s = sum(d for _, path, d in rows
                  if "sconv_mix" in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    batch, seq = facts["batch"], facts["seq"]
    flops = arith_sconv.mix_flops_per_step(cfg, batch, seq)
    nbytes = arith_sconv.mix_bytes_per_step(cfg, batch, seq)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="sconv_mix_roofline", cell=ctx["cell"]["name"],
                 layers=arith_sconv.layers_of(cfg, "sconv"), taps=cfg.sconv_taps,
                 route=facts.get("sconv_route"),
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
