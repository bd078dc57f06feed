"""Share of its roofline that the state-space scan reaches (what lies between
a Mamba-2 mixer's convolution and its gated norm: ``ops/ssd.ssd_chunked``), in
percent: the least time the chip could take for the bytes and operations the
scans REQUIRE in a training step (``arith_ssm.scan_bytes_per_step`` /
``scan_flops_per_step``: forward once, the backward's own reads, writes and
products, the replay under remat NOT required; the larger of operations over
the bf16 peak and bytes over the HBM peak), over the self time per traced step
of the first device's ops under the scope ``ssm_scan``, in every pass. The
counts read the work, not the implementation: an XLA body and a kernel are
judged on the same yardstick. Prints the phase line ``ssd_scan_roofline`` with
both counts, the milliseconds a step, which peak binds and the route the
program says the scan took. None where the trace has no such op (a program
without the scope, a CPU trace) or the driver kept no facts of a model with
state-space layers.
"""

from chipbench import arith_ssm, harness, xscope


def reduce(ctx):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "ssm_heads", 0) \
            or not arith_ssm.layers_of(cfg, "ssm"):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope_s = sum(d for _, path, d in rows
                  if "ssm_scan" in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    batch, seq = facts["batch"], facts["seq"]
    flops = arith_ssm.scan_flops_per_step(cfg, batch, seq)
    nbytes = arith_ssm.scan_bytes_per_step(cfg, batch, seq)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="ssd_scan_roofline", cell=ctx["cell"]["name"],
                 layers=arith_ssm.layers_of(cfg, "ssm"), chunk=arith_ssm.SCAN_CHUNK,
                 route=facts.get("ssd_route"),
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / scope_s
