"""``gmm_roofline_held_routed``'s reading for UNGATED experts (two matrices an
expert: six grouped GEMMs a routed block and step, not nine), in percent: the
least time the chip could take for the operations and bytes the step's grouped
GEMMs REQUIRE on the rows that fell on the held experts
(``arith_ssm.held_gemm_flops_per_step`` / ``held_gemm_bytes_per_step`` on the
driver's ``held_rows_per_step``: pad rows and recomputation not counted; the
larger of operations over the bf16 peak and bytes over the HBM peak), over the
device time of the ops matching ``pattern`` per traced step. Prints the phase
line ``gmm_roofline_held_ungated`` with both counts, the kernel milliseconds a
step and which peak binds. None where the trace has no such op, the driver
kept no held rows, or the model's experts are gated (``activation`` "swiglu":
``gmm_roofline_held`` / ``_held_routed`` read those).

args: ``pattern`` (a regular expression on the op's name in the trace).
"""

import re

from chipbench import arith_ssm, harness


def reduce(ctx, pattern):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    rows = facts.get("held_rows_per_step")
    if cfg is None or not steps or not rows or not hasattr(cfg, "routed_layers") \
            or getattr(cfg, "activation", "swiglu") == "swiglu":
        return None
    rx = re.compile(pattern)
    kernel_s = sum(s for n, s in ctx["trace_summary"]["ops"].items()
                   if rx.search(n)) / steps
    if kernel_s <= 0:
        return None
    flops = arith_ssm.held_gemm_flops_per_step(cfg, rows)
    nbytes = arith_ssm.held_gemm_bytes_per_step(cfg, rows)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    harness.emit(phase="gmm_roofline_held_ungated", cell=ctx["cell"]["name"],
                 held_rows_per_step=rows, routed_layers=cfg.routed_layers,
                 required_flops_per_step=flops, required_bytes_per_step=nbytes,
                 kernel_ms_per_step=kernel_s * 1e3,
                 least_ms_by_flops=by_flops * 1e3,
                 least_ms_by_bytes=by_bytes * 1e3,
                 binds="bf16_flops_per_s" if by_flops >= by_bytes
                 else "hbm_bytes_per_s", traced_steps=steps)
    return 100.0 * max(by_flops, by_bytes) / kernel_s
