"""Share of its roofline that the state-space mixer's epilogue reaches (the
skip ``o + D x``, the gate ``silu(z)`` and the gated RMSNorm over each group's
channels: ``ops/ssm_gate_norm.ssm_gate_norm``), in percent: the least time the
chip could take for the bytes the epilogues REQUIRE in a training step
(``arith_granite4h.gate_norm_bytes_per_step``: 10 reads and writes of [rows,
inner] bf16 a layer, forward 4 and backward 6; the replay under remat, float32
copies, partial sums and the statistic's own passes NOT required; the bytes bind: ~20 elementwise
operations a channel are nothing beside them) over the self time per traced
step of the first device's ops under the scope ``ssm_out_norm``, in every
pass. The counts read the work, not the implementation: an XLA body and a
kernel are judged on the same yardstick. The sizes come from the driver's
facts (the program's ``TransformerConfig``, the cell's batch and sequence).
Prints the phase line ``ssm_gate_norm_roofline`` with the count, the
milliseconds a step and the ROUTE the program says the epilogue takes at the
cell's shapes (``ops.ssm_gate_norm.ssm_gate_norm_route``). None where the trace
has no such op (a program without the scope, a CPU trace) or the driver kept
no facts of a model with state-space layers.

None, too, where that route is "xla" (the line is still printed): XLA fuses
its body ACROSS the scope's edge (the forward's output is never written: the
out-projection's matmul reads o, x and z and norms them as its operand, under
``ssm_out``), so the scope's self time leaves out part of the work and a share
read there passes 100 (``granite4h-train``, one group of 4096 channels: 16.3 ms
a step for 20.6 by 14 passes, 6.8 for 7.4 by these 10: 126.5% and 108.9%; my
chip runs, PR 55). The kernels' launches are the whole pass and nothing else
runs under the scope (``nemotron3-train``), which is where the share is read.
"""

from chipbench import arith_granite4h, arith_ssm, harness, xscope


def _route(cfg, facts):
    """The route the program states for the cell's shapes, or None where it
    exports no such function."""
    if facts.get("ssm_gate_norm_route"):
        return facts["ssm_gate_norm_route"]
    try:
        import jax
        import jax.numpy as jnp

        from shuffle_exchange_tpu.ops.ssm_gate_norm import ssm_gate_norm_route

        rows = max(1, facts["batch"] // max(1, facts.get("chips", 1)))
        return ssm_gate_norm_route(jax.ShapeDtypeStruct(
            (rows, facts["seq"], cfg.ssm_heads * cfg.ssm_head_dim), jnp.bfloat16),
            cfg.ssm_groups)
    except Exception:
        return None


def reduce(ctx):
    facts = ctx["result"].get("facts", {})
    cfg, steps = facts.get("model_cfg"), facts.get("traced_steps")
    if cfg is None or not steps or not getattr(cfg, "ssm_heads", 0) \
            or not arith_ssm.layers_of(cfg, "ssm"):
        return None
    tab = xscope.table(ctx)
    rows = xscope.op_self_times(tab) if tab else []
    scope_s = sum(d for _, path, d in rows
                  if "ssm_out_norm" in xscope.components(path)) * 1e-9 / steps
    if scope_s <= 0:
        return None
    # a device's own rows: the epilogue runs per device on its share
    batch = facts["batch"] / max(1, facts.get("chips", 1))
    nbytes = arith_granite4h.gate_norm_bytes_per_step(cfg, batch, facts["seq"])
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    route = _route(cfg, facts)
    harness.emit(phase="ssm_gate_norm_roofline", cell=ctx["cell"]["name"],
                 layers=arith_ssm.layers_of(cfg, "ssm"), groups=cfg.ssm_groups,
                 route=route, required_bytes_per_step=nbytes,
                 scope_ms_per_step=scope_s * 1e3, least_ms_by_bytes=least_s * 1e3,
                 binds="hbm_bytes_per_s", traced_steps=steps)
    return None if route == "xla" else 100.0 * least_s / scope_s
