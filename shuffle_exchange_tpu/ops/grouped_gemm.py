"""Grouped (ragged) GEMM for MoE experts.

TPU counterpart of the reference's CUTLASS grouped per-expert GEMM
(``inference/v2/kernels/cutlass_ops/moe_gemm/``, SURVEY.md §2.13) and the
megablocks-style dropless training path: tokens sorted by expert, one
matmul whose row-groups select per-expert weight matrices.

Dispatch:
- **TPU**: the Pallas megablox ``gmm`` kernel
  (``jax.experimental.pallas.ops.tpu.megablox``) — MXU-tiled, skips empty
  groups, custom VJP (dx via ``gmm(transpose_rhs)``, dw via ``tgmm``).
  Rows are padded to the row tile and billed to the last group; the
  pad rows are sliced away by the caller's unsort. Tiles: ``_gmm_tiling``.
- **CPU / fallback**: ``jax.lax.ragged_dot`` (also the numerics oracle).

Shape contract: x [N, K] sorted by group, w [E, K, F], group_sizes [E]
(sum == N) -> [N, F].
"""

from __future__ import annotations


def _gmm_ok(x, w) -> bool:
    """megablox tiles are whole lane tiles: K and F are multiples of 128, or
    wider than their tile of ``_GMM_TILE`` (the kernels mask the contraction's
    last, partial tile and clip the output's: experts of 1856, 14.5 lane
    tiles); row padding handles N."""
    N, K = x.shape
    E, K2, F = w.shape
    _, tk, tn = _GMM_TILE
    return (K % 128 == 0 or K > tk) and (F % 128 == 0 or F > tn)


def grouped_matmul(x, w, group_sizes):
    """x [N, K] (rows sorted by group), w [E, K, F], group_sizes [E] int32
    -> [N, F] in x.dtype with fp32 accumulation semantics on TPU.

    ``w`` may be an int8/fp8 :class:`~..ops.quant_matmul.QuantizedMatrix`
    stack (quantized streamed-weight MoE decode, ISSUE 20 satellite): on
    the ``ragged_dot`` path the dequant fuses into the dot's RHS operand —
    expert weights cross HBM at quantized width and convert in registers,
    the same contract as ``quant_matmul``'s default path; the megablox
    kernel reads dense operands, so the Pallas route dequantizes once
    before the call (the at-rest/transfer byte win survives; the compute
    temp is freed after the gmm).

    Eligibility/dispatch resolves through
    :func:`ops.dispatch.resolve_grouped_gemm` — the seam shared with
    ``ops/lora_gemm.lora_delta``. megablox ``gmm`` has no interpret hook,
    so ``interpret_capable`` stays False and every non-TPU resolution is
    "fallback" (``lax.ragged_dot``, which is also the numerics oracle)."""
    from .dispatch import resolve_grouped_gemm
    from .quant_matmul import QuantizedMatrix

    quantized = isinstance(w, QuantizedMatrix)
    route = resolve_grouped_gemm("moe", shapes_ok=_gmm_ok(x, w),
                                 quantized=quantized)
    if quantized:
        w = w.dequantize().astype(x.dtype)
    if route == "pallas":
        return _grouped_matmul_gmm(x, w, group_sizes)
    import jax

    return jax.lax.ragged_dot(x, w, group_sizes)


# (rows, contraction, output) tile of the megablox kernels, forward and
# backward alike. The kernel's own default is (128, 128, 128): a grid step
# then multiplies 4 MFLOP, some tens of nanoseconds of MXU work under a
# fixed per-step cost several times that. Measured on a v5e (PR 28's chip
# runs, PERF.md section 6) on the nine grouped GEMMs of one OLMoE layer's
# training step (131,072 rows, 64 experts, 2048 x 1024), even groups /
# Dirichlet(1) groups: (128, 128, 128) 480 / 508 ms, (256, 512, 512) 57 / 62,
# (512, 512, 512) 46 / 54, (512, 1024, 512) 40 / 45, (256, 1024, 1024)
# 40 / 43, (512, 1024, 1024) 35.7 / 41.8 ms (70% / 60% of the bf16 peak);
# (512, 2048, 1024) and (1024, 1024, 1024) overflow VMEM at compile time.
_GMM_TILE = (512, 1024, 1024)


def _gmm_tiling(n_rows: int, k: int, f: int):
    """The tile for [n_rows, k] x [E, k, f]: ``_GMM_TILE`` clipped to the
    problem. The row tile halves until the rows fill it at least once
    (decode batches are small); rows are then padded to a multiple of it."""
    tm, tk, tn = _GMM_TILE
    while tm > 128 and n_rows < tm:
        tm //= 2
    return tm, min(tk, k), min(tn, f)


def _grouped_matmul_gmm(x, w, group_sizes):
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    N = x.shape[0]
    tiling = _gmm_tiling(N, w.shape[1], w.shape[2])
    pad = -N % tiling[0]
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        # bill pad rows to the last group: they multiply real weights but
        # land in out[N:], which the caller slices away
        group_sizes = group_sizes.at[-1].add(pad)
    out = gmm(x, w, group_sizes.astype(jnp.int32),
              preferred_element_type=x.dtype, tiling=tiling)
    return out[:N] if pad else out
