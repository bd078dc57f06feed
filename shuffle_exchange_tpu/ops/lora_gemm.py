"""Ragged multi-adapter LoRA application for the one-dispatch serving step.

ISSUE 18: a mixed-adapter serving batch applies, per row, the low-rank
delta of whatever adapter that row's request named —

    y[b] += (x[b] @ A[slot[b]]) @ B[slot[b]]

with ``slot`` a per-row index into the :class:`inference.adapters.AdapterPool`
slot axis (slot 0 is the reserved all-zeros "no adapter" slot, so
no-adapter rows ride the same program and add an exact zero). The S-LoRA /
Punica shape (PAPERS.md): adapter identity is per-row DATA — an i32 gather
operand — never a program shape, so a warmed server admits new adapters
with zero recompiles.

Two implementations behind one dispatcher, the streamed-weight decode
kernel idiom (``ops/fused_decode.py``):

- :func:`lora_delta_oracle` — the XLA gather oracle: ``take`` the per-row
  factor pair then two batched einsums with f32 accumulation. Runs on any
  backend; the CPU numerics reference the Pallas kernel is pinned against.
- :func:`lora_delta_pallas` — a Pallas grouped-GEMM kernel: grid over
  rows, the slot indices ride as a scalar-prefetch operand driving the
  factor BlockSpec index maps, so each grid step DMAs exactly its row's
  adapter pair from the pool (rows sharing a slot re-read it from VMEM on
  revisits; no [B, D, R] gather ever materializes in HBM — the bandwidth
  win over the oracle at serving batch sizes).

Per-row results are independent in both paths (the contraction runs over
each row's own d/r axes), so a mixed-adapter batch is bit-identical
per row to a single-adapter batch through the same path — the exact-token
parity contract tests/test_adapters.py pins.

Parity is tested in CPU interpret mode (``SXT_FUSED_INTERPRET=1``) and the
TPU variant is lowering-gated in tests/test_mosaic_lowering.py.
"""

from __future__ import annotations


def lora_delta_oracle(x, a_stack, b_stack, slots):
    """XLA gather path: x [B, T, D], a_stack [S, D, R], b_stack [S, R, N],
    slots [B] i32 -> delta [B, T, N] in x.dtype (f32 accumulation).

    Scaling (lora_alpha / r) is the pool's business — folded into the
    stored B factors at registration — so the kernel seam stays a pure
    ragged grouped GEMM."""
    import jax.numpy as jnp

    a = jnp.take(a_stack, slots, axis=0)               # [B, D, R]
    b = jnp.take(b_stack, slots, axis=0)               # [B, R, N]
    mid = jnp.einsum("btd,bdr->btr", x, a,
                     preferred_element_type=jnp.float32)
    out = jnp.einsum("btr,brn->btn", mid, b.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def lora_delta_pallas(x, a_stack, b_stack, slots, *, interpret: bool = False):
    """Pallas grouped-GEMM path: one grid step per row; ``slots`` is the
    scalar-prefetch operand whose values drive the A/B BlockSpec index
    maps (the Punica-style per-row pool gather, resolved at DMA time)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, D = x.shape
    S, _, R = a_stack.shape
    N = b_stack.shape[-1]

    def kernel(slots_ref, x_ref, a_ref, b_ref, o_ref):
        del slots_ref   # consumed by the index maps
        mid = jax.lax.dot_general(
            x_ref[0], a_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [T, R]
        out = jax.lax.dot_general(
            mid, b_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [T, N]
        o_ref[0] = out.astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, T, D), lambda i, s: (i, 0, 0)),
            pl.BlockSpec((1, D, R), lambda i, s: (s[i], 0, 0)),
            pl.BlockSpec((1, R, N), lambda i, s: (s[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, N), lambda i, s: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        name="sxt_lora_delta",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots.astype(jnp.int32), x, a_stack, b_stack)


def lora_pallas_ok(x, a_stack, b_stack) -> bool:
    """Static Mosaic-eligibility gate for the grouped-GEMM kernel: model
    dims must be lane-aligned and the padded rank sublane-aligned (the
    pool pads ranks to the config's max_rank; tiny test geometries fall
    back to the oracle). Mirrors the fused-decode eligibility idiom —
    shape checks only, decided at trace time."""
    D, R = a_stack.shape[1], a_stack.shape[2]
    N = b_stack.shape[-1]
    return D % 128 == 0 and N % 128 == 0 and R % 8 == 0


def lora_delta(x, a_stack, b_stack, slots):
    """The dispatch seam the engine layer body calls: Pallas when the TPU
    backend is live (or ``SXT_FUSED_INTERPRET=1`` forces interpret mode)
    and the shapes lower, XLA gather oracle otherwise. Resolution goes
    through :func:`ops.dispatch.resolve_grouped_gemm` — the eligibility
    seam shared with ``ops/grouped_gemm.grouped_matmul``."""
    from .dispatch import resolve_grouped_gemm

    mode = resolve_grouped_gemm(
        "lora", shapes_ok=lora_pallas_ok(x, a_stack, b_stack),
        interpret_capable=True)
    if mode == "fallback":
        return lora_delta_oracle(x, a_stack, b_stack, slots)
    return lora_delta_pallas(x, a_stack, b_stack, slots,
                             interpret=mode == "interpret")
