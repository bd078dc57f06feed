"""Kernel dispatch policy: Pallas on TPU by default.

Pallas kernels are the default on a TPU backend (a kernel compiles in a
second or two; ``chip_smoke.py`` phase 1 checks each against its jnp oracle
on the chip); ``SXT_DISABLE_PALLAS=1`` is the kill-switch. Selection is by
backend and shape eligibility only: a kernel that was selected runs or
raises, it is never rescued by its reference.
"""

from __future__ import annotations

import os


def pallas_enabled() -> bool:
    """True when Pallas kernels should be used (TPU backend, not disabled)."""
    if os.environ.get("SXT_DISABLE_PALLAS"):
        return False
    import jax

    return jax.default_backend() == "tpu"


def interpret_forced() -> bool:
    """The ``SXT_FUSED_INTERPRET=1`` test hook: run Pallas kernels through
    the interpreter so the CPU suite drives the kernel path end to end.
    Shared by the fused-decode kernels and the grouped-GEMM seam — one
    contract, one env var (``ops/fused_decode.py::_interpret_forced``
    aliases this)."""
    return bool(os.environ.get("SXT_FUSED_INTERPRET"))


#: grouped-GEMM call sites sharing the eligibility/dispatch seam
#: (ISSUE 19 satellite): the MoE megablox ``gmm`` route and the LoRA
#: per-row pool-gather kernel
_GROUPED_GEMM_KINDS = ("moe", "lora")


def resolve_grouped_gemm(kind: str, *, shapes_ok: bool,
                         interpret_capable: bool = False,
                         quantized: bool = False) -> str:
    """Resolve a grouped-GEMM call site to "pallas", "interpret", or
    "fallback" — the single seam ``ops/grouped_gemm.grouped_matmul``
    (megablox ``gmm`` vs ``lax.ragged_dot``) and ``ops/lora_gemm
    .lora_delta`` (pool-gather kernel vs XLA gather oracle) both resolve
    through, on the same ``SXT_FUSED_INTERPRET``/:func:`pallas_enabled`
    contract as :func:`resolve_decode_kernel`.

    ``shapes_ok`` is the caller's static lane/sublane eligibility
    (``_gmm_ok`` / ``lora_pallas_ok`` — TPU tiling wants lane-aligned
    128 contractions and 8-row sublanes). ``interpret_capable`` says the
    caller's kernel accepts ``interpret=True`` (the LoRA kernel does;
    megablox ``gmm`` offers no interpret hook, so the MoE site falls
    back to ``ragged_dot`` — which IS its numerics oracle — off-TPU).

    ``quantized`` (ISSUE 20 satellite) marks an int8/fp8 streamed-weight
    call (``QuantizedMatrix`` RHS). It never changes the routing — both
    routes admit quantized weights — but a "pallas" resolution gets a
    once-per-process note that the megablox kernel reads dense operands,
    so the dequant materializes before the call instead of fusing into
    the dot as the ragged_dot route does (relevant when comparing the
    two routes' HBM traffic on-chip).
    """
    if kind not in _GROUPED_GEMM_KINDS:
        raise ValueError(f"grouped-GEMM kind must be one of "
                         f"{_GROUPED_GEMM_KINDS}, got {kind!r}")
    from ..utils.logging import warning_once

    if quantized and shapes_ok and pallas_enabled() and not interpret_forced():
        # sxt: ignore[SXT005] kind is one of two literals — dedup cardinality 2
        warning_once(
            f"grouped_gemm[{kind}]: quantized weights on the Pallas "
            f"megablox route dequantize BEFORE the kernel (dense "
            f"operands); the ragged_dot route fuses the convert into the "
            f"dot — measure both if HBM-bound")

    if not shapes_ok:
        if pallas_enabled() or interpret_forced():
            # sxt: ignore[SXT005] kind is one of two literals — dedup cardinality 2
            warning_once(
                f"grouped_gemm[{kind}]: shapes not lane/sublane aligned "
                f"for the Pallas kernel; using the XLA fallback "
                f"(ragged_dot / gather oracle)")
        return "fallback"
    if interpret_forced() and interpret_capable:
        return "interpret"
    if pallas_enabled():
        return "pallas"
    if os.environ.get("SXT_DISABLE_PALLAS"):
        # the explicit kill-switch is the one fallback worth a note — a
        # CPU host falling back is the expected contract (ragged_dot /
        # the gather oracle IS the numerics reference there), same
        # silence as resolve_decode_kernel's "auto" off-TPU
        # sxt: ignore[SXT005] kind is one of two literals — dedup cardinality 2
        warning_once(
            f"grouped_gemm[{kind}]: SXT_DISABLE_PALLAS is set; using the "
            f"XLA fallback (ragged_dot / gather oracle)")
    return "fallback"


def resolve_decode_kernel(mode: str, speculative_k: int = 0) -> str:
    """Resolve the serving ``decode_kernel`` knob to "pallas" or "xla".

    - "xla": always the reference XLA layer body.
    - "pallas": force the fused decode kernels (ops/fused_decode.py) —
      errors surface instead of degrading; on a non-TPU backend this only
      makes sense with SXT_FUSED_INTERPRET=1 (the CPU test hook).
    - "auto": fused kernels iff the backend is TPU (and Pallas isn't
      kill-switched) — the working-fallback contract for CPU/GPU hosts.

    ``speculative_k`` (ISSUE 8 satellite): when speculative serving is
    configured (k >= 1 drafts per tick), the resolution STILL applies to
    the plain 1-token decode rows, but the caller is warned once that
    verify rows — k+1 tokens wide — are outside the fused decode kernels'
    single-token contract and take the paged-extend kernel instead. The
    old behavior would have let a width-(k+1) row reach the fused
    QKV+append (one token written, k silently dropped); the gate makes
    the routing explicit instead of shape-dependent.

    Caveat: the engines' runtime fallbacks catch TRACE-time kernel
    failures; a Mosaic failure at XLA-compile time still surfaces (the
    lowering gate in tests/test_mosaic_lowering.py pins the real serving
    geometries precisely so that class is caught chip-free). Kill
    switches: ``decode_kernel: "xla"`` per engine, ``SXT_DISABLE_PALLAS=1``
    globally.
    """
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'decode_kernel must be "auto", "pallas" or "xla", got {mode!r}')
    resolved = ("pallas" if pallas_enabled() else "xla") if mode == "auto" \
        else mode
    if resolved == "pallas" and speculative_k > 0:
        from ..utils.logging import warning_once

        # sxt: ignore[SXT005] k comes from the serving config, fixed per process — dedup cardinality 1
        warning_once(
            f"decode_kernel resolves to the fused Pallas path with "
            f"speculative k={speculative_k}: verify rows "
            f"({speculative_k + 1} tokens wide) exceed the single-token "
            "fused decode kernels and route through the paged-extend "
            "kernel; fused decode applies to plain decode rows only")
    return resolved
