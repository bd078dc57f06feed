"""Grouped (ragged) GEMM for MoE experts.

TPU counterpart of the reference's CUTLASS grouped per-expert GEMM
(``inference/v2/kernels/cutlass_ops/moe_gemm/``, SURVEY.md §2.13) and the
megablocks-style dropless training path: tokens sorted by expert, one
matmul whose row-groups select per-expert weight matrices.

Dispatch:
- **TPU**: the Pallas megablox kernels
  (``jax.experimental.pallas.ops.tpu.megablox``) — MXU-tiled, skip empty
  groups; the gradient rule is the library's (dx via ``gmm(transpose_rhs)``,
  dw via ``tgmm``) under this module's own ``custom_vjp`` (``_gmm_vjp``), so
  that each of the three kernels runs under the tile of ITS product
  (``_gmm_tiling``: a rule of the product's (m, k, n) and the element size;
  no one tile of the module, no table). Rows are padded to the row tile and
  billed to the last group; the pad rows are sliced away by the caller's
  unsort.
- **CPU / fallback**: ``jax.lax.ragged_dot`` (also the numerics oracle).

Shape contract: x [N, K] sorted by group, w [E, K, F], group_sizes [E]
(sum == N) -> [N, F].
"""

from __future__ import annotations

import functools


def _gmm_ok(x, w) -> bool:
    """megablox tiles are whole lane tiles: K and F are multiples of 128, or
    wider than the cap of their tile, ``_GMM_TILE_CAP`` (``_lane_tile`` then
    finds a tile below them, the kernels mask the contraction's last, partial
    tile and clip the output's: experts of 1856, 14.5 lane tiles); row padding
    handles N."""
    N, K = x.shape
    E, K2, F = w.shape
    _, tk, tn = _GMM_TILE_CAP
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


# The tile of a product whose contraction does not fit VMEM as ONE k-step:
# (rows, contraction, output), each clipped to a divisor of its dimension.
# The kernel's own default is (128, 128, 128): a grid step then multiplies 4
# MFLOP, some tens of nanoseconds of MXU work under a fixed per-step cost
# several times that. Measured on a v5e (PR 28's chip runs, PERF.md section 6)
# on the nine grouped GEMMs of one OLMoE layer's training step (131,072 rows,
# 64 experts, 2048 x 1024), even groups / Dirichlet(1) groups, ONE tile for
# all nine: (128, 128, 128) 480 / 508 ms, (256, 512, 512) 57 / 62, (512, 512,
# 512) 46 / 54, (512, 1024, 512) 40 / 45, (256, 1024, 1024) 40 / 43, (512,
# 1024, 1024) 35.7 / 41.8 ms (70% / 60% of the bf16 peak); (512, 2048, 1024)
# and (1024, 1024, 1024) overflow VMEM at compile time. With two k-steps a
# [tk, tn] weight block is fetched again for every row tile, so the rows a
# tile holds pay for it: 512.
_GMM_TILE_CAP = (512, 1024, 1024)

# The row tile of every other product. The readings below are PR 65's builder's
# chip runs (a PR that was built and never merged, ISSUE 66; v5e: each of a
# layer's six products alone at six cells' shapes, 5 calls' wall time, unaligned
# groups around the balanced share / Dirichlet(1) shares; the parent's (512,
# 1024, 1024) clipped to the forward's k and n in brackets).
# ``gmm`` with the WHOLE contraction as one k-step keeps a group's weight
# block in VMEM over the group's row tiles (fetched once a group, no
# accumulator carried between k-steps), and then 256 rows beat 512 and 128
# from groups of 320 rows to groups of 4,096:
#   [131072, 2048] x [64, 2048, 1024]: (256, 2048, 1024) 3.55 ms, (128, ..)
#     3.66 [4.00]; x [64, 1024, 2048]^T-shaped (k 1024, n 2048): (256, 1024,
#     2048) 3.60, (256, 1024, 1024) 3.74 [3.80]
#   [98304, 2048] x [8, 2048, 1792]: (256, 2048, 896) 1.53, (512, 2048, 896)
#     1.54, (512, 1024, 896) 1.70 [1.90]; k 1792, n 2048: (256, 1792, 1024)
#     1.56, (512, 896, 1024) 1.73 [1.86]
#   [73728, 2560] x [16, 2560, 768]: (256, 2560, 768) 0.77, (512, 1280, 768)
#     0.87, (512, 640, 768) 0.94 [1.03]; k 768, n 2560: (256, 768, 2560) 0.79,
#     (512, 768, 1280) 0.82, (512, 768, 640) 0.89 [1.00]
#   [30720, 2048] x [32, 2048, 512]: (256, 2048, 512) 0.39 [0.47]; k 512, n
#     2048: (256, 512, 2048) 0.43, (128, ..) 0.42 [0.47]
#   [18432, 2688] x [8, 2688, 1856]: (256, 2688, 640) 0.96, (128, 896, 1856)
#     0.92 [1.24]; k 1856, n 2688: (256, 1856, 896) 0.70 [0.95]
# ``tgmm`` reads both operands once a [tk, tn] output tile, so the largest
# output tile VMEM admits wins, at 128 rows where 256 do not fit beside it:
# 2560 x 768: (256, 1280, 768) 0.87 [1.06]; 2048 x 512: (256, 2048, 512) 0.43
# [0.54]; 2048 x 768: (128, 2048, 768) 0.58, (256, 1024, 768) 0.60 [0.64];
# 2688 x 1856: (128, 896, 1856) 0.98, (256, 896, 640) 1.07 [1.24]; 2048 x
# 1024: (256, 1024, 1024) 4.03 [4.10]; 2048 x 1792: (256, 1024, 896) 1.73,
# (512, 1024, 896) 1.69 [1.89].
# Read again by PR 66 (my chip run; PERF.md section 6): value and gradient of
# one expert layer (nine products gated, six ungated) under the rule as it
# stands here against the parent's one tuple, 8 calls' wall time, even /
# Dirichlet(1) groups, ms: lfm2 (98304 rows, 8 x 2048 x 1792) 22.10 / 22.08 ->
# 19.28 / 19.26; smallthinker (73728, 16 x 2560 x 768) 12.57 / 12.58 -> 9.97 /
# 9.91; qwen3next (30720, 32 x 2048 x 512) 5.28 / 5.14 -> 3.69 / 3.58; keyevl2
# (49152, 16 x 2048 x 768) 7.08 / 7.02 -> 5.85 / 5.81; kanana2 (36864, the same
# experts) 5.68 / 5.60 -> 4.65 / 4.61; laguna (49152, 32 x 2048 x 512) 7.05 /
# 6.90 -> 5.17 / 5.09; nemotron3 (18432, 8 x 2688 x 1856, ungated) 5.38 / 5.17
# -> 3.83 / 3.76; olmoe (131072, 64 x 2048 x 1024) 40.94 / 40.92 -> 38.48 /
# 38.41.
_GMM_ROWS = 256

# What a tile may take of the kernels' scoped VMEM (16 MiB on a v5e: the
# library's ``pallas_call``s pass no ``vmem_limit_bytes``) by this count: two
# buffers of each operand block and of the output block, one float32
# accumulator. The rest is the compiler's (the float32 copies the kernels mask
# a tile's rows through). The count is no exact model (PR 65's builder's
# compiles): ``gmm`` (512, 2048, 896) at 14.5 MiB and ``tgmm`` (256, 2048, 768)
# at 14.75 ran; ``gmm`` (512, 2688, 640) at 14.3, (512, 2048, 1024) and ``tgmm``
# (1024, 1024, 1024) at 16.0 were refused. So the tiles the rule gives at the
# cells' shapes are compiled for a described v5e in
# ``tests/test_mosaic_lowering.py``; the largest there count 14.03 (``tgmm``
# (128, 896, 1856)) and 13.25 (``gmm`` (256, 768, 2560)).
_GMM_VMEM_BYTES = 14 * 2 ** 20 + 2 ** 19


def _lane_tile(d: int, cap: int) -> int:
    """The tile of a contraction or output dimension ``d`` under ``cap``: the
    multiple of 128 up to ``cap`` that pads ``d`` least, the largest of those.
    That is the largest one that divides it (no masked last k-step, no clipped
    output tile) where one does; 1856 = 14.5 lane tiles has none and gets 640
    (3 tiles for 2.9 of them, where 1024 ran 2 for 1.8)."""
    return min(range(128, min(cap, d) + 1, 128), key=lambda t: (-(-d // t) * t, -t), default=d)


def _lane_tiles(d: int) -> list:
    """Every tile a dimension may take, widest first: the dimension whole (a
    block as wide as its array is whole whatever its width), then the
    multiples of 128 below it that divide it; where none does,
    :func:`_lane_tile` under the cap's 1024."""
    below = [t for t in range(128 * ((d - 1) // 128), 0, -128) if d % t == 0]
    return [d] + (below or ([_lane_tile(d, _GMM_TILE_CAP[1])] if d > 128 else []))


def _tile_bytes(kernel: str, tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM of one grid step by ``_GMM_VMEM_BYTES``' count, in the kernel's own
    roles: ``gmm`` [tm, tk] x [tk, tn] -> [tm, tn], ``tgmm`` [tm, tk]^T x
    [tm, tn] -> [tk, tn]."""
    a, b, out = ((tm * tk, tm * tn, tk * tn) if kernel == "tgmm" else
                 (tm * tk, tk * tn, tm * tn))
    return 2 * itemsize * (a + b + out) + 4 * out


def _gmm_tiling(m: int, k: int, n: int, itemsize: int = 2, kernel: str = "gmm"):
    """The tile for ONE product, from its own shape: ``gmm`` [m, k] x [groups,
    k, n] (the forward; the rows' gradient is the same kernel with the
    forward's output width as its contraction) or ``tgmm`` [m, k]^T x [m, n]
    -> [groups, k, n] (the weights' gradient; m is its contraction).

    - ``tm``: ``_GMM_ROWS``, halved until the rows fill it once (decode batches
      are small); rows are padded to a multiple of it.
    - ``gmm``: the whole contraction as ONE k-step and the widest output tile
      that VMEM admits beside it (the rows are read once an output tile, a
      group's weights once). Where that leaves no tile of 512 columns (a
      contraction of many thousands), ``_GMM_TILE_CAP``: rows of 512, k-steps
      of 1024.
    - ``tgmm``: the [tk, tn] tile of the output under which the two operands
      are read the fewest times ([m, k] once a tile of n, [m, n] once a tile
      of k), at half the rows where that admits a larger one.

    Every tile divides its dimension wherever a multiple of 128 does (no
    masked k-step, no clipped output tile) or is the dimension whole.
    """
    def rows(tm):
        while tm > 128 and m < tm:
            tm //= 2
        return tm

    def fits(tm, tk, tn):
        return _tile_bytes(kernel, tm, tk, tn, itemsize) <= _GMM_VMEM_BYTES

    tm = rows(_GMM_ROWS)
    if kernel == "gmm":
        for tn in _lane_tiles(n):
            if tn >= min(n, 512) and fits(tm, k, tn):
                return tm, k, tn
        tm, tk, tn = _GMM_TILE_CAP
        return rows(tm), _lane_tile(k, tk), _lane_tile(n, tn)

    def reads(tile):
        _, tk, tn = tile
        tiles_k, tiles_n = -(-k // tk), -(-n // tn)
        return tiles_k * tk * tiles_n + tiles_n * tn * tiles_k

    return min(((half, tk, tn) for half in {tm, max(tm // 2, 128)}
                for tk in _lane_tiles(k) for tn in _lane_tiles(n) if fits(half, tk, tn)),
               key=lambda tile: (reads(tile), -tile[0], -tile[1] * tile[2]))


@functools.lru_cache(maxsize=None)
def _gmm_vjp(interpret: bool):
    """megablox's ``gmm`` with the gradient rule the library gives it (``ops.py``:
    dx = ``gmm(transpose_rhs)``, dw = ``tgmm``), each of the three kernels under
    the tile of ITS product: through the library's own rule all three get one
    ``tiling``, and as a callable ``tgmm`` gets the forward's."""
    import importlib

    import jax

    # the package rebinds its attribute `gmm` to the custom_vjp'd function
    backend = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

    def tile(kernel, x, k, n):
        return _gmm_tiling(x.shape[0], k, n, x.dtype.itemsize, kernel)

    def value(x, w, group_sizes):
        return backend.gmm(x, w, group_sizes, x.dtype, tile("gmm", x, *w.shape[1:]),
                           interpret=interpret)

    def forward(x, w, group_sizes):
        return value(x, w, group_sizes), (x, w, group_sizes)

    def backward(kept, dy):
        x, w, group_sizes = kept
        _, k, n = w.shape
        dx = backend.gmm(dy, w, group_sizes, x.dtype, tile("gmm", dy, n, k),
                         transpose_rhs=True, interpret=interpret)
        dw = backend.tgmm(x.swapaxes(0, 1), dy, group_sizes, w.dtype, tile("tgmm", x, k, n),
                          num_actual_groups=w.shape[0], interpret=interpret)
        return dx, dw, None

    mm = jax.custom_vjp(value)
    mm.defvjp(forward, backward)
    return mm


def _grouped_matmul_gmm(x, w, group_sizes, interpret=False):
    import jax.numpy as jnp

    N, (_, K, F) = x.shape[0], w.shape
    # one padding for the three kernels: the larger row tile of the two gmm
    # products (tgmm's is one of theirs or half of it)
    pad = -N % max(_gmm_tiling(N, k, n, x.dtype.itemsize)[0] for k, n in ((K, F), (F, K)))
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        # bill pad rows to the last group: they multiply real weights but
        # land in out[N:], which the caller slices away
        group_sizes = group_sizes.at[-1].add(pad)
    out = _gmm_vjp(interpret)(x, w, group_sizes.astype(jnp.int32))
    return out[:N] if pad else out
