"""The grouped GEMM's tile is a rule of each product's own (m, k, n)
(``ops/grouped_gemm._gmm_tiling``, PR 66): the module's ``custom_vjp`` asks it
once for the forward ``gmm``, once for the backward ``gmm(transpose_rhs)``
(whose contraction is the forward's output width) and once for ``tgmm`` (the
forward's shape, its own roles). Pure Python: what the rule answers at the
eight expert cells' shapes is what their compiled steps run.
"""

import pytest

from shuffle_exchange_tpu.ops.grouped_gemm import (_GMM_ROWS, _GMM_TILE_CAP, _GMM_VMEM_BYTES,
                                                   _gmm_tiling, _lane_tile, _lane_tiles)

# cell -> rows of the sorted buffer (R; every token-choice in `olmoe-train`),
# held experts, M, F
CELLS = {
    "olmoe-train": (131072, 64, 2048, 1024),
    "lfm2-train": (98304, 8, 2048, 1792),
    "smallthinker-train": (73728, 16, 2560, 768),
    "keyevl2-train": (49152, 16, 2048, 768),
    "kanana2-train": (36864, 16, 2048, 768),
    "qwen3next-train": (30720, 32, 2048, 512),
    "nemotron3-train": (18432, 8, 2688, 1856),
    "laguna-train": (49152, 32, 2048, 512),
}
MATRICES = ("up", "down")                 # [R, M] x [E, M, F] and [R, F] x [E, F, M]
PRODUCTS = ("forward", "backward", "tgmm")


def product_shape(cell, matrix, product):
    """(m, k, n) of the kernel's call: lhs [m, k], n the output's width."""
    R, _, M, F = CELLS[cell]
    k, n = (M, F) if matrix == "up" else (F, M)
    return (R, n, k) if product == "backward" else (R, k, n)


def tile_of(cell, matrix, product):
    return _gmm_tiling(*product_shape(cell, matrix, product),
                       kernel="tgmm" if product == "tgmm" else "gmm")


def vmem_bytes(product, tm, tk, tn, itemsize=2):
    """Two buffers of each operand block and of the output block, one float32
    accumulator, in each kernel's own roles: ``gmm`` [tm, tk] x [tk, tn] ->
    [tm, tn], ``tgmm`` [tm, tk]^T x [tm, tn] -> [tk, tn]."""
    a, b, out = ((tm * tk, tm * tn, tk * tn) if product == "tgmm" else
                 (tm * tk, tk * tn, tm * tn))
    return 2 * itemsize * (a + b + out) + 4 * out


every_product = pytest.mark.parametrize("product", PRODUCTS)
every_matrix = pytest.mark.parametrize("matrix", MATRICES)
every_cell = pytest.mark.parametrize("cell", list(CELLS))


@every_product
@every_matrix
@every_cell
def test_contraction_and_output_tiles_divide_their_dimension(cell, matrix, product):
    """No masked last k-step (``k_rem == 0``) and no clipped output tile: a
    tile is its dimension whole or a multiple of 128 that divides it. 1856
    (14.5 lane tiles) has no such divisor: it is taken whole, or in the
    tiles of 640 that pad it least (3 for 2.9)."""
    _, k, n = product_shape(cell, matrix, product)
    tm, tk, tn = tile_of(cell, matrix, product)
    assert tm in (128, 256)
    for d, t in ((k, tk), (n, tn)):
        assert t == d or (t % 128 == 0 and d % t == 0) or (d, t) == (1856, 640), (d, t)
    if product != "tgmm":
        assert tk == k      # one k-step: a group's weight block is fetched once


@every_product
@every_matrix
@every_cell
def test_every_tile_fits_the_vmem_the_rule_states(cell, matrix, product):
    assert vmem_bytes(product, *tile_of(cell, matrix, product)) <= _GMM_VMEM_BYTES < 16 * 2 ** 20


# ISSUE 66's table (the tiles PR 65's builder read fastest on the chip, each
# product alone): cell -> up forward, down forward, tgmm up, tgmm down. The two
# rows' gradients are the forwards' with k and n swapped.
EXPECTED = {
    "olmoe-train": ((256, 2048, 1024), (256, 1024, 2048), (256, 1024, 1024), (256, 1024, 1024)),
    "lfm2-train": ((256, 2048, 896), (256, 1792, 1024), (256, 1024, 896), (256, 896, 1024)),
    "smallthinker-train": ((256, 2560, 768), (256, 768, 2560), (256, 1280, 768), (256, 768, 1280)),
    "keyevl2-train": ((256, 2048, 768), (256, 768, 2048), (128, 2048, 768), (128, 768, 2048)),
    "kanana2-train": ((256, 2048, 768), (256, 768, 2048), (128, 2048, 768), (128, 768, 2048)),
    "qwen3next-train": ((256, 2048, 512), (256, 512, 2048), (256, 2048, 512), (256, 512, 2048)),
    "nemotron3-train": ((256, 2688, 640), (256, 1856, 896), (128, 896, 1856), (128, 1856, 896)),
    "laguna-train": ((256, 2048, 512), (256, 512, 2048), (256, 2048, 512), (256, 512, 2048)),
}


@every_product
@every_matrix
@every_cell
def test_the_tiles_the_chip_read_fastest(cell, matrix, product):
    """``olmoe-train`` has no path of its own: at ITS shape the whole
    contraction under 256 rows read 11% under PR 28's (512, 1024, 1024) for
    the four ``gmm`` products and 256 rows 2% under 512 for ``tgmm``."""
    up, down, tgmm_up, tgmm_down = EXPECTED[cell]
    want = {("up", "forward"): up, ("down", "forward"): down,
            ("up", "backward"): down, ("down", "backward"): up,
            ("up", "tgmm"): tgmm_up, ("down", "tgmm"): tgmm_down}[matrix, product]
    assert tile_of(cell, matrix, product) == want


@pytest.mark.parametrize("m, tm, cap_tm", [
    (8, 128, 128), (64, 128, 128), (200, 128, 128), (300, 256, 256), (5000, 256, 512)])
def test_decode_rows_keep_the_halving(m, tm, cap_tm):
    """Few rows (the serving engines' decode batches): the row tile halves
    while the rows do not fill it once, the cap's as the rule's; the other
    two follow the dimensions."""
    assert _gmm_tiling(m, 2048, 1024) == (tm, 2048, 1024)
    assert _gmm_tiling(m, 2048, 1024, kernel="tgmm") == (tm, 1024, 1024)
    assert _gmm_tiling(m, 14336, 4096) == (cap_tm, 1024, 1024)


@every_cell
def test_one_padding_serves_the_three_kernels(cell):
    """The rows are padded once, to the larger row tile of the two ``gmm``
    products: every kernel of the call must tile the same padded rows."""
    for matrix in MATRICES:
        rows = [tile_of(cell, matrix, p)[0] for p in PRODUCTS]
        assert all(max(rows[:2]) % r == 0 for r in rows) and CELLS[cell][0] % max(rows) == 0


def test_a_contraction_vmem_cannot_hold_whole_takes_the_cap():
    """Many thousands of columns (no expert of the cells): PR 28's tile, rows
    of 512 under k-steps of 1024, clipped to divisors."""
    assert _GMM_TILE_CAP == (512, 1024, 1024) and _GMM_ROWS == 256
    assert _gmm_tiling(8192, 14336, 4096) == (512, 1024, 1024)
    assert _gmm_tiling(8192, 14336, 1792) == (512, 1024, 896)


@pytest.mark.parametrize("d, cap, want", [
    (512, 1024, 512), (768, 1024, 768), (1024, 1024, 1024), (1792, 1024, 896),
    (2048, 1024, 1024), (2560, 1024, 640), (2688, 1024, 896), (1856, 1024, 640),
    (128, 1024, 128), (4096, 1024, 1024), (1856, 512, 384)])
def test_lane_tile(d, cap, want):
    assert _lane_tile(d, cap) == want


@pytest.mark.parametrize("d, want", [
    (512, [512, 256, 128]), (1792, [1792, 896, 256, 128]), (1856, [1856, 640]),
    (2688, [2688, 896, 384, 128]), (128, [128])])
def test_lane_tiles(d, want):
    assert _lane_tiles(d) == want


def test_the_rule_reads_shapes_alone(monkeypatch):
    """No environment variable and no table keyed by a model: two products of
    one shape get one tile (the down projection's rows' gradient is the up
    projection's forward), and there is one ``custom_vjp`` a process, so a
    layer's three calls trace the kernels' wrappers once."""
    from shuffle_exchange_tpu.ops import grouped_gemm

    monkeypatch.setenv("SXT_GMM_TILE", "128,128,128")
    for cell in CELLS:
        assert tile_of(cell, "up", "forward") == tile_of(cell, "down", "backward")
        assert tile_of(cell, "down", "forward") == tile_of(cell, "up", "backward")
    assert tile_of("kanana2-train", "up", "tgmm") == tile_of("keyevl2-train", "up", "tgmm")
    assert grouped_gemm._gmm_vjp(False) is grouped_gemm._gmm_vjp(False)
    assert not hasattr(grouped_gemm, "_GMM_TILE")


def test_float32_operands_take_smaller_tiles():
    """The element size is part of the product: float32 rows (the kernel
    parity oracle's, a quantized stack's dequantized copy) under the same
    VMEM."""
    for kernel in ("gmm", "tgmm"):
        tile = _gmm_tiling(131072, 2048, 1024, itemsize=4, kernel=kernel)
        assert vmem_bytes(kernel, *tile, itemsize=4) <= _GMM_VMEM_BYTES
        assert tile != _gmm_tiling(131072, 2048, 1024, kernel=kernel)
