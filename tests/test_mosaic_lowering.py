"""Chip-free gates for every Pallas kernel, in two strengths.

*Lowering* (``_tpu_lower``, the first half of the file): ``jax.export`` with
``platforms=["tpu"]`` runs the TPU MLIR lowering - block-shape rules,
memory-space constraints, the Mosaic module is built and serialized. It
never runs the Mosaic *compiler*, so a kernel that lowers here can still be
refused on the chip (interpret-mode parity tests check even less: the Python
evaluator never sees a block mapping).

*Compilation* (``chip_compile``, the second half): the TPU compiler
installed here compiles for a v5e that is described, not attached
(``jax.experimental.topologies``), and raises what the chip's compiler would
raise: a DMA slice that is not whole tiles, a kernel that wants more VMEM
than it may use, a program that does not fit HBM. Those cases run at the
widths ``chip_smoke.py`` serves and trains - GPT-2 125M and Llama-3-8B. A
compile that passes is still not a chip run: nothing executes, so results
and times come only from ``chip_smoke.py`` on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
Keep every such test in THIS file (a second file can land on another worker,
whose fixture then skips), and compile in the test's own process.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.test_grouped_gemm_tiles import CELLS as _EXPERT_CELLS    # pure Python: shapes only


def _tpu_lower(fn, *args):
    """Lower ``fn`` for the TPU platform (no TPU backend needed)."""
    from jax import export

    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    return export.export(jax.jit(fn), platforms=["tpu"])(*shapes)


def test_alibi_flash_fwd_and_bwd_lower():
    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.alibi_attention import alibi_flash_attention

    B, T, H, D = 2, 512, 4, 128          # H=4: not a multiple of 8 (the
    q = jnp.zeros((B, T, H, D), jnp.bfloat16)   # case that broke on-chip)
    s = jnp.asarray(alibi_slopes(H), jnp.float32)
    _tpu_lower(lambda q, k, v, s: alibi_flash_attention(q, k, v, s, True, False),
               q, q, q, s)
    _tpu_lower(jax.grad(lambda q, k, v, s: alibi_flash_attention(
        q, k, v, s, True, False).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3)),
        q, q, q, s)


def test_alibi_flash_gqa_rect_lowers():
    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.alibi_attention import alibi_flash_attention

    B, T, S, H, Hkv, D = 1, 256, 512, 4, 2, 128
    q = jnp.zeros((B, T, H, D), jnp.bfloat16)
    kv = jnp.zeros((B, S, Hkv, D), jnp.bfloat16)
    s = jnp.asarray(alibi_slopes(H), jnp.float32)
    _tpu_lower(jax.grad(lambda q, k, v: alibi_flash_attention(
        q, k, v, s, True, False).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        q, kv, kv)


def test_flash_attention_lse_lowers():
    from shuffle_exchange_tpu.ops.alibi_attention import flash_attention_lse

    q = jnp.zeros((1, 512, 4, 128), jnp.bfloat16)

    def loss(q, k, v):
        out, lse = flash_attention_lse(q, k, v, True, False)
        return out.astype(jnp.float32).sum() + lse.sum()

    _tpu_lower(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


@pytest.mark.parametrize("t", [512, 127])
def test_save_flash_lse_policy_lowers(t):
    """The save_flash_lse remat path — jax.checkpoint with the named-seam
    policy around the lse kernel route — must pass the real TPU lowering,
    including the backward that consumes the SAVED out+lse residuals, for
    both exact-tile and ragged (pad-to-128) sequence lengths."""
    from shuffle_exchange_tpu.models.transformer import _remat_policy
    from shuffle_exchange_tpu.ops.flash_attention import flash_attention_remat

    q = jnp.zeros((1, t, 4, 128), jnp.bfloat16)

    def body(q, k, v):
        return flash_attention_remat(q, k, v, True, False).astype(
            jnp.float32).sum()

    f = jax.checkpoint(body, policy=_remat_policy("save_flash_lse"))
    _tpu_lower(jax.grad(f, argnums=(0, 1, 2)), q, q, q)


@pytest.mark.parametrize("with_alibi", [False, True])
def test_paged_decode_and_extend_lower(with_alibi):
    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.paged_attention import (
        paged_decode_attention_pallas, paged_extend_attention_pallas)

    B, H, KV, Dh, bs, nblk = 2, 8, 8, 128, 64, 10
    q1 = jnp.zeros((B, 1, H, Dh), jnp.bfloat16)
    ck = jnp.zeros((nblk, KV, bs, Dh), jnp.bfloat16)
    bt = jnp.zeros((B, 3), jnp.int32)
    kvl = jnp.zeros((B,), jnp.int32)
    sl = jnp.asarray(alibi_slopes(H), jnp.float32) if with_alibi else None
    _tpu_lower(lambda q, k, v, bt, kvl: paged_decode_attention_pallas(
        q, k, v, bt, kvl, alibi_slopes=sl), q1, ck, ck, bt, kvl)

    qc = jnp.zeros((B, 4, H, Dh), jnp.bfloat16)
    st = jnp.zeros((B,), jnp.int32)
    nn = jnp.zeros((B,), jnp.int32)
    _tpu_lower(lambda q, k, v, bt, st, nn: paged_extend_attention_pallas(
        q, k, v, bt, st, nn, alibi_slopes=sl), qc, ck, ck, bt, st, nn)

    # stacked-pool mode: [L, nblk, KV, bs, Dh] + scalar-prefetched layer
    # index (the decode loop's in-place-carry path)
    ck5 = jnp.zeros((3, nblk, KV, bs, Dh), jnp.bfloat16)
    lyr = jnp.zeros((), jnp.int32)
    _tpu_lower(lambda q, k, v, bt, kvl, lyr: paged_decode_attention_pallas(
        q, k, v, bt, kvl, layer=lyr, alibi_slopes=sl), q1, ck5, ck5, bt, kvl, lyr)


@pytest.mark.parametrize("bits", [8, 4, "fp8"])
def test_quant_matmul_lowers(bits):
    from shuffle_exchange_tpu.ops.quant_matmul import (_quant_matmul_pallas,
                                                       quantize_weight)

    w = jnp.asarray(np.random.default_rng(0).standard_normal((512, 256)),
                    jnp.float32)
    qm = quantize_weight(w, group_size=128, bits=bits)
    x = jnp.zeros((64, 512), jnp.float32)
    # nk = K/gs = 4 (not a multiple of 8) — the scales layout that failed
    _tpu_lower(lambda x: _quant_matmul_pallas(x, qm), x)


def test_fused_qkv_rope_lowers():
    from shuffle_exchange_tpu.ops.fused_decode import fused_qkv_rope_pallas

    B, D, H, KV, Dh = 4, 1024, 8, 4, 128
    y = jnp.zeros((B, D), jnp.bfloat16)
    wq = jnp.zeros((D, H * Dh), jnp.bfloat16)
    wkv = jnp.zeros((D, KV * Dh), jnp.bfloat16)
    cos = jnp.zeros((B, Dh // 2), jnp.float32)
    _tpu_lower(lambda y, wq, wk, wv, c, s: fused_qkv_rope_pallas(
        y, wq, wk, wv, cos=c, sin=s, n_heads=H, kv_heads=KV),
        y, wq, wkv, wkv, cos, cos)

    # append form: in-kernel DMA into the aliased paged pool
    pool = jnp.zeros((32, KV, 64, Dh), jnp.bfloat16)
    idx = jnp.zeros((B,), jnp.int32)
    _tpu_lower(lambda y, wq, wk, wv, c, s, pk, pv, blk, off:
               fused_qkv_rope_pallas(y, wq, wk, wv, cos=c, sin=s,
                                     n_heads=H, kv_heads=KV, pool_k=pk,
                                     pool_v=pv, blk=blk, off=off),
               y, wq, wkv, wkv, cos, cos, pool, pool, idx, idx)


@pytest.mark.parametrize("with_alibi", [False, True])
def test_fused_splitk_attention_lowers(with_alibi):
    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.fused_decode import (
        fused_paged_decode_attention_pallas)

    B, H, KV, Dh, bs, nblk = 4, 8, 4, 128, 64, 32
    q = jnp.zeros((B, 1, H, Dh), jnp.bfloat16)
    pool = jnp.zeros((nblk, KV, bs, Dh), jnp.bfloat16)
    bt = jnp.zeros((B, 8), jnp.int32)
    kvl = jnp.zeros((B,), jnp.int32)
    sl = jnp.asarray(alibi_slopes(H), jnp.float32) if with_alibi else None
    _tpu_lower(lambda q, ck, cv, bt, kvl: fused_paged_decode_attention_pallas(
        q, ck, cv, bt, kvl, alibi_slopes=sl, num_splits=2),
        q, pool, pool, bt, kvl)

    # stacked-pool + scalar-prefetched layer index
    pool5 = jnp.zeros((3, nblk, KV, bs, Dh), jnp.bfloat16)
    lyr = jnp.zeros((), jnp.int32)
    _tpu_lower(lambda q, ck, cv, bt, kvl, lyr:
               fused_paged_decode_attention_pallas(
                   q, ck, cv, bt, kvl, layer=lyr, alibi_slopes=sl,
                   num_splits=2), q, pool5, pool5, bt, kvl, lyr)


@pytest.mark.parametrize("bits", [None, 8, 4, "fp8"])
def test_fused_mlp_lowers(bits):
    from shuffle_exchange_tpu.ops.fused_decode import (fused_mlp_pallas,
                                                       fused_mlp_quant_pallas)
    from shuffle_exchange_tpu.ops.quant_matmul import quantize_weight

    B, D, F = 4, 1024, 2048
    resid = jnp.zeros((B, D), jnp.bfloat16)
    lnw = jnp.zeros((D,), jnp.float32)
    if bits is None:
        w = jnp.zeros((D, F), jnp.bfloat16)
        wd = jnp.zeros((F, D), jnp.bfloat16)
        _tpu_lower(lambda r, y, lnw, wu, wd, wg: fused_mlp_pallas(
            r, y, lnw, None, wu, wd, wg, norm="rmsnorm",
            activation="swiglu"), resid, resid, lnw, w, wd, w)
        return
    qg = quantize_weight(np.zeros((D, F), np.float32), group_size=256, bits=bits)
    qd = quantize_weight(np.zeros((F, D), np.float32), group_size=256, bits=bits)
    _tpu_lower(lambda r, y, lnw: fused_mlp_quant_pallas(
        r, y, lnw, None, qg, qd, qg, norm="rmsnorm", activation="swiglu"),
        resid, resid, lnw)


@pytest.mark.parametrize("geom", [
    # llama-style serving: GQA 12 over 3 KV heads at Dh=128, gated MLP
    dict(D=1536, H=12, KV=3, Dh=128, F=4096, bs=64, rope=True, bias=False,
         gated=True),
    # gpt2-style HF serving: Dh=64, MHA, biases, no rope
    dict(D=768, H=12, KV=12, Dh=64, F=3072, bs=64, rope=False, bias=True,
         gated=False),
])
def test_fused_decode_serving_geometries_lower(geom):
    """The exact shapes the serving stack will hand the fused kernels on
    chip (decode_kernel=auto flips TPU serving onto them sight-unseen, so
    the lowering gate must cover the real geometries, not just nice round
    ones)."""
    from shuffle_exchange_tpu.ops.fused_decode import (
        fused_mlp_pallas, fused_paged_decode_attention_pallas,
        fused_qkv_rope_pallas)

    B, D, H, KV, Dh, F, bs = (4, geom["D"], geom["H"], geom["KV"],
                              geom["Dh"], geom["F"], geom["bs"])
    y = jnp.zeros((B, D), jnp.bfloat16)
    wq = jnp.zeros((D, H * Dh), jnp.bfloat16)
    wkv = jnp.zeros((D, KV * Dh), jnp.bfloat16)
    pool = jnp.zeros((64, KV, bs, Dh), jnp.bfloat16)
    idx = jnp.zeros((B,), jnp.int32)
    kw = {}
    if geom["rope"]:
        kw.update(cos=jnp.zeros((B, Dh // 2), jnp.float32),
                  sin=jnp.zeros((B, Dh // 2), jnp.float32))
    if geom["bias"]:
        kw.update(bq=jnp.zeros((H * Dh,), jnp.float32),
                  bk=jnp.zeros((KV * Dh,), jnp.float32),
                  bv=jnp.zeros((KV * Dh,), jnp.float32))
    _tpu_lower(lambda y, wq, wk, wv, pk, pv, blk, off: fused_qkv_rope_pallas(
        y, wq, wk, wv, n_heads=H, kv_heads=KV, pool_k=pk, pool_v=pv,
        blk=blk, off=off, **kw), y, wq, wkv, wkv, pool, pool, idx, idx)

    q = jnp.zeros((B, 1, H, Dh), jnp.bfloat16)
    bt = jnp.zeros((B, 32), jnp.int32)
    kvl = jnp.zeros((B,), jnp.int32)
    _tpu_lower(lambda q, ck, cv, bt, kvl: fused_paged_decode_attention_pallas(
        q, ck, cv, bt, kvl, num_splits=2), q, pool, pool, bt, kvl)

    resid = jnp.zeros((B, D), jnp.bfloat16)
    lnw = jnp.zeros((D,), jnp.float32)
    wu = jnp.zeros((D, F), jnp.bfloat16)
    wd = jnp.zeros((F, D), jnp.bfloat16)
    if geom["gated"]:
        _tpu_lower(lambda r, y, lnw, wu, wd, wg: fused_mlp_pallas(
            r, y, lnw, None, wu, wd, wg, norm="rmsnorm",
            activation="swiglu"), resid, resid, lnw, wu, wd, wu)
    else:
        lnb = jnp.zeros((D,), jnp.float32)
        bu = jnp.zeros((F,), jnp.float32)
        bd = jnp.zeros((D,), jnp.float32)
        _tpu_lower(lambda r, y, lnw, lnb, wu, wd, bu, bd: fused_mlp_pallas(
            r, y, lnw, lnb, wu, wd, None, b_up=bu, b_down=bd,
            norm="layernorm", activation="gelu_new"),
            resid, resid, lnw, lnb, wu, wd, bu, bd)


def test_rmsnorm_lowers():
    from shuffle_exchange_tpu.ops.rmsnorm import rmsnorm

    x = jnp.zeros((4, 256, 512), jnp.float32)
    w = jnp.zeros((512,), jnp.float32)
    _tpu_lower(jax.grad(lambda x, w: rmsnorm(x, w).sum(), argnums=(0, 1)), x, w)


def _expert_layer_products(cell):
    """Value and gradient of a cell's two expert products, [R, M] x [E, M, F]
    and [R, F] x [E, F, M], each kernel under its own product's tile: forward
    ``gmm``, backward ``gmm(transpose_rhs)``, ``tgmm``."""
    from shuffle_exchange_tpu.ops.grouped_gemm import _grouped_matmul_gmm

    R, E, M, F = _EXPERT_CELLS[cell]
    for K, W in ((M, F), (F, M)):
        yield (jax.value_and_grad(lambda x, w, gs: _grouped_matmul_gmm(
            x, w, gs).astype(jnp.float32).sum() ** 2, argnums=(0, 1)),
            ((R, K), jnp.bfloat16), ((E, K, W), jnp.bfloat16), ((E,), jnp.int32))


def test_grouped_gemm_lowers():
    from shuffle_exchange_tpu.ops.grouped_gemm import _grouped_matmul_gmm

    x = jnp.zeros((1000, 256), jnp.bfloat16)
    w = jnp.zeros((4, 256, 384), jnp.bfloat16)
    gs = jnp.zeros((4,), jnp.int32)
    _tpu_lower(jax.grad(lambda x, w: _grouped_matmul_gmm(
        x, w, gs).astype(jnp.float32).sum() ** 2, argnums=(0, 1)), x, w)


@pytest.mark.parametrize("cell", list(_EXPERT_CELLS))
def test_grouped_gemm_lowers_at_the_expert_cells_shapes(cell):
    """Value and gradient under the tile each product's own (m, k, n) gets
    (PR 66): block shapes that are no whole (8, 128) tiles of their arrays, or
    a row tile that does not divide the padded rows, are refused here."""
    for fn, *specs in _expert_layer_products(cell):
        exported = _tpu_lower(fn, *[jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs])
        assert exported.mlir_module().count("tpu_custom_call") == 3


def test_lora_grouped_gemm_lowers():
    """Multi-tenant LoRA ragged grouped-GEMM (ISSUE 18): the per-row
    scalar-prefetch slot gather driving the factor BlockSpec index maps
    must pass the real Mosaic block checks at the serving decode shape
    (T=1) and at a prefill-chunk shape — slot indices are data, so one
    lowering covers every adapter mix."""
    from shuffle_exchange_tpu.ops.lora_gemm import (lora_delta_pallas,
                                                    lora_pallas_ok)

    S, D, R, N = 5, 256, 8, 128
    a = jnp.zeros((S, D, R), jnp.bfloat16)
    b = jnp.zeros((S, R, N), jnp.bfloat16)
    slots = jnp.zeros((4,), jnp.int32)
    assert lora_pallas_ok(jnp.zeros((4, 1, D), jnp.bfloat16), a, b)
    for T in (1, 8):
        x = jnp.zeros((4, T, D), jnp.bfloat16)
        _tpu_lower(lambda x, a, b, s: lora_delta_pallas(x, a, b, s),
                   x, a, b, slots)


def _gated_delta_vjp(q, k, v, g, beta, cotangent):
    """o and the five gradients through the rule's kernels themselves (the
    dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.gated_delta import _gated_delta_pallas

    o, back = jax.vjp(_gated_delta_pallas, q, k, v, g, beta)
    return (o,) + back(cotangent)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gated_delta_rule_lowers(dtype):
    """Forward (o alone), the forward that keeps S0, and the backward; a
    ragged tail; bf16 and float32 operands."""
    from shuffle_exchange_tpu.ops.gated_delta import _gated_delta_pallas

    B, T, H, d = 2, 150, 8, 128
    wide = jnp.zeros((B, T, H, d), dtype)
    flat = jnp.zeros((B, T, H), jnp.float32)
    _tpu_lower(_gated_delta_pallas, wide, wide, wide, flat, flat)
    _tpu_lower(_gated_delta_vjp, wide, wide, wide, flat, flat,
               jnp.zeros((B, T, H, d), jnp.float32))


def _kda_vjp(q, k, v, g, beta, cotangent):
    """o and the five gradients (dg per channel) through the KDA rule's
    kernels themselves."""
    from shuffle_exchange_tpu.ops.kda import _kda_pallas

    o, back = jax.vjp(_kda_pallas, q, k, v, g, beta)
    return (o,) + back(cotangent)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_rule_lowers(dtype):
    """The rule with a decay a key channel: forward (o alone), the forward
    that keeps S0, and the backward; a ragged tail; bf16 and float32."""
    from shuffle_exchange_tpu.ops.kda import _kda_pallas

    B, T, H, d = 2, 150, 8, 128
    wide = jnp.zeros((B, T, H, d), dtype)
    g = jnp.zeros((B, T, H, d), jnp.float32)
    flat = jnp.zeros((B, T, H), jnp.float32)
    _tpu_lower(_kda_pallas, wide, wide, wide, g, flat)
    _tpu_lower(_kda_vjp, wide, wide, wide, g, flat,
               jnp.zeros((B, T, H, d), jnp.float32))


def _gdn_prologue_vjp(qkvz, conv_w, dq, dk, dv, dz, heads=(16, 128, 128)):
    """q, k, v, z and the two gradients through the prologue's kernels
    themselves (the dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.gated_delta import _gdn_prologue_pallas

    out, back = jax.vjp(lambda x, w: _gdn_prologue_pallas(x, w, *heads), qkvz, conv_w)
    return out + back((dq, dk, dv, dz))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gdn_prologue_lowers(dtype):
    """The forward and the backward launch; a ragged last block of rows;
    bf16 and float32 activations; one value head a key head and two; heads
    of whole lane tiles and Olmo-Hybrid's 30 of 96 / 192 (two key heads a
    grid step, segments between lane tiles)."""
    import functools

    for Hk, dk, dv, rep, T in ((2, 128, 128, 2, 600), (2, 128, 128, 1, 100),
                               (30, 96, 192, 1, 600)):
        K = 4
        qkvz = jnp.zeros((2, T, Hk * (2 * dk + 2 * rep * dv)), dtype)
        conv_w = jnp.zeros((K, Hk * (2 * dk + rep * dv)), jnp.float32)
        wide = lambda d: jnp.zeros((2, T, Hk * rep, d), dtype)
        _tpu_lower(functools.partial(_gdn_prologue_vjp, heads=(Hk, dk, dv)),
                   qkvz, conv_w, wide(dk), wide(dk), wide(dv), wide(dv))


def _kda_prologue_vjp(qkv, conv_w, dq, dk, dv, heads=(32, 128, 128)):
    """q, k, v and the two gradients through the KDA prologue's kernels
    themselves (``gdn_prologue``'s bodies on three column ranges; the
    dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.kda import _kda_prologue_pallas

    out, back = jax.vjp(lambda x, w: _kda_prologue_pallas(x, w, *heads), qkv, conv_w)
    return out + back((dq, dk, dv))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_prologue_lowers(dtype):
    """The forward and the backward launch on the four-axis grid; a ragged
    last block of rows; bf16 and float32 activations; four heads a grid
    step, three (6 heads), one, and two heads of two lane tiles."""
    for H, d, T in ((8, 128, 600), (6, 128, 100), (1, 128, 600), (2, 256, 100)):
        qkv = jnp.zeros((2, T, 3 * H * d), dtype)
        conv_w = jnp.zeros((4, 3 * H * d), jnp.float32)
        wide = jnp.zeros((2, T, H, d), dtype)
        _tpu_lower(functools.partial(_kda_prologue_vjp, heads=(H, d, d)),
                   qkv, conv_w, wide, wide, wide)


def _ssd_vjp(x, dt, A, B, C, cotangent):
    """o and the five gradients through the scan's kernels themselves (the
    dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.ssd import CHUNK, _ssd_pallas

    o, back = jax.vjp(lambda *a: _ssd_pallas(*a, CHUNK, False), x, dt, A, B, C)
    return (o,) + back(cotangent)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("P", [64, 128])
def test_ssd_scan_lowers(dtype, P):
    """Forward (o alone), the forward that keeps the
    chunks' states, and the backward; a ragged tail; two heads a lane tile and
    one; bf16 and float32 operands."""
    from shuffle_exchange_tpu.ops.ssd import CHUNK, _ssd_pallas

    Bt, T, H, G, N = 2, 300, 8, 2, 128
    x = jnp.zeros((Bt, T, H, P), dtype)
    dt, A = jnp.zeros((Bt, T, H), jnp.float32), jnp.zeros((H,), jnp.float32)
    group = jnp.zeros((Bt, T, G, N), dtype)
    _tpu_lower(lambda *a: _ssd_pallas(*a, CHUNK, False), x, dt, A, group, group)
    _tpu_lower(_ssd_vjp, x, dt, A, group, group, x)


def _ssm_conv_vjp(zxbcdt, conv_w, conv_b, dz, dx, dB, dC, ddt, start=4096,
                  widths=(4096, 1024, 1024)):
    """z, x, B, C, dt and the three gradients through the convolution's
    kernels themselves (the dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.ssm_conv import _ssm_conv_pallas

    out, back = jax.vjp(lambda *a: _ssm_conv_pallas(*a, start, widths),
                        zxbcdt, conv_w, conv_b)
    return out + back((dz, dx, dB, dC, ddt))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssm_conv_lowers(dtype):
    """The forward and the backward launch of every segment; a ragged last
    block of rows; bf16 and float32 activations; segments one lane tile wide
    and three (a lane block of 384), four taps and two."""
    import functools

    for (start, widths), T, K in (((256, (256, 128, 128)), 1100, 4),
                                  ((384, (384, 384, 768)), 100, 2)):
        zxbcdt = jnp.zeros((2, T, start + sum(widths) + 8), dtype)
        cotangents = [jnp.zeros((2, T, n), dtype) for n in (start, *widths, 8)]
        _tpu_lower(functools.partial(_ssm_conv_vjp, start=start, widths=widths),
                   zxbcdt, jnp.zeros((K, sum(widths)), jnp.float32),
                   jnp.zeros((sum(widths),), jnp.float32), *cotangents)


def _sconv_mix_vjp(bcx, w, dy):
    """The pass and its three gradients through the kernels themselves (the
    dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.short_conv import _sconv_mix_pallas

    out, back = jax.vjp(_sconv_mix_pallas, bcx, w)
    return (out,) + back(dy)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_sconv_mix_lowers(dtype):
    """The forward and the backward launch; one row block a sequence and
    three; bf16 and float32 activations; three taps and two."""
    for T, C, K in ((1536, 256, 3), (64, 384, 2)):
        _tpu_lower(_sconv_mix_vjp, jnp.zeros((2, T, 3 * C), dtype),
                   jnp.zeros((K, C), dtype), jnp.zeros((2, T, C), dtype))


@pytest.mark.parametrize("store", [jnp.int8, jnp.float8_e4m3fn])
def test_paged_kernels_quantized_kv_lower(store):
    """kv_cache_dtype int8/fp8 (ISSUE 6): every streaming kernel that
    dequantizes scale planes in-register must pass the real Mosaic block
    checks — the (…, 1, bs) scale block leans on the singleton-second-
    minor trick, which only the TPU lowering validates."""
    from shuffle_exchange_tpu.ops.fused_decode import (
        fused_paged_decode_attention_pallas)
    from shuffle_exchange_tpu.ops.paged_attention import (
        paged_decode_attention_pallas, paged_extend_attention_pallas)

    B, H, KV, Dh, bs, nblk, L = 2, 8, 4, 128, 64, 10, 3
    q1 = jnp.zeros((B, 1, H, Dh), jnp.bfloat16)
    ck = jnp.zeros((nblk, KV, bs, Dh), store)
    sc = jnp.zeros((nblk, KV, bs), jnp.float32)
    bt = jnp.zeros((B, 3), jnp.int32)
    kvl = jnp.zeros((B,), jnp.int32)
    _tpu_lower(lambda q, k, v, ks, vs, bt, kvl: paged_decode_attention_pallas(
        q, k, v, bt, kvl, k_scale=ks, v_scale=vs), q1, ck, ck, sc, sc, bt, kvl)

    qc = jnp.zeros((B, 4, H, Dh), jnp.bfloat16)
    st = jnp.zeros((B,), jnp.int32)
    nn = jnp.zeros((B,), jnp.int32)
    _tpu_lower(lambda q, k, v, ks, vs, bt, st, nn: paged_extend_attention_pallas(
        q, k, v, bt, st, nn, k_scale=ks, v_scale=vs),
        qc, ck, ck, sc, sc, bt, st, nn)

    # stacked pools (the decode loop's in-place-carry mode): per-kv-head
    # streaming decode AND the all-kv-head split-K flash form
    ck5 = jnp.zeros((L, nblk, KV, bs, Dh), store)
    sc5 = jnp.zeros((L, nblk, KV, bs), jnp.float32)
    lyr = jnp.zeros((), jnp.int32)
    _tpu_lower(lambda q, k, v, ks, vs, bt, kvl, lyr:
               paged_decode_attention_pallas(
                   q, k, v, bt, kvl, layer=lyr, k_scale=ks, v_scale=vs),
               q1, ck5, ck5, sc5, sc5, bt, kvl, lyr)
    _tpu_lower(lambda q, k, v, ks, vs, bt, kvl, lyr:
               fused_paged_decode_attention_pallas(
                   q, k, v, bt, kvl, layer=lyr, k_scale=ks, v_scale=vs,
                   num_splits=2), q1, ck5, ck5, sc5, sc5, bt, kvl, lyr)


# ---------------------------------------------------------------------------
# Real compiles for a described v5e (see the module docstring)
# ---------------------------------------------------------------------------

GPT2 = dict(D=768, H=12, KV=12, Dh=64, F=3072, V=50257, rope=False,
            bias=True, gated=False)
def _ssm_gate_norm_vjp(o, x, z, D, gain, dout, groups=8):
    """The output and the five gradients through the epilogue's kernels
    themselves (the dispatching entry takes the XLA form off a TPU)."""
    from shuffle_exchange_tpu.ops.ssm_gate_norm import _ssm_gate_norm_pallas

    out, back = jax.vjp(lambda *a: _ssm_gate_norm_pallas(*a, groups, 1e-5), o, x, z, D, gain)
    return (out,) + back(dout)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssm_gate_norm_lowers(dtype):
    """The forward and the backward launch; a ragged last block of rows;
    bf16 and float32 activations; groups of one lane tile (two a lane block)
    and of three (a lane block of 384)."""
    import functools

    for inner, groups, H, T in ((512, 4, 8, 1100), (768, 2, 6, 100)):
        rows = jnp.zeros((2, T, inner), dtype)
        _tpu_lower(functools.partial(_ssm_gate_norm_vjp, groups=groups), rows, rows, rows,
                   jnp.zeros((H,), jnp.float32), jnp.zeros((inner,), jnp.float32), rows)


def _expert_act_vjp(fit, dh, *arrays, activation="swiglu"):
    """The pass and its cotangents through the kernels themselves (the
    dispatching entry takes the text off a TPU): ``arrays`` is (gate, up) or
    (up,)."""
    from shuffle_exchange_tpu.ops.expert_act import _expert_act_pallas

    out, back = jax.vjp(lambda *a: _expert_act_pallas(a, fit, activation), *arrays)
    return (out,) + back(dh)


@pytest.mark.parametrize("activation, inputs", [("swiglu", 2), ("reglu", 2), ("relu2", 1),
                                                ("silu", 1), ("relu", 1)])
def test_expert_act_lowers(activation, inputs):
    """The forward and the backward launch of every activation the kernels
    take; whole lane tiles and a width of two and a half; whole row blocks
    and a ragged last one."""
    import functools

    for rows, width in ((1024, 256), (1300, 320)):
        block = jnp.zeros((rows, width), jnp.bfloat16)
        _tpu_lower(functools.partial(_expert_act_vjp, activation=activation),
                   jnp.zeros((), jnp.int32), block, *[block] * inputs)


LLAMA = dict(D=4096, H=32, KV=8, Dh=128, F=14336, V=128256, rope=True,
             bias=False, gated=True)
GEOMS = {"gpt2-125m": GPT2, "llama3-8b": LLAMA}
_BF16, _F32, _I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip):
    """``chip_compile(fn, *(shape, dtype), donate=())`` -> the executable
    compiled for one described v5e chip; asserts the kernel is still in it.
    The persistent cache is off around it: an executable compiled for a
    chip that is not attached cannot be read back and only draws warnings."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *specs, donate=()):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), \
            "the Pallas kernel is not in the compiled program"
        return compiled

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("B", [1, 8, 64])
@pytest.mark.parametrize("append", [False, True], ids=["plain", "append"])
@pytest.mark.parametrize("model", list(GEOMS))
def test_fused_qkv_rope_compiles(chip_compile, model, append, B):
    """Both refusals of the bring-up live here: the append form was refused
    at every geometry (one-row DMA slices are not whole (8, 128) tiles)."""
    from shuffle_exchange_tpu.ops.fused_decode import fused_qkv_rope_pallas

    g = GEOMS[model]
    D, H, KV, Dh = g["D"], g["H"], g["KV"], g["Dh"]
    specs = [((B, D), _BF16), ((D, H * Dh), _BF16), ((D, KV * Dh), _BF16),
             ((D, KV * Dh), _BF16)]
    names = ["y", "wq", "wk", "wv"]
    if g["rope"]:
        specs += [((B, Dh // 2), _F32)] * 2
        names += ["cos", "sin"]
    if g["bias"]:
        specs += [((H * Dh,), _F32), ((KV * Dh,), _F32), ((KV * Dh,), _F32)]
        names += ["bq", "bk", "bv"]
    donate = ()
    if append:
        donate = (len(specs), len(specs) + 1)
        specs += [((128, KV, 64, Dh), _BF16)] * 2 + [((B,), _I32)] * 2
        names += ["pool_k", "pool_v", "blk", "off"]

    def fn(*a):
        kw = dict(zip(names, a))
        return fused_qkv_rope_pallas(kw.pop("y"), kw.pop("wq"), kw.pop("wk"),
                                     kw.pop("wv"), n_heads=H, kv_heads=KV,
                                     **kw)

    chip_compile(fn, *specs, donate=donate)


@pytest.mark.parametrize("stacked", [False, True], ids=["flat", "stacked"])
@pytest.mark.parametrize("model", list(GEOMS))
def test_fused_splitk_attention_compiles(chip_compile, model, stacked):
    from shuffle_exchange_tpu.ops.fused_decode import (
        fused_paged_decode_attention_pallas)

    g = GEOMS[model]
    B, H, KV, Dh, bs, nblk = 8, g["H"], g["KV"], g["Dh"], 64, 128
    pool = ((4,) if stacked else ()) + (nblk, KV, bs, Dh)
    specs = [((B, 1, H, Dh), _BF16), (pool, _BF16), (pool, _BF16),
             ((B, 32), _I32), ((B,), _I32)]
    if stacked:
        specs.append(((), _I32))
    chip_compile(lambda q, ck, cv, bt, kvl, *lyr:
                 fused_paged_decode_attention_pallas(
                     q, ck, cv, bt, kvl, num_splits=2,
                     **({"layer": lyr[0]} if lyr else {})), *specs)


@pytest.mark.parametrize("B", [1, 16, 64])
def test_fused_mlp_compiles(chip_compile, B):
    """Llama-3-8B widths, the second refusal: at B=64 the fixed F-chunk and
    the default scoped VMEM limit ran the kernel out of fast memory."""
    from shuffle_exchange_tpu.ops.fused_decode import fused_mlp_pallas

    D, F = LLAMA["D"], LLAMA["F"]
    chip_compile(lambda r, y, lnw, wu, wd, wg: fused_mlp_pallas(
        r, y, lnw, None, wu, wd, wg, norm="rmsnorm", activation="swiglu"),
        ((B, D), _BF16), ((B, D), _BF16), ((D,), _F32), ((D, F), _BF16),
        ((F, D), _BF16), ((D, F), _BF16))


@pytest.mark.parametrize("model", list(GEOMS))
def test_paged_decode_and_extend_compile(chip_compile, model):
    from shuffle_exchange_tpu.ops.paged_attention import (
        paged_decode_attention_pallas, paged_extend_attention_pallas)

    g = GEOMS[model]
    B, H, KV, Dh, bs, nblk, C = 8, g["H"], g["KV"], g["Dh"], 64, 128, 64
    pool = ((nblk, KV, bs, Dh), _BF16)
    chip_compile(lambda q, k, v, bt, kvl: paged_decode_attention_pallas(
        q, k, v, bt, kvl), ((B, 1, H, Dh), _BF16), pool, pool,
        ((B, 32), _I32), ((B,), _I32))
    chip_compile(lambda q, k, v, bt, st, nn: paged_extend_attention_pallas(
        q, k, v, bt, st, nn), ((B, C, H, Dh), _BF16), pool, pool,
        ((B, 32), _I32), ((B,), _I32), ((B,), _I32))


@pytest.mark.parametrize("model,T", [("gpt2-125m", 1024), ("llama3-8b", 2048)])
def test_flash_attention_fwd_bwd_compiles(chip_compile, model, T):
    """The trainer's attention at the smoke's sequence lengths: the splash
    MQA kernel for Llama's 32/8 GQA and, at a group of one, for GPT-2's MHA
    (PR 56: the call is made on one device)."""
    from shuffle_exchange_tpu.ops.flash_attention import pallas_attention

    g = GEOMS[model]
    q = ((1, T, g["H"], g["Dh"]), _BF16)
    kv = ((1, T, g["KV"], g["Dh"]), _BF16)
    chip_compile(jax.grad(lambda q, k, v: pallas_attention(
        q, k, v, causal=True).astype(_F32).sum(), argnums=(0, 1, 2)),
        q, kv, kv)


@pytest.mark.parametrize("heads, window", [(64, 512), (48, 0)],
                         ids=["window-64-heads", "full-48-heads"])
def test_splash_attention_at_16k_compiles(chip_compile, heads, window):
    """The two attention kinds of a window / full hybrid stack at 16,384
    positions over 8 KV heads of 128 (PR 39): the splash kernels under the
    local causal mask of a 512-key window at 512-blocks (groups of 8), and
    under the causal mask at 1024-blocks (groups of 6), forward and
    backward."""
    from shuffle_exchange_tpu.ops.flash_attention import pallas_attention

    q = ((1, 16384, heads, 128), _BF16)
    kv = ((1, 16384, 8, 128), _BF16)
    chip_compile(jax.grad(lambda q, k, v: pallas_attention(
        q, k, v, causal=True, window=window).astype(_F32).sum(), argnums=(0, 1, 2)),
        q, kv, kv)


# the softmax cells' attention calls at their REAL shapes (PR 43):
# batch, T, query heads, key heads, score width, value width, window
_CELL_ATTENTION = {
    "kanana2-train": (2, 8192, 32, 32, 192, 128, 0),
    "laguna-train-full": (1, 16384, 48, 8, 128, 128, 0),
    "laguna-train-window": (1, 16384, 64, 8, 128, 128, 512),
    "qwen3next-train": (1, 8192, 16, 2, 256, 256, 0),
    "lfm2-train": (8, 4096, 32, 8, 64, 64, 0),
    "mistral7b-zero3-x4": (1, 4096, 32, 8, 128, 128, 0),
    # MHA on one device, a group of one (PR 56)
    "gpt2m-train": (4, 1024, 16, 16, 64, 64, 0),
    "olmoe-train": (4, 4096, 16, 16, 128, 128, 0),
    # 7 query heads a KV head, the first odd group above 1, and a window of
    # 4096 = 8 key blocks of 512 and the diagonal (PR 57)
    "smallthinker-train-full": (1, 16384, 28, 4, 128, 128, 0),
    "smallthinker-train-window": (1, 16384, 28, 4, 128, 128, 4096),
    # olmoe-train's route (MHA, 16 heads of 128, a group of one) at one
    # sequence of 8,192: every visit of the looped stack (PR 64)
    "ouro-train": (1, 8192, 16, 16, 128, 128, 0),
}


def _kernel_launches(compiled):
    """kernel name -> custom calls of it in a compiled program's text."""
    import collections
    import re

    return collections.Counter(re.findall(
        r'%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text()))


@pytest.mark.parametrize("cell", list(_CELL_ATTENTION))
def test_fused_attention_backward_compiles_at_the_cells_shapes(chip_compile, cell):
    """The splash routes' backward as one kernel (``ops/splash_backward``):
    dk and dv of a key head resident in VMEM in float32 (12-16 MiB at
    these shapes) beside the tiles of a block pair, within what the kernel
    asks Mosaic for: an overflow fails here, before any chip time. The
    compiled gradient holds the library's forward kernel, the fused backward
    and neither of the library's backward kernels."""
    from shuffle_exchange_tpu.ops.flash_attention import (
        attention_backward_route, pallas_attention)
    from shuffle_exchange_tpu.ops.splash_backward import KERNEL_NAME

    B, T, H, KV, D, Dv, window = _CELL_ATTENTION[cell]
    q, k, v = ((B, T, H, D), _BF16), ((B, T, KV, D), _BF16), ((B, T, KV, Dv), _BF16)
    assert attention_backward_route(*(jax.ShapeDtypeStruct(*x) for x in (q, k, v)),
                                    True, window) == "fused_resident_dkv"
    compiled = chip_compile(jax.grad(lambda q, k, v: pallas_attention(
        q, k, v, causal=True, window=window).astype(_F32).sum(), argnums=(0, 1, 2)),
        q, k, v)
    assert _kernel_launches(compiled) == {
        "splash_mqa_fwd_residuals": 1, KERNEL_NAME: 1}


def test_the_per_shard_mha_call_compiles_the_stock_kernels(chip_compile, topo):
    """``olmohybrid-zero3-x4``'s attention layer as one shard of ZeRO-3's
    mesh over the four described chips runs it, 2 x 8192 x 30 heads of 128:
    MHA on a kernel mesh of several devices keeps the stock flash family
    (``_pallas_kernel``), forward and its two backward kernels."""
    from shuffle_exchange_tpu.config.config import MeshConfig
    from shuffle_exchange_tpu.ops.flash_attention import pallas_attention
    from shuffle_exchange_tpu.parallel.mesh import MeshTopology, kernel_mesh

    mesh = MeshTopology.build(MeshConfig(fsdp=4), devices=list(topo.devices)).mesh
    q = ((2, 8192, 30, 128), _BF16)

    def grads(q, k, v):
        with kernel_mesh(mesh):
            return jax.grad(lambda q, k, v: pallas_attention(
                q, k, v, causal=True).astype(_F32).sum(), argnums=(0, 1, 2))(q, k, v)

    launches = _kernel_launches(chip_compile(grads, q, q, q))
    assert sum(launches.values()) == 3 and not any(
        "splash" in name for name in launches), launches


@pytest.mark.parametrize("route", ["fused_resident_dkv", "splash_two_kernels"])
def test_full_remat_replays_no_forward_kernel_before_either_backward(
        chip_compile, route, monkeypatch):
    """A mixer half's checkpoint under "full" remat with
    ``_keeping_splash_residuals`` (PR 36), compiled for the chip: the forward
    kernel is in the program once, not again in the replay, whichever
    backward the call takes."""
    import importlib

    from shuffle_exchange_tpu.models.transformer import (
        _keeping_splash_residuals, _remat_policy)
    from shuffle_exchange_tpu.ops.splash_backward import KERNEL_NAME

    fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "attention_backward_route", lambda *a, **kw: route)
    half = jax.checkpoint(
        lambda q, k, v: fa.pallas_attention(q, k, v, causal=True),
        policy=_keeping_splash_residuals(_remat_policy("full")))
    q, kv = ((1, 4096, 32, 128), _BF16), ((1, 4096, 8, 128), _BF16)
    compiled = chip_compile(jax.grad(lambda q, k, v: half(q, k, v).astype(
        _F32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    backward = ({KERNEL_NAME: 1} if route == "fused_resident_dkv" else
                {"splash_mqa_dkv_no_residuals": 1, "splash_mqa_dq_no_residuals": 1})
    assert _kernel_launches(compiled) == {"splash_mqa_fwd_residuals": 1, **backward}


@pytest.mark.parametrize("rows", [(2, 2048), (8, 1)],
                         ids=["train-rows", "decode-rows"])
def test_rmsnorm_compiles(chip_compile, rows):
    """Llama's d 4096 in float32 (``_norm`` upcasts): at >= 256 rows the
    fixed 256-row block was refused by 16 KiB of VMEM (the third refusal of
    the bring-up, found by this very case)."""
    from shuffle_exchange_tpu.ops.rmsnorm import _rmsnorm_vjp

    # value AND grad: the backward is analytic jnp and never reads the
    # forward's output, so under grad alone XLA drops the kernel
    D = LLAMA["D"]
    chip_compile(jax.value_and_grad(
        lambda x, w: _rmsnorm_vjp(x, w, 1e-5).sum(), argnums=(0, 1)),
        (rows + (D,), _F32), ((D,), _F32))


def test_grouped_gemm_fwd_bwd_compiles(chip_compile):
    """megablox gmm at the R1 cell's expert geometry (64 experts, d 2048,
    expert width 1024), forward and both backward kernels."""
    from shuffle_exchange_tpu.ops.grouped_gemm import _grouped_matmul_gmm

    E, K, F, N = 64, 2048, 1024, 4096
    chip_compile(jax.grad(lambda x, w, gs: _grouped_matmul_gmm(
        x, w, gs).astype(_F32).sum() ** 2, argnums=(0, 1)),
        ((N, K), _BF16), ((E, K, F), _BF16), ((E,), _I32))


@pytest.mark.parametrize("bits", [8, 4, "fp8"])
def test_quantized_serving_kernels_compile(chip_compile, bits):
    """Weight-only serving at Llama widths: the streamed-dequant matmul and
    the fused MLP over ``QuantizedMatrix`` storage (int4 at the kernel's
    own group size, so its packed row pairs stay whole sublane tiles)."""
    from shuffle_exchange_tpu.ops.fused_decode import fused_mlp_quant_pallas
    from shuffle_exchange_tpu.ops.quant_matmul import (_quant_matmul_pallas,
                                                       quantize_weight)

    D, F, B = LLAMA["D"], LLAMA["F"], 8
    up = quantize_weight(np.zeros((D, F), np.float32), group_size=256, bits=bits)
    down = quantize_weight(np.zeros((F, D), np.float32), group_size=256, bits=bits)
    chip_compile(lambda x: _quant_matmul_pallas(x, up), ((64, D), _BF16))
    chip_compile(lambda r, lnw: fused_mlp_quant_pallas(
        r, r, lnw, None, up, down, up, norm="rmsnorm", activation="swiglu"),
        ((B, D), _BF16), ((D,), _F32))


def test_lora_and_alibi_kernels_compile(chip_compile):
    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.alibi_attention import alibi_flash_attention
    from shuffle_exchange_tpu.ops.lora_gemm import lora_delta_pallas

    S, D, R, N = 9, LLAMA["D"], 16, LLAMA["D"]
    chip_compile(lambda x, a, b, s: lora_delta_pallas(x, a, b, s),
                 ((8, 1, D), _BF16), ((S, D, R), _BF16), ((S, R, N), _BF16),
                 ((8,), _I32))
    H, T = 8, 2048
    slopes = jnp.asarray(alibi_slopes(H), _F32)
    q = ((1, T, H, 128), _BF16)
    chip_compile(jax.grad(lambda q, k, v: alibi_flash_attention(
        q, k, v, slopes, True, False).astype(_F32).sum(), argnums=(0, 1, 2)),
        q, q, q)



def test_gated_delta_rule_compiles(chip_compile):
    """The three kernels at the shape ``qwen3next-train`` runs them: two
    rows of 8,192 tokens, 32 heads of 128, bf16 with float32 g and beta."""
    from shuffle_exchange_tpu.ops.gated_delta import _gated_delta_pallas

    wide, flat = ((2, 8192, 32, 128), _BF16), ((2, 8192, 32), _F32)
    chip_compile(_gated_delta_pallas, wide, wide, wide, flat, flat)
    compiled = chip_compile(_gated_delta_vjp, wide, wide, wide, flat, flat,
                            ((2, 8192, 32, 128), _F32))
    text = compiled.as_text()
    assert "gdn_rule_fwd_keep" in text and "gdn_rule_bwd" in text


def test_kda_rule_compiles(chip_compile):
    """The KDA rule's three kernels at the shape ``kimilinear-train`` runs
    them: one row of 16,384 tokens, 32 heads of 128 / 128, bf16 with float32
    g (a decay a key channel) and beta."""
    from shuffle_exchange_tpu.ops.kda import _kda_pallas

    wide, g, flat = (((1, 16384, 32, 128), _BF16), ((1, 16384, 32, 128), _F32),
                     ((1, 16384, 32), _F32))
    chip_compile(_kda_pallas, wide, wide, wide, g, flat)
    compiled = chip_compile(_kda_vjp, wide, wide, wide, g, flat,
                            ((1, 16384, 32, 128), _F32))
    text = compiled.as_text()
    assert "kda_rule_fwd_keep" in text and "kda_rule_bwd" in text


def test_gated_delta_rule_compiles_on_padded_lanes(chip_compile):
    """The same kernels at the shape ``olmohybrid-zero3-x4`` runs them on
    each chip: two rows of 8,192 tokens, 30 heads of 96 / 192 padded to
    128 / 256 lanes (route "pallas_padded"), six heads a grid step."""
    from shuffle_exchange_tpu.ops.gated_delta import _gated_delta_pallas

    keys, values = ((2, 8192, 30, 96), _BF16), ((2, 8192, 30, 192), _BF16)
    flat = ((2, 8192, 30), _F32)
    compiled = chip_compile(_gated_delta_vjp, keys, keys, values, flat, flat,
                            ((2, 8192, 30, 192), _F32))
    text = compiled.as_text()
    assert "gdn_rule_fwd_keep" in text and "gdn_rule_bwd" in text


@pytest.mark.parametrize("rows, seq, groups", [(2, 8192, 8), (1, 8192, 1)],
                         ids=["nemotron3_8_groups", "granite4h_one_group"])
def test_ssd_scan_compiles(chip_compile, rows, seq, groups):
    """The three kernels at the shapes ``nemotron3-train`` and
    ``granite4h-train`` run them: 64 heads of 64 and a state of 128, bf16 with
    a float32 step; two rows of 8,192 tokens in 8 groups, and one row in ONE
    group (a grid step then holds all 64 heads: 32 lane tiles walked in turn,
    a 2 MB state scratch; Mosaic takes it, PR 55)."""
    wide, group = ((rows, seq, 64, 64), _BF16), ((rows, seq, groups, 128), _BF16)
    compiled = chip_compile(_ssd_vjp, wide, ((rows, seq, 64), _F32), ((64,), _F32),
                            group, group, wide)
    text = compiled.as_text()
    assert "ssd_fwd_keep" in text and "ssd_bwd" in text


def test_ssm_conv_compiles(chip_compile):
    """The convolution's two kernels at the shape ``nemotron3-train`` runs
    them: two rows of 8,192 tokens, the projection's 10,304 columns of which
    x (4096), B and C (1024 each) start at 4096, four taps and a bias, bf16
    with bf16 weights; a launch a segment, forward and backward."""
    rows = lambda n: ((2, 8192, n), _BF16)
    compiled = chip_compile(_ssm_conv_vjp, rows(10304), ((4, 6144), _BF16),
                            ((6144,), _BF16), rows(4096), rows(4096), rows(1024),
                            rows(1024), rows(64))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert "ssm_conv_fwd" in text and "ssm_conv_bwd" in text


@pytest.mark.parametrize("B, T, C, K, dtype", [(8, 4096, 2048, 3, _BF16), (2, 1536, 384, 4, _F32)],
                         ids=["lfm2", "float32_three_lane_tiles"])
def test_sconv_mix_compiles(chip_compile, B, T, C, K, dtype):
    """The pass's two kernels at the shape ``lfm2-train`` runs them (eight
    sequences of 4,096 tokens, the projection's 3 x 2048 columns, three taps,
    bf16 with bf16 taps) and in float32 at blocks of 512 rows of three lane
    tiles and four taps; one launch forward, one backward."""
    rows = lambda n: ((B, T, n), dtype)
    compiled = chip_compile(_sconv_mix_vjp, rows(3 * C), ((K, C), dtype), rows(C))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "sconv_mix_fwd" in text and "sconv_mix_bwd" in text


def test_ssm_gate_norm_compiles(chip_compile):
    """The epilogue's two kernels at the shape ``nemotron3-train`` runs them:
    two rows of 8,192 tokens, 4096 channels in 8 groups of 512 and 64 heads of
    64, bf16 with a float32 skip and gain; one launch forward, one backward."""
    rows = ((2, 8192, 4096), _BF16)
    compiled = chip_compile(_ssm_gate_norm_vjp, rows, rows, rows, ((64,), _F32),
                            ((4096,), _F32), rows)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "ssm_gate_norm_fwd" in text and "ssm_gate_norm_bwd" in text


# cell -> (rows of the experts' buffer R, the experts' width F, activation)
_CELL_EXPERTS = {"lfm2-train": (98304, 1792, "swiglu"), "smallthinker-train": (73728, 768, "reglu"),
                 "qwen3next-train": (30720, 512, "swiglu"), "kanana2-train": (36864, 768, "swiglu"),
                 "laguna-train": (49152, 512, "swiglu"), "nemotron3-train": (18432, 1856, "relu2"),
                 "olmoe-train": (131072, 1024, "swiglu")}


@pytest.mark.parametrize("cell", list(_CELL_EXPERTS))
def test_expert_act_compiles_at_the_cells_shapes(chip_compile, cell):
    """The activation pass's two kernels at the buffer each MoE cell runs
    them on (the six held shares' R = 3 x the balanced share and
    ``olmoe-train``'s every token-choice; bf16; ``nemotron3-train``'s 1856 is
    14.5 lane tiles), ``fit`` a traced scalar: one launch forward, one
    backward."""
    import functools

    R, F, activation = _CELL_EXPERTS[cell]
    block = ((R, F), _BF16)
    compiled = chip_compile(functools.partial(_expert_act_vjp, activation=activation),
                            ((), _I32), block, *[block] * (1 if activation == "relu2" else 2))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "sxt_expert_act_fwd" in text and "sxt_expert_act_bwd" in text


def test_grouped_gemm_compiles_at_a_width_of_half_lane_tiles(chip_compile):
    """megablox gmm at ``nemotron3-train``'s expert geometry: 8 held experts
    of 2688 x 1856 (14.5 lane tiles: the contraction's last tile masked, the
    output's clipped), forward and both backward kernels, both matrices."""
    from shuffle_exchange_tpu.ops.grouped_gemm import _gmm_ok, _grouped_matmul_gmm

    E, D, F, N = 8, 2688, 1856, 18432
    for K, W in ((D, F), (F, D)):
        assert _gmm_ok(jnp.zeros((N, K), _BF16), jnp.zeros((E, K, W), _BF16))
        compiled = chip_compile(jax.grad(lambda x, w, gs: _grouped_matmul_gmm(
            x, w, gs).astype(_F32).sum() ** 2, argnums=(0, 1)),
            ((N, K), _BF16), ((E, K, W), _BF16), ((E,), _I32))
        assert "tgmm" in compiled.as_text()
    assert not _gmm_ok(jnp.zeros((N, 200), _BF16), jnp.zeros((E, 200, 1024), _BF16))


@pytest.mark.parametrize("cell", ["lfm2-train", "smallthinker-train", "nemotron3-train",
                                  "qwen3next-train"])
def test_grouped_gemm_compiles_under_the_tile_rule(chip_compile, cell):
    """The three kernels of both expert products at the two claimed cells',
    the largest tiles' (14.03 MiB by the rule's count: ``tgmm`` (128, 896,
    1856)) and the shortest groups' shapes, each under the tile its own
    product gets (PR 66): a tile over the kernels' scoped VMEM fails here, not
    on the chip."""
    for fn, *specs in _expert_layer_products(cell):
        text = chip_compile(fn, *specs).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 3 and "tgmm" in text


@pytest.mark.parametrize("Hk, rep, dk, dv", [(16, 2, 128, 128), (30, 1, 96, 192),
                                             (4, 1, 192, 192)],
                         ids=["qwen3next", "olmohybrid", "one_unaligned_key_head"])
def test_gdn_prologue_compiles(chip_compile, Hk, rep, dk, dv):
    """The prologue's two kernels at the shapes the cells run them: two rows
    of 8,192 tokens, a convolution over 4 tokens, bf16 with a float32
    ``conv_w``; ``qwen3next-train``'s 16 key heads and 32 value heads of 128
    (``qkvz`` [2, 8192, 12288], a key head a grid step) and
    ``olmohybrid-zero3-x4``'s 30 heads of 96 / 192 ([2, 8192, 17280], two key
    heads = 9 lane tiles a grid step, every segment's loads and stores at
    lanes between tiles: the widest block ``prologue_route`` admits); and
    the other shape the route admits that no cell runs, ONE key head a grid
    step whose segments lie between lane tiles (192 / 192, W = 768)."""
    import functools

    qkvz = ((2, 8192, Hk * (2 * dk + 2 * rep * dv)), _BF16)
    wide = lambda d: ((2, 8192, Hk * rep, d), _BF16)
    compiled = chip_compile(functools.partial(_gdn_prologue_vjp, heads=(Hk, dk, dv)),
                            qkvz, ((4, Hk * (2 * dk + rep * dv)), _F32),
                            wide(dk), wide(dk), wide(dv), wide(dv))
    text = compiled.as_text()
    assert "gdn_prologue_fwd" in text and "gdn_prologue_bwd" in text


def test_kda_prologue_compiles(chip_compile):
    """The KDA prologue's two kernels at the shape ``kimilinear-train`` runs
    them: one row of 16,384 tokens, ``qkv`` [1, 16384, 12288] (32 heads of
    128 / 128, all q | all k | all v), 4 taps, bf16 with a float32
    ``conv_w``; four heads of one part a grid step."""
    wide = ((1, 16384, 32, 128), _BF16)
    compiled = chip_compile(_kda_prologue_vjp, ((1, 16384, 12288), _BF16),
                            ((4, 12288), _F32), wide, wide, wide)
    text = compiled.as_text()
    assert "kda_prologue_fwd" in text and "kda_prologue_bwd" in text


def _moved_like_q(text):
    """The names of the compiled program's ``copy`` / ``transpose``
    instructions (fused or not) whose result has q's 16,384 x 32 x 128
    elements, heads split off or not, in any order of the axes."""
    import re

    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* (copy|transpose|fusion)\(", line)
        if m and sorted(m.group(2).split(",")) in (["1", "128", "16384", "32"],
                                                   ["1", "16384", "4096"]) and (
                m.group(3) != "fusion" or "copy" in m.group(1) or "transpose" in m.group(1)):
            found.append(m.group(1))
    return found


def test_nothing_moves_q_k_v_between_the_kda_prologue_and_the_rule(chip_compile):
    """The layout witness: projection -> prologue -> rule and its gradient,
    compiled for the described v5e at the cell's shape (g one row for every
    token and o summed in float32, so nothing else of q's shape is in the
    program). With ``kda_prologue``'s kernels the program holds NO copy or
    transpose of an array of q's shape, forward or backward: the kernels
    write q, k, v (read dq, dk, dv) as [B, H, T, d], the rule's own layout.
    Composed as the mixer ran it before (``ssm_conv``'s kernels, then XLA's
    l2 norms) the same compile shows XLA's copies (six forward, nine with
    the gradient, when this was written), which is what the chip's trace
    charged to ``copy`` under ``kda_conv`` (31.2 ms a step, PERF.md section
    5): here the CPU-side compile reproduces the chip's choice (PR 67 found
    a case, the decay statistic's second reader of g, where it did not)."""
    from shuffle_exchange_tpu.ops import kda
    from shuffle_exchange_tpu.ops.gated_delta import l2norm
    from shuffle_exchange_tpu.ops.ssm_conv import _ssm_conv_pallas

    B, T, H, d, D = 1, 16384, 32, 128, 2304

    def as_before(qkv, conv_w, H, dk, dv):
        q, k, v = _ssm_conv_pallas(qkv, conv_w, jnp.zeros((conv_w.shape[1],), _F32),
                                   0, (H * dk, H * dk, H * dv))[1:4]
        q = (l2norm(q.reshape(B, T, H, dk)) * dk ** -0.5).astype(qkv.dtype)
        return q, l2norm(k.reshape(B, T, H, dk)).astype(qkv.dtype), v.reshape(B, T, H, dv)

    def loss(prologue, y, w_qkv, conv_w, g, beta):
        q, k, v = prologue(y @ w_qkv, conv_w, H, d, d)
        return jnp.sum(kda._kda_pallas(q, k, v, jnp.broadcast_to(g, (B, T, H, d)), beta))

    specs = (((B, T, D), _BF16), ((D, 3 * H * d), _BF16), ((4, 3 * H * d), _F32),
             ((H, d), _F32), ((B, T, H), _F32))
    moved = {}
    for name, prologue in (("kernels", kda._kda_prologue_pallas), ("before", as_before)):
        fn = functools.partial(loss, prologue)
        moved[name] = [_moved_like_q(chip_compile(f, *specs).as_text())
                       for f in (fn, jax.grad(fn, argnums=(0, 1, 2)))]
    assert moved["kernels"] == [[], []], moved["kernels"]
    assert all(len(found) >= 6 for found in moved["before"]), moved["before"]


def test_the_learned_sparse_attentions_kernels_compile_at_the_cells_shapes(chip_compile):
    """``keyevl2-train``'s five kernels (PR 61) for a described v5e: the core's
    forward and the fused backward under a mask that is data (32 heads over 4
    KV heads of 128, 16,384 positions, int8 tiles of 1024 x 1024), the
    head-averaged probabilities, and the indexer's scores of a chunk of 512
    queries (16 heads of 64 over one key head) with their backward."""
    from shuffle_exchange_tpu.ops import dsa_kernels

    B, T, H, KV, D, C, Hi, Di = 1, 16384, 32, 4, 128, 512, 16, 64
    q, kv = ((B, T, H, D), _BF16), ((B, T, KV, D), _BF16)
    mask = ((B, T, T), jnp.int8)

    def core(q, k, v, mask_t):
        (out, lse), back = jax.vjp(lambda q, k, v: dsa_kernels.core(q, k, v, mask_t), q, k, v)
        return back((out, jnp.zeros_like(lse))), lse

    text = chip_compile(core, q, kv, kv, mask).as_text()
    assert dsa_kernels.FWD_NAME in text and "sxt_splash_bwd_fused" in text
    text = chip_compile(dsa_kernels.head_mean, q, kv, ((B, H, T), _F32), mask).as_text()
    assert dsa_kernels.MEAN_NAME in text

    def index(qi, ki, w, first, g):
        scores, back = jax.vjp(
            lambda qi, ki, w: dsa_kernels.index_scores(qi, ki, w, 0.03125, first), qi, ki, w)
        return scores, back(g)

    text = chip_compile(index, ((C, Hi, Di), _BF16), ((T, Di), _BF16), ((C, Hi), _BF16),
                        ((), jnp.int32), ((T, C), _F32)).as_text()
    assert dsa_kernels.INDEX_FWD_NAME in text and dsa_kernels.INDEX_BWD_NAME in text


def test_the_selections_kernel_compiles_at_the_cells_shapes(chip_compile):
    """``sxt_dsa_select`` at ``keyevl2-train``'s shapes (16,384 keys, a chunk
    of 512 queries, the 256 a grid step that ``select_lanes`` picks, the mask
    [1, 16384, 16384] written in place): what it asks of VMEM (two float32
    score blocks, the int32 keys, two int8 mask blocks) is the route's
    estimate and under the kernels' limit."""
    import re

    from shuffle_exchange_tpu.ops import dsa_kernels

    T, C, k = 16384, 512, 2048
    lanes = dsa_kernels.select_lanes(T, C)
    assert lanes == 256

    def search(scores, mask_t, first):
        return dsa_kernels.select_chunk(scores, mask_t, 0, first, k)

    compiled = chip_compile(search, ((T, C), _F32), ((1, T, T), jnp.int8), ((), _I32),
                            donate=(1,))
    call = re.search(r'%sxt_dsa_select[^\n]*custom_call_target="tpu_custom_call"[^\n]*',
                     compiled.as_text()).group(0)
    used = int(re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call).group(1))
    assert 14 * T * lanes <= used < 15 * T * lanes, used
    assert used < dsa_kernels.VMEM_LIMIT_BYTES
    # the mask goes in and comes out in one buffer
    assert '"aliasing_operands":{"lists":[{"indices":["2","3"]}]}' in call
    assert compiled.memory_analysis().temp_size_in_bytes < T * C


def test_tracer_reads_a_chip_compiled_programs_peak_and_passes(
        chip_compile, monkeypatch):
    """What ``trace.register_program`` keeps of a program compiled for the
    chip: XLA's peak is the program at its fullest, arguments included (the
    CPU backend's reads BELOW ``temp``), and a kernel replayed under
    ``jax.checkpoint`` reads ``recompute`` off its own path."""
    import collections

    from shuffle_exchange_tpu.ops.flash_attention import pallas_attention
    from shuffle_exchange_tpu.profiling import trace

    def loss(q, k, v):
        attend = jax.checkpoint(lambda q, k, v: pallas_attention(q, k, v, causal=True))
        return attend(q, k, v).astype(_F32).sum()

    g = GEOMS["gpt2-125m"]
    q = ((1, 1024, g["H"], g["Dh"]), _BF16)
    compiled = chip_compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    monkeypatch.setattr(trace, "_programs", {})
    trace.register_program("probe", compiled)
    sizes = trace.registered_memory("probe")
    assert type(sizes["peak"]) is int
    assert sizes["peak"] >= sizes["temp"] and sizes["peak"] >= sizes["argument"] > 0
    kernels = collections.Counter(
        trace.phase_of(op.scope) for op in trace.registered_ops("probe").values()
        if op.scope.endswith("pallas_call"))
    assert set(kernels) == {"recompute", "backward"}, kernels
