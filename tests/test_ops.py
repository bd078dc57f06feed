"""Kernel numerics tests (CPU: reference paths + interpret-mode pallas).

Pallas-vs-reference numerics on the real chip run via tests/tpu_smoke.py
(SURVEY.md §4b: kernel parity tests compare fused ops vs reference impls).
"""

import numpy as np
import pytest


def test_reference_attention_causality():
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import reference_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    out1 = reference_attention(q, k, v, causal=True)
    # Perturb the future: outputs at position t must not change.
    k2 = k.at[:, 5:].set(99.0)
    v2 = v.at[:, 5:].set(-99.0)
    out2 = reference_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :5]), np.asarray(out2[:, :5]), rtol=1e-5)
    assert not np.allclose(np.asarray(out1[:, 6:]), np.asarray(out2[:, 6:]))


def test_gqa_equals_repeated_mha():
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import reference_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    out_gqa = reference_attention(q, k, v)
    k_full = jnp.repeat(k, 2, axis=2)
    v_full = jnp.repeat(v, 2, axis=2)
    out_full = reference_attention(q, k_full, v_full)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_full), rtol=1e-5)


def test_int8_quant_roundtrip():
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant import quantize_dequantize, quantize_int8

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(300, 70)).astype(np.float32))
    y = quantize_dequantize(x, group_size=256)
    # int8 symmetric: relative error bounded by ~1/127 of group max
    err = np.abs(np.asarray(x) - np.asarray(y))
    assert err.max() <= float(np.abs(np.asarray(x)).max()) / 127 + 1e-6
    q, s = quantize_int8(x, group_size=256)
    assert q.dtype == jnp.int8


def test_rmsnorm_reference():
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.rmsnorm import rmsnorm_reference

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    w = jnp.ones((128,))
    out = rmsnorm_reference(x, w)
    norms = np.sqrt((np.asarray(out) ** 2).mean(-1))
    np.testing.assert_allclose(norms, 1.0, rtol=1e-4)


def test_rmsnorm_custom_vjp_matches_autodiff(monkeypatch):
    """The analytic backward behind the Pallas forward (r3 fix: the raw
    pallas_call had no VJP, so rmsnorm models could not train on TPU) must
    match jax.grad through the reference formula. The Pallas fwd is swapped
    for the reference here so the VJP math is exercised on CPU."""
    import jax
    import jax.numpy as jnp

    import importlib

    # the module, not the same-named function re-exported by ops/__init__
    rn = importlib.import_module("shuffle_exchange_tpu.ops.rmsnorm")

    monkeypatch.setattr(rn, "_rmsnorm_pallas", rn.rmsnorm_reference)
    rn._VJP_CACHE.clear()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(3, 7, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(3, 7, 256)), jnp.float32)

    def via_vjp(x, w):
        return (rn._rmsnorm_vjp(x, w, 1e-5) * g).sum()

    def via_ref(x, w):
        return (rn.rmsnorm_reference(x, w, 1e-5) * g).sum()

    dx_c, dw_c = jax.grad(via_vjp, argnums=(0, 1))(x, w)
    dx_r, dw_r = jax.grad(via_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx_c), np.asarray(dx_r), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dw_c), np.asarray(dw_r), rtol=2e-5, atol=2e-5)
    rn._VJP_CACHE.clear()


def test_quantized_matrix_matmul_parity():
    """int8-storage weight matmul (reference cutlass mixed_gemm, SURVEY
    §2.13): y @ QuantizedMatrix dispatches to the quantized path and tracks
    the dense product within int8 rounding."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant_matmul import quantize_weight

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, 7, 512)), jnp.float32)
    qm = quantize_weight(w, group_size=128)
    assert qm.nbytes < w.nbytes / 1.9          # the storage win
    out = jax.jit(lambda x, qm: x @ qm)(x, qm)
    ref = x @ w
    denom = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) / denom < 0.02
    # dequantize() round-trips the storage exactly
    np.testing.assert_allclose(np.asarray(x @ qm.dequantize()), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_quant_matmul_pallas_interpret_matches_fallback():
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant_matmul import (_quant_matmul_pallas,
                                                       quantize_weight)

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((19, 256)), jnp.float32)  # ragged M pads
    qm = quantize_weight(w, group_size=128)
    got = _quant_matmul_pallas(x, qm, interpret=True)
    ref = x @ qm.dequantize()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bits", [8, 4, "fp8"])
def test_quantized_serving_generates(bits):
    """The v1 engine with quantize_weights=True stores int8/int4 layer
    weights and still generates exactly like an engine fed the dequantized
    dense weights (same rounding by construction)."""
    import jax

    from shuffle_exchange_tpu.inference import InferenceConfig, InferenceEngine
    from shuffle_exchange_tpu.models import Transformer, tiny
    from shuffle_exchange_tpu.ops.quant_matmul import QuantizedMatrix

    model = Transformer(tiny(vocab=64, d=64, layers=2, heads=4, seq=64))
    params = model.init(jax.random.PRNGKey(0))
    eng_q = InferenceEngine(model, params, InferenceConfig(
        dtype="float32", max_seq_len=64, quantize_weights=True,
        quant_bits=bits))
    assert isinstance(eng_q.params["layers"]["wq"], QuantizedMatrix)
    assert eng_q.params["layers"]["wq"].bits == bits

    deq = jax.tree.map(
        lambda p: p.dequantize() if isinstance(p, QuantizedMatrix) else p,
        eng_q.params, is_leaf=lambda p: isinstance(p, QuantizedMatrix))
    eng_d = InferenceEngine(model, deq, InferenceConfig(dtype="float32", max_seq_len=64))
    prompts = np.random.default_rng(2).integers(0, 64, size=(2, 8)).astype(np.int32)
    out_q = eng_q.generate(prompts, max_new_tokens=6)
    out_d = eng_d.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(out_q, out_d)


def test_splash_gqa_interpret_parity():
    """Splash-MQA GQA path (unexpanded KV — the structural fix for the r2
    GQA-bandwidth question): forward AND gradients match the reference
    attention in interpret mode."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import (reference_attention,
                                                          splash_attention_gqa)

    rng = np.random.default_rng(0)
    B, T, H, KV, D = 1, 256, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)

    out = splash_attention_gqa(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    def loss_splash(q, k, v):
        return (splash_attention_gqa(q, k, v, causal=True, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gs = jax.grad(loss_splash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gs, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name}")


def test_fp8_quant_roundtrip():
    """fp8 e4m3 group quantization (reference FPQuantizerBuilder): wire dtype
    is 1 byte with ~2 decimal digits; round-trip error bounded by the e4m3
    relative step (2^-3) of each group's scale-mapped range."""
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant import quantize_dequantize_fp8, quantize_fp8

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(300, 70)).astype(np.float32))
    q, s = quantize_fp8(x, group_size=256)
    assert q.dtype == jnp.float8_e4m3fn
    y = quantize_dequantize_fp8(x, group_size=256)
    err = np.abs(np.asarray(x) - np.asarray(y))
    # e4m3: 3 mantissa bits -> rel err <= 2^-4 of the value, plus the
    # subnormal floor near zero
    ref = np.abs(np.asarray(x)) * 2 ** -4 + float(np.abs(np.asarray(x)).max()) / 448.0
    assert (err <= ref + 1e-7).all()


def test_int4_quantized_matrix_parity_and_packing():
    """int4 nibble-pair storage (reference cutlass mixed_gemm int4 path,
    SURVEY §2.13): quarter the bytes of bf16, pack/unpack round-trips
    exactly, and the matmul tracks dense within int4 rounding."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant_matmul import (_pack_int4,
                                                       _unpack_int4,
                                                       quantize_weight)

    rng = np.random.default_rng(0)
    # pack/unpack is exact over the full nibble range
    q = jnp.asarray(rng.integers(-7, 8, size=(16, 32)), jnp.int32)
    np.testing.assert_array_equal(np.asarray(_unpack_int4(_pack_int4(q, 8), 8)),
                                  np.asarray(q))

    w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, 7, 512)), jnp.float32)
    qm = quantize_weight(w, group_size=128, bits=4)
    assert qm.shape == w.shape and qm.q.shape == (256, 256)
    assert qm.nbytes < w.nbytes / 3.2          # ~4x storage win minus scales
    out = jax.jit(lambda x, qm: x @ qm)(x, qm)
    ref = x @ w
    denom = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) / denom < 0.15   # int4 rounding
    np.testing.assert_allclose(np.asarray(x @ qm.dequantize()), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_int4_quant_matmul_pallas_interpret():
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant_matmul import (_quant_matmul_pallas,
                                                       quantize_weight)

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((256, 512)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((19, 256)), jnp.float32)  # ragged M pads
    qm = quantize_weight(w, group_size=128, bits=4)
    got = _quant_matmul_pallas(x, qm, interpret=True)
    ref = x @ qm.dequantize()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_attn_block_override_clamped_to_itemsize_cap(monkeypatch):
    """ADVICE r3: SXT_ATTN_BLOCK must not bypass the VMEM block cap — forcing
    1024 with fp32 operands would recreate the documented Mosaic overflow."""
    from shuffle_exchange_tpu.ops.flash_attention import _pick_block

    monkeypatch.setenv("SXT_ATTN_BLOCK", "1024")
    assert _pick_block(4096, itemsize=2) == 1024   # within bf16 cap: honored
    assert _pick_block(4096, itemsize=4) == 512    # fp32: clamped to cap
    monkeypatch.setenv("SXT_ATTN_BLOCK", "512")
    assert _pick_block(4096, itemsize=4) == 512
    monkeypatch.setenv("SXT_ATTN_BLOCK", "333")    # not dividing n: ignored
    assert _pick_block(4096, itemsize=2) == 1024


def test_alibi_flash_kernel_parity_interpret():
    """Fused ALiBi flash kernel (ops/alibi_attention.py; reference applies
    ALiBi inside the fused inference softmax, ds_attention.py:16): interpret-
    mode forward matches the jnp reference, and the custom_vjp backward
    replays the reference VJP exactly."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.alibi_attention import alibi_flash_attention
    from shuffle_exchange_tpu.ops.flash_attention import reference_attention

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    s = jnp.asarray(alibi_slopes(H), jnp.float32)
    out = alibi_flash_attention(q, k, v, s, True, True)
    ref = reference_attention(q, k, v, causal=True, alibi_slopes=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    # full backward parity — dq, dk, dv AND dslopes all come from the
    # from-scratch Pallas dq/dkv kernels (round 5: no quadratic VJP replay)
    def loss_flash(q, k, v, s):
        o = alibi_flash_attention(q, k, v, s, True, True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v, s):
        o = reference_attention(q, k, v, causal=True, alibi_slopes=s)
        return jnp.sum(o * jnp.cos(o))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, s)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, s)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv", "dslopes")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_alibi_flash_kernel_gqa_and_rect_interpret():
    """GQA head repeat (dk/dv summed over repeat groups) and S > T
    rectangular attention (cache-offset causal mask) through the fused
    fwd+bwd kernels."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.models.transformer import alibi_slopes
    from shuffle_exchange_tpu.ops.alibi_attention import alibi_flash_attention
    from shuffle_exchange_tpu.ops.flash_attention import reference_attention

    rng = np.random.default_rng(1)
    B, T, S, H, Hkv, D = 1, 128, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    s = jnp.asarray(alibi_slopes(H), jnp.float32)
    out = alibi_flash_attention(q, k, v, s, True, True)
    ref = reference_attention(q, k, v, causal=True, alibi_slopes=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    g1 = jax.grad(lambda q, k, v: alibi_flash_attention(q, k, v, s, True, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: reference_attention(q, k, v, causal=True,
                                                      alibi_slopes=s).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_alibi_kernel_no_longcontext_fallback():
    """VERDICT r4 #4: the streamed-KV kernel has no whole-sequence VMEM cap,
    so a 32k-context BLOOM-style shape must NOT fall back (the old gate
    rejected kv_bytes > 8MB)."""
    from shuffle_exchange_tpu.ops.alibi_attention import alibi_kernel_ok
    from shuffle_exchange_tpu.ops import dispatch

    class _Q:
        shape = (1, 32768, 8, 128)
        dtype = np.dtype(np.float16)  # bf16-equivalent itemsize 2

    class _K:
        shape = (1, 32768, 8, 128)
        dtype = np.dtype(np.float16)

    orig = dispatch.pallas_enabled
    dispatch.pallas_enabled = lambda: True
    try:
        assert alibi_kernel_ok(_Q, _K, causal=True), \
            "32k ALiBi context fell back — streamed kernel gate regressed"
    finally:
        dispatch.pallas_enabled = orig


def test_noncausal_reference_attention_bidirectional():
    """Encoder support: causal=False attends both directions."""
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import reference_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    out_bi = reference_attention(q, k, v, causal=False)
    out_c = reference_attention(q, k, v, causal=True)
    # last position sees every key under both masks
    np.testing.assert_allclose(np.asarray(out_bi[:, -1]), np.asarray(out_c[:, -1]),
                               rtol=1e-5)
    assert not np.allclose(np.asarray(out_bi[:, :-1]), np.asarray(out_c[:, :-1]))


def test_fp8_quantized_matrix_serving_path():
    """VERDICT r3 missing #3: fp8 group quantization now reaches a matmul —
    e4m3 storage in QuantizedMatrix with the same kernel/fallback path as
    int8 (reference fp_quantizer serving GEMM)."""
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant_matmul import (_quant_matmul_pallas,
                                                       quantize_weight)

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    qm = quantize_weight(w, group_size=128, bits="fp8")
    assert qm.q.dtype == jnp.float8_e4m3fn
    assert qm.nbytes < w.size * 2          # ~1 byte/elem + scales
    # e4m3 has ~2 decimal digits: dequant within ~8% relative of source
    np.testing.assert_allclose(np.asarray(qm.dequantize(), np.float32),
                               np.asarray(w), rtol=0.09, atol=0.02)
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    got = x @ qm
    want = x @ qm.dequantize()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)
    # the Pallas kernel body handles the fp8 storage (interpret mode)
    got_k = _quant_matmul_pallas(x, qm, interpret=True)
    np.testing.assert_allclose(np.asarray(got_k, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)


def test_attn_bwd_block_override(monkeypatch):
    """SXT_ATTN_BLOCK_BWD tunes the splash dkv/dq blocks independently of
    the forward blocks (clamped like SXT_ATTN_BLOCK); interpret-mode parity
    is unchanged under the override."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import (reference_attention,
                                                          splash_attention_gqa)

    monkeypatch.setenv("SXT_ATTN_BLOCK_BWD", "128")
    rng = np.random.default_rng(0)
    # head_dim 128: this jaxlib's splash kernel requires head_dim to be a
    # multiple of its 128 lanes even in interpret mode
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)
    out = splash_attention_gqa(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_evoformer_attention_parity_and_grads():
    """DS4Sci EvoformerAttention analog (reference ops/deepspeed4science/
    evoformer_attn.py:88): chunked biased attention matches the dense
    softmax oracle, with grads for q/k/v AND both biases; bias shape
    checks mirror the reference's."""
    import jax
    import jax.numpy as jnp
    import pytest

    from shuffle_exchange_tpu.ops.evoformer_attn import (
        ds4sci_evoformer_attention, evoformer_attention)

    rng = np.random.default_rng(0)
    B, N, L, H, D = 2, 3, 24, 4, 16
    q = jnp.asarray(rng.normal(size=(B, N, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, L, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, L, H, D)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(B, N, 1, 1, L)) * 2, jnp.float32)
    b2 = jnp.asarray(rng.normal(size=(B, 1, H, L, L)), jnp.float32)

    def dense(q, k, v, b1, b2):
        s = jnp.einsum("bnihd,bnjhd->bnhij", q * D ** -0.5, k)
        s = s + b1 + b2
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnhij,bnjhd->bnihd", p, v)

    out = ds4sci_evoformer_attention(q, k, v, [b1, b2])
    want = dense(q, k, v, b1, b2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # chunked path (chunk < L) identical
    out_c = evoformer_attention(q, k, v, bias1=b1, bias2=b2, chunk=8)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # single bias / no bias
    np.testing.assert_allclose(
        np.asarray(ds4sci_evoformer_attention(q, k, v, [b1])),
        np.asarray(dense(q, k, v, b1, jnp.zeros_like(b2))),
        rtol=2e-5, atol=2e-5)
    # grads incl. both biases (reference computes dB1/dB2)
    def loss_k(q, k, v, b1, b2):
        o = evoformer_attention(q, k, v, bias1=b1, bias2=b2, chunk=8)
        return jnp.sum(o * jnp.sin(o))

    def loss_d(q, k, v, b1, b2):
        o = dense(q, k, v, b1, b2)
        return jnp.sum(o * jnp.sin(o))

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3, 4))(q, k, v, b1, b2)
    gd = jax.grad(loss_d, argnums=(0, 1, 2, 3, 4))(q, k, v, b1, b2)
    for a, b, nm in zip(gk, gd, ("dq", "dk", "dv", "db1", "db2")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5, err_msg=nm)
    # strict shape checks
    with pytest.raises(ValueError, match="bias1 shape"):
        ds4sci_evoformer_attention(q, k, v, [b2])
    with pytest.raises(ValueError, match="bias2 shape"):
        ds4sci_evoformer_attention(q, k, v, [b1, b1])


def test_quant_matmul_pallas_eligibility_guard():
    """ADVICE r5 #2: impl="pallas" validates kernel eligibility up front
    with a descriptive error instead of an opaque Mosaic failure."""
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.quant_matmul import (quant_matmul,
                                                       quantize_weight)

    w = jnp.asarray(np.random.default_rng(0).standard_normal((256, 256)),
                    jnp.float32)
    qm = quantize_weight(w, group_size=128)
    with pytest.raises(ValueError, match="contraction dim"):
        quant_matmul(jnp.zeros((4, 128), jnp.float32), qm, impl="pallas")
    qm64 = quantize_weight(w, group_size=64)
    with pytest.raises(ValueError, match="group_size=64"):
        quant_matmul(jnp.zeros((4, 256), jnp.float32), qm64, impl="pallas")
    w_odd = jnp.asarray(np.random.default_rng(0).standard_normal((256, 192)),
                        jnp.float32)
    qm_odd = quantize_weight(w_odd, group_size=128)
    with pytest.raises(ValueError, match="multiple of.*128"):
        quant_matmul(jnp.zeros((4, 256), jnp.float32), qm_odd, impl="pallas")
