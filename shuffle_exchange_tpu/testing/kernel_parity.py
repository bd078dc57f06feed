"""Every Pallas kernel against its jnp oracle, compiled and run on the chip.

Phase 1 of ``chip_smoke.py`` (SURVEY.md section 4b: the reference's kernel
parity tests compare fused CUDA ops against torch). Interpret-mode tests on
the CPU check a kernel's arithmetic and ``tests/test_mosaic_lowering.py``
checks that the chip's compiler accepts it; only this run shows that what
Mosaic built computes the same numbers. The kernel ENTRY POINTS are called,
not the dispatching wrappers, so a broken kernel cannot be papered over by a
reference route.

``run()`` yields one ``{"name", "err", "tol", "ok"}`` record per check and
never raises on a mismatch: the caller counts failures.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def _f32(x):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x).astype(jnp.float32))


def _check(name: str, got, want, tol: float) -> Dict[str, object]:
    err = float(np.max(np.abs(_f32(got) - _f32(want))))
    return {"name": name, "err": err, "tol": tol,
            "ok": bool(np.isfinite(err) and err <= tol)}


def _paged_pool(rng, B, H, KV, Dh, bs, nblk, kv_lens, dtype):
    """q [B,1,H,Dh], a filled pool pair, a -1-padded block table (block 0 is
    never handed out, as in the engine) and the kv lengths."""
    import jax.numpy as jnp

    q = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), dtype)
    ck = jnp.asarray(rng.standard_normal((nblk, KV, bs, Dh)), dtype)
    cv = jnp.asarray(rng.standard_normal((nblk, KV, bs, Dh)), dtype)
    maxblk = max(-(-int(n) // bs) for n in kv_lens)
    bt = np.full((B, maxblk), -1, np.int32)
    nxt = iter(range(1, nblk))
    for b, n in enumerate(kv_lens):
        for j in range(-(-int(n) // bs)):
            bt[b, j] = next(nxt)
    return q, ck, cv, jnp.asarray(bt), jnp.asarray(np.asarray(kv_lens, np.int32))


def _decode_oracle(q, ck, cv, bt, kvl, alibi_slopes=None):
    import jax.numpy as jnp

    from ..inference.engine import decode_attention
    from ..inference.paged import gather_kv

    k, v = gather_kv(ck, cv, jnp.maximum(bt, 0))
    return decode_attention(q, k, v, kvl, alibi_slopes=alibi_slopes)


def _extend_oracle(q, ck, cv, bt, start, nnew, alibi_slopes=None):
    import jax.numpy as jnp

    from ..inference.engine import extend_attention
    from ..inference.paged import gather_kv

    k, v = gather_kv(ck, cv, jnp.maximum(bt, 0))
    return extend_attention(q, k, v, start, start + nnew,
                            alibi_slopes=alibi_slopes)


def _attention(rng) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from ..ops.flash_attention import (attention_backward_route,
                                       pallas_attention, reference_attention)

    # splash on this one device: MHA (a group of one, PR 56) and GQA
    # (unexpanded KV), forward and dk
    for H, KV, label in [(8, 8, "splash-mha"), (8, 2, "splash-gqa")]:
        q = jnp.asarray(rng.standard_normal((2, 256, H, 128)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 256, KV, 128)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 256, KV, 128)), jnp.float32)
        yield _check(label, pallas_attention(q, k, v, causal=True),
                     reference_attention(q, k, v, causal=True), 5e-2)
        g_p = jax.grad(lambda q, k, v: (pallas_attention(q, k, v) ** 2).sum(),
                       argnums=1)(q, k, v)
        g_r = jax.grad(lambda q, k, v: (reference_attention(q, k, v) ** 2).sum(),
                       argnums=1)(q, k, v)
        yield _check(label + "-dk", g_p, g_r, 5e-1)

    # the splash routes' backward as one kernel (ops/splash_backward: bf16
    # inputs take it, the float32 rows above keep the library's two), causal
    # over several blocks and under a window that leaves blocks unvisited:
    # forward and dq, dk, dv against the oracle in float32 on the same
    # rounded inputs, each as a share of the oracle's largest value
    for KV, D, Dv, window, label in [(2, 128, 128, 0, "splash-fused-bwd"),
                                     (8, 192, 128, 0, "splash-fused-bwd-192-128"),
                                     (1, 128, 128, 512, "splash-fused-bwd-window"),
                                     (8, 64, 64, 0, "splash-fused-bwd-mha-64")]:
        q, k, v, do = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                       for shape in [(1, 2048, 8, D), (1, 2048, KV, D),
                                     (1, 2048, KV, Dv), (1, 2048, 8, Dv)])
        assert attention_backward_route(q, k, v, True, window) == "fused_resident_dkv"
        wide = [x.astype(jnp.float32) for x in (q, k, v)]
        loss = lambda attend: lambda q, k, v: jnp.sum(
            attend(q, k, v, causal=True, window=window).astype(jnp.float32)
            * do.astype(jnp.float32))
        got = jax.grad(loss(pallas_attention), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(*wide)
        pairs = [("", pallas_attention(q, k, v, causal=True, window=window),
                  reference_attention(*wide, causal=True, window=window))]
        pairs += [("-d" + n, a, b) for n, a, b in zip("qkv", got, want)]
        for suffix, a, b in pairs:
            top = float(jnp.max(jnp.abs(b)))
            yield _check(label + suffix, _f32(a) / top, _f32(b) / top, 2e-2)


def _rmsnorm(rng) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from ..ops.rmsnorm import _rmsnorm_vjp, rmsnorm_reference

    # d 4096 at >= 256 rows is the shape whose row block once overflowed VMEM
    for shape in [(4, 256, 512), (2, 512, 4096)]:
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        w = jnp.asarray(rng.standard_normal(shape[-1:]), jnp.float32)
        tag = f"rmsnorm-d{shape[-1]}"
        yield _check(tag, _rmsnorm_vjp(x, w, 1e-5), rmsnorm_reference(x, w), 1e-4)
        gp = jax.grad(lambda x, w: _rmsnorm_vjp(x, w, 1e-5).sum(),
                      argnums=(0, 1))(x, w)
        gr = jax.grad(lambda x, w: rmsnorm_reference(x, w).sum(),
                      argnums=(0, 1))(x, w)
        yield _check(tag + "-dx", gp[0], gr[0], 1e-3)
        yield _check(tag + "-dw", gp[1], gr[1], 1e-2)


def _paged(rng) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from ..models.transformer import alibi_slopes
    from ..ops.paged_attention import (paged_decode_attention_pallas,
                                       paged_extend_attention_pallas)

    q, ck, cv, bt, kvl = _paged_pool(rng, 3, 24, 8, 64, 64, 40,
                                     [33, 200, 64], jnp.float32)
    yield _check("paged-decode",
                 paged_decode_attention_pallas(q, ck, cv, bt, kvl),
                 _decode_oracle(q, ck, cv, bt, kvl), 5e-3)
    starts = jnp.asarray([5, 0, 30], jnp.int32)
    nnew = [8, 3, 6]
    qc = jnp.asarray(rng.standard_normal((3, 8, 24, 64)), jnp.float32)
    got = _f32(paged_extend_attention_pallas(qc, ck, cv, bt, starts,
                                             jnp.asarray(nnew, jnp.int32)))
    want = _f32(_extend_oracle(qc, ck, cv, bt, starts,
                               jnp.asarray(nnew, jnp.int32)))
    errs = [float(np.max(np.abs(got[b, :n] - want[b, :n])))
            for b, n in enumerate(nnew)]
    # 2e-2: kernel and oracle BOTH run default-precision (bf16-product) MXU
    # matmuls; against an f64 ground truth the kernel was the closer of the
    # two on a v5e (2026-07-31, before PR 1), so the gap is rounding
    yield _check("paged-extend", np.asarray(errs), np.zeros(3), 2e-2)

    # ALiBi slopes riding both paged kernels (BLOOM serving without the
    # per-layer cache gather): the (1, G) slope block must survive Mosaic
    B, H, KV, Dh, bs, nblk = 2, 8, 8, 128, 64, 10
    qp = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.bfloat16)
    ckp = jnp.asarray(rng.standard_normal((nblk, KV, bs, Dh)), jnp.bfloat16)
    cvp = jnp.asarray(rng.standard_normal((nblk, KV, bs, Dh)), jnp.bfloat16)
    btp = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0]], np.int32))
    kvlp = jnp.asarray(np.array([170, 100], np.int32))
    slp = jnp.asarray(alibi_slopes(H), jnp.float32)
    got_p = jax.jit(lambda q, k, v: paged_decode_attention_pallas(
        q, k, v, btp, kvlp, alibi_slopes=slp))(qp, ckp, cvp)
    yield _check("paged-decode-alibi", got_p,
                 _decode_oracle(qp, ckp, cvp, btp, kvlp, alibi_slopes=slp), 5e-2)
    qe = jnp.asarray(rng.standard_normal((B, 4, H, Dh)), jnp.bfloat16)
    st = jnp.asarray(np.array([100, 40], np.int32))
    nn = np.array([4, 3], np.int32)
    got_e = _f32(jax.jit(lambda q, k, v: paged_extend_attention_pallas(
        q, k, v, btp, st, jnp.asarray(nn), alibi_slopes=slp))(qe, ckp, cvp))
    want_e = _f32(_extend_oracle(qe, ckp, cvp, btp, st, jnp.asarray(nn),
                                 alibi_slopes=slp))
    for b in range(B):
        yield _check(f"paged-extend-alibi-b{b}", got_e[b, :nn[b]],
                     want_e[b, :nn[b]], 5e-2)


def _matmuls(rng) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from ..ops.grouped_gemm import _grouped_matmul_gmm
    from ..ops.lora_gemm import lora_delta_oracle, lora_delta_pallas
    from ..ops.quant_matmul import _quant_matmul_pallas, quantize_weight

    wd = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    xq = jnp.asarray(rng.standard_normal((64, 512)), jnp.float32)
    for bits, tag in [(8, "quant-matmul"), (4, "quant-matmul-int4"),
                      ("fp8", "quant-matmul-fp8")]:
        qm = quantize_weight(wd, group_size=128, bits=bits)
        yield _check(tag, _quant_matmul_pallas(xq, qm), xq @ qm.dequantize(),
                     5e-3)

    # megablox gmm vs ragged_dot, uneven groups, one empty group; then its
    # custom-VJP backward (dx via transposed gmm, dw via tgmm), which MoE
    # training runs. First N not a tile multiple; then an expert's widths (PR
    # 66): the whole contraction 2048 one k-step forward, the whole 2048 x 512
    # output one tgmm tile
    for tag, (N, K, F), sizes in (("grouped-gemm", (1000, 256, 384), [300, 0, 450, 250]),
                                  ("grouped-gemm-wide", (640, 2048, 512), [130, 0, 401, 97, 12])):
        xg = jnp.asarray(rng.standard_normal((N, K)), jnp.bfloat16)
        wg = jnp.asarray(rng.standard_normal((len(sizes), K, F)) * K ** -0.5, jnp.bfloat16)
        gs = jnp.asarray(sizes, jnp.int32)
        yield _check(tag, _grouped_matmul_gmm(xg, wg, gs), jax.lax.ragged_dot(xg, wg, gs), 5e-2)

        def loss(fn, xx, ww):
            return (fn(xx, ww, gs).astype(jnp.float32) ** 2).mean()

        gx, gw = jax.grad(lambda a, b: loss(_grouped_matmul_gmm, a, b),
                          argnums=(0, 1))(xg, wg)
        rx, rw = jax.grad(lambda a, b: loss(jax.lax.ragged_dot, a, b),
                          argnums=(0, 1))(xg, wg)
        yield _check(f"{tag}-dx", gx, rx, 5e-2)
        yield _check(f"{tag}-dw", gw, rw, 5e-2)

    # multi-tenant LoRA pool-gather kernel: slot 0 is the all-zeros adapter
    S, D, R, Nn = 5, 256, 8, 128
    a = jnp.asarray(rng.standard_normal((S, D, R)) * 0.1, jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((S, R, Nn)) * 0.1, jnp.bfloat16)
    a, b = a.at[0].set(0), b.at[0].set(0)
    x = jnp.asarray(rng.standard_normal((4, 1, D)), jnp.bfloat16)
    slots = jnp.asarray([0, 3, 1, 3], jnp.int32)
    yield _check("lora-gemm", lora_delta_pallas(x, a, b, slots),
                 lora_delta_oracle(x, a, b, slots), 2e-2)


def _alibi_flash(rng) -> Iterator[dict]:
    import jax
    import jax.numpy as jnp

    from ..models.transformer import alibi_slopes
    from ..ops.alibi_attention import alibi_flash_attention
    from ..ops.flash_attention import reference_attention

    B, T, H, D = 2, 512, 4, 128
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
               for _ in range(3))
    sl = jnp.asarray(alibi_slopes(H), jnp.float32)
    got = jax.jit(lambda q, k, v: alibi_flash_attention(
        q, k, v, sl, True, False))(q, k, v)
    yield _check("alibi-flash", got,
                 reference_attention(q, k, v, causal=True, alibi_slopes=sl), 5e-2)

    # the from-scratch Pallas backward (dq + dkv kernels), slopes included:
    # the dslope path is the riskiest Mosaic construct (a revisited
    # per-kv-block f32 output)
    def sq(fn):
        return lambda q, k, v, s: (fn(q, k, v, s).astype(jnp.float32) ** 2).mean()

    ga = jax.jit(jax.grad(sq(lambda q, k, v, s: alibi_flash_attention(
        q, k, v, s, True, False)), argnums=(0, 1, 2, 3)))(q, k, v, sl)
    ra = jax.grad(sq(lambda q, k, v, s: reference_attention(
        q, k, v, causal=True, alibi_slopes=s)), argnums=(0, 1, 2, 3))(q, k, v, sl)
    for gg, rr, nm in zip(ga, ra, ("dq", "dk", "dv", "dslopes")):
        yield _check(f"alibi-flash-bwd-{nm}", gg, rr, 5e-2)

    # 32k context through the streamed-KV kernel: finite is the whole check
    q32 = jnp.asarray(rng.standard_normal((1, 32768, 2, 128)), jnp.bfloat16)
    o32 = jax.jit(lambda q, k, v: alibi_flash_attention(
        q, k, v, jnp.asarray(alibi_slopes(2), jnp.float32), True, False))(
            q32, q32, q32)
    fin = bool(np.isfinite(_f32(o32)).all())
    yield {"name": "alibi-32k-fwd", "err": 0.0 if fin else float("inf"),
           "tol": 0.0, "ok": fin}


def _fused_decode(rng) -> Iterator[dict]:
    """The three kernels of the default serving path, at one lane-aligned
    (Dh 128, the Llama family) and one lane-padded (Dh 64, GPT-2) geometry."""
    import jax
    import jax.numpy as jnp

    from ..inference.engine import _apply_rope_batched
    from ..models.transformer import _norm, rope_table
    from ..ops import fused_decode as fd
    from ..ops.quant_matmul import quantize_weight

    bf = jnp.bfloat16
    for Dh, KV, pooled in [(128, 2, False), (128, 2, True), (64, 8, False)]:
        B, D, H, nblk, bs, L = 5, 1024, 8, 12, 16, 3
        y = jnp.asarray(rng.standard_normal((B, D)), bf)
        wq = jnp.asarray(rng.standard_normal((D, H * Dh)) * D ** -0.5, bf)
        wk = jnp.asarray(rng.standard_normal((D, KV * Dh)) * D ** -0.5, bf)
        wv = jnp.asarray(rng.standard_normal((D, KV * Dh)) * D ** -0.5, bf)
        shape = ((L,) if pooled else ()) + (nblk, KV, bs, Dh)
        pool_k = jnp.asarray(rng.standard_normal(shape), bf)
        pool_v = jnp.asarray(rng.standard_normal(shape), bf)
        route = fd.qkv_append_route(shape, bf)
        tag = f"fused-qkv-{route}-dh{Dh}" + ("-stacked" if pooled else "")
        # slots in both 8-row groups of a block, a fresh block's slot 0,
        # and two rows in one block (different groups: rows of one group
        # would race, which the engine only lets happen in scratch)
        pos = jnp.asarray([16, 0, 13, 7, 8], jnp.int32)
        blk = jnp.asarray([4, 2, 6, 9, 9], jnp.int32)
        off = pos % bs
        cos_t, sin_t = rope_table(64, Dh, 10000.0)
        cos, sin = jnp.take(cos_t, pos, axis=0), jnp.take(sin_t, pos, axis=0)
        kw = {"layer": jnp.int32(1)} if pooled else {}
        q, k, v, pk2, pv2 = jax.jit(
            lambda y, pk, pv: fd.fused_qkv_rope_pallas(
                y, wq, wk, wv, cos=cos, sin=sin, n_heads=H, kv_heads=KV,
                pool_k=pk, pool_v=pv, blk=blk, off=off, **kw))(y, pool_k, pool_v)
        qr = _apply_rope_batched((y @ wq).reshape(B, 1, H, Dh),
                                 cos[:, None], sin[:, None])[:, 0]
        kr = _apply_rope_batched((y @ wk).reshape(B, 1, KV, Dh),
                                 cos[:, None], sin[:, None])[:, 0]
        yield _check(tag + "-q", q, qr, 5e-2)
        yield _check(tag + "-k", k, kr, 5e-2)
        yield _check(tag + "-v", v, (y @ wv).reshape(B, KV, Dh), 5e-2)
        # the pool: new rows are exactly the returned k/v, every other
        # element is bit-identical to what was there
        at = (1,) if pooled else ()
        for new, got, old, nm in ((k, pk2, pool_k, "k"), (v, pv2, pool_v, "v")):
            want = np.array(_f32(old))
            for b in range(B):
                want[at + (int(blk[b]), slice(None), int(off[b]))] = _f32(new[b])
            yield _check(f"{tag}-pool-{nm}", got, want, 0.0)

    # split-K flash-decode over the block table, flat and stacked pool
    B, H, KV, Dh, bs = 4, 8, 2, 128, 16
    q, ck, cv, bt, kvl = _paged_pool(rng, B, H, KV, Dh, bs, 40,
                                     [33, 64, 1, 100], bf)
    want = _decode_oracle(q, ck, cv, bt, kvl)
    yield _check("fused-splitk", jax.jit(
        lambda q, ck, cv: fd.fused_paged_decode_attention_pallas(
            q, ck, cv, bt, kvl, num_splits=2))(q, ck, cv), want, 5e-2)
    ck5 = jnp.stack([jnp.zeros_like(ck), ck])
    cv5 = jnp.stack([jnp.zeros_like(cv), cv])
    yield _check("fused-splitk-stacked", jax.jit(
        lambda q, ck, cv: fd.fused_paged_decode_attention_pallas(
            q, ck, cv, bt, kvl, layer=jnp.int32(1), num_splits=2))(q, ck5, cv5),
        want, 5e-2)

    # residual + norm + MLP: gated swiglu/rmsnorm, gelu_new/layernorm with
    # biases, and the int8 streamed-weight form
    B, D, F = 5, 1024, 2816
    resid = jnp.asarray(rng.standard_normal((B, D)), bf)
    lnw = jnp.asarray(1.0 + 0.1 * rng.standard_normal((D,)), jnp.float32)
    lnb = jnp.asarray(0.1 * rng.standard_normal((D,)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((D, F)) * D ** -0.5, bf)
    wu = jnp.asarray(rng.standard_normal((D, F)) * D ** -0.5, bf)
    wdn = jnp.asarray(rng.standard_normal((F, D)) * F ** -0.5, bf)
    yn = _norm(resid, lnw, 0, "rmsnorm")
    yield _check("fused-mlp-swiglu", jax.jit(
        lambda r: fd.fused_mlp_pallas(r, r, lnw, None, wu, wdn, wg,
                                      norm="rmsnorm", activation="swiglu"))(resid),
        resid + (jax.nn.silu(yn @ wg) * (yn @ wu)) @ wdn, 1e-1)
    bu = jnp.asarray(0.1 * rng.standard_normal((F,)), jnp.float32)
    bd = jnp.asarray(0.1 * rng.standard_normal((D,)), jnp.float32)
    yl = _norm(resid, lnw, lnb, "layernorm")
    yield _check("fused-mlp-gelu-bias", jax.jit(
        lambda r: fd.fused_mlp_pallas(r, r, lnw, lnb, wu, wdn, None, b_up=bu,
                                      b_down=bd, norm="layernorm",
                                      activation="gelu_new"))(resid),
        resid + (jax.nn.gelu(yl @ wu + bu.astype(bf), approximate=True)
                 @ wdn + bd.astype(bf)), 1e-1)
    F8 = 2048
    qs = [quantize_weight(_f32(w), group_size=256, bits=8, dtype=bf)
          for w in (wg[:, :F8], wu[:, :F8], wdn[:F8])]
    deq = [m.dequantize().astype(bf) for m in qs]
    yield _check("fused-mlp-int8", jax.jit(
        lambda r: fd.fused_mlp_quant_pallas(r, r, lnw, None, qs[1], qs[2],
                                            qs[0], norm="rmsnorm",
                                            activation="swiglu"))(resid),
        resid + (jax.nn.silu(yn @ deq[0]) * (yn @ deq[1])) @ deq[2], 1e-1)


def _gated_delta(rng) -> Iterator[dict]:
    """The gated delta rule's kernels (``ops/gated_delta.py``: forward, the
    forward that keeps S0, backward) against the token-by-token recurrence:
    o and the five gradients under a seeded cotangent. float32 inputs take
    every product at float32 accuracy and agree closely; bf16 inputs are the
    trainer's, and their distance is the rounding of the operands. Four
    chunks and a ragged fifth, a memory of tens of tokens."""
    import jax
    import jax.numpy as jnp

    from ..ops.gated_delta import (_gated_delta_pallas, gated_delta_recurrent,
                                   l2norm)

    B, T, H, d = 2, 300, 8, 128
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = l2norm(normal(B, T, H, d)) * d ** -0.5
    k = l2norm(normal(B, T, H, d))
    v = jax.nn.silu(normal(B, T, H, d))
    g = -jax.nn.softplus(normal(B, T, H)) / 30.0
    beta = jax.nn.sigmoid(normal(B, T, H))
    cotangent = normal(B, T, H, d)

    def answers(rule, *args):
        o, back = jax.vjp(lambda *a: rule(*a).astype(jnp.float32), *args)
        return (o,) + back(cotangent)

    up = lambda x: x.astype(jnp.float32)
    recurrence = lambda q, k, v, g, beta: gated_delta_recurrent(
        up(q), up(k), up(v), g, beta)
    for dtype, tols in [(jnp.float32, (1e-5, 1e-3, 5e-4, 1e-5, 5e-4, 5e-4)),
                        (jnp.bfloat16, (2e-3, 2e-1, 3e-2, 5e-3, 2e-2, 2e-2))]:
        args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
        got = jax.jit(lambda *a: answers(_gated_delta_pallas, *a))(*args)
        want = jax.jit(lambda *a: answers(recurrence, *a))(*args)
        for part, a, b, tol in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                                   got, want, tols):
            yield _check(f"gated-delta-{jnp.dtype(dtype).name}-{part}", a, b, tol)


# tag: (T, key heads, value heads a key head, dk, dv); 700 rows: a block of
# 512 and a padded second one; 80: one ragged block of 128; 512: a whole one
_PROLOGUE_CASES = {
    "": (700, 4, 2, 128, 128),
    "-2x96x192-ragged": (80, 2, 1, 96, 192),
    "-2x96x192": (512, 2, 1, 96, 192),
    "-30x96x192-ragged": (80, 30, 1, 96, 192),
    "-30x96x192": (512, 30, 1, 96, 192),
    # one key head a grid step whose segments start between lane tiles
    "-2x192x192-ragged": (80, 2, 1, 192, 192),
}


def _gdn_prologue(rng) -> Iterator[dict]:
    """The DeltaNet mixer's prologue kernels (``ops/gated_delta.py``:
    ``gdn_prologue_fwd`` / ``gdn_prologue_bwd``) against the composition they
    replace (``silu(causal_conv1d)`` -> split -> ``l2norm`` -> repeat): q, k,
    v, z and the gradients of ``qkvz`` and ``conv_w`` under seeded cotangents,
    at heads of 128 / 128 (a key head a grid step), of 96 / 192 (two: the
    segments start between lane tiles; 2 key heads and Olmo-Hybrid's 30) and
    of 192 / 192 (one, its segments between lane tiles too).
    float32 agrees to rounding; in bf16 the composition rounds the
    convolution's result before SiLU, the kernels once at the write."""
    import jax
    import jax.numpy as jnp

    from ..ops.gated_delta import _gdn_prologue_pallas, _gdn_prologue_xla

    B, K = 2, 4
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    for tag, (T, Hk, rep, dk, dv) in _PROLOGUE_CASES.items():
        qkvz = normal(B, T, Hk * (2 * dk + 2 * rep * dv))
        conv_w = 0.5 * normal(K, Hk * (2 * dk + rep * dv))
        cotangents = tuple(normal(B, T, Hk * rep, d) for d in (dk, dk, dv, dv))

        def answers(fn, qkvz, conv_w, cotangents):
            out, back = jax.vjp(lambda x, w: fn(x, w, Hk, dk, dv), qkvz, conv_w)
            return out + back(cotangents)

        for dtype, tols in [(jnp.float32, (1e-5, 1e-5, 1e-5, 0.0, 1e-4, 5e-3)),
                            (jnp.bfloat16, (2e-3, 2e-2, 1e-1, 0.0, 3e-1, 4.0))]:
            args = (qkvz.astype(dtype), conv_w, tuple(c.astype(dtype) for c in cotangents))
            got = jax.jit(lambda *a: answers(_gdn_prologue_pallas, *a))(*args)
            want = jax.jit(lambda *a: answers(_gdn_prologue_xla, *a))(*args)
            for part, a, b, tol in zip(("q", "k", "v", "z", "dqkvz", "dconv_w"),
                                       got, want, tols):
                yield _check(f"gdn-prologue{tag}-{jnp.dtype(dtype).name}-{part}", a, b, tol)


def run(seed: int = 0) -> Iterator[dict]:
    """All parity checks, one record each. One seeded generator feeds them
    in this order, so a record's inputs do not depend on which passed."""
    rng = np.random.default_rng(seed)
    for group in (_attention, _rmsnorm, _paged, _matmuls,
                  _alibi_flash, _fused_decode, _gated_delta, _gdn_prologue):
        yield from group(rng)
