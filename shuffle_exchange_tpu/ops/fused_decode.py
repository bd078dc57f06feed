"""Fused Pallas decode path: per-layer serving kernels for 1-token steps.

TPU replacement for the reference FastGen per-layer decode fusion
(``inference/v2/kernels/ragged_ops/linear_blocked_kv_rotary`` +
``blocked_flash`` + the core-ops gated MLP, driven from
``model_implementations/llama_v2/model.py:133-175``, SURVEY.md §2.10/§2.13).
A one-token decode step is bound by the bytes of the weights it reads; left
to XLA the layer body lowers to many small ops, each bouncing [B, D]-sized
activations through HBM and re-reading weights per op. The three kernels
here stream every weight matrix through VMEM exactly once per step:

  1. :func:`fused_qkv_rope` — QKV projection + bias + RoPE + (optionally)
     the paged-KV append, writing the new token's K/V straight into the
     block pool via ``input_output_aliases`` (no pool copy; the
     ``linear_blocked_kv_rotary`` analog).
  2. :func:`fused_paged_decode_attention` — paged flash-decode over the
     block pool with all KV heads per grid step and a split-K partial
     reduction (FlashDecoding-style): per-split (m, l, acc) partials merge
     in one tiny XLA epilogue, the block-table index map clamps past each
     sequence's last block so padded table entries cost no DMA, and the
     split grid dimension is marked parallel for Megacore.
  3. :func:`fused_mlp` — residual + norm + (gated) MLP in one kernel,
     streaming bf16 weights once; int8/int4/fp8 ``QuantizedMatrix``
     storage (ops/quant_matmul.py) dequantizes block-wise into the MXU so
     quantized weights cross HBM at storage width.

RoPE rides in a flat-layout formulation chosen for Mosaic: the host
pre-expands the per-position cos/sin rows to the full projection width and
the kernel applies rotate-half as a lane roll + sign mask — no in-kernel
reshape or per-head slicing (the constructs the round-5 on-chip bringup
showed Mosaic rejects or relayouts expensively).

Dispatch: ``inference.config.InferenceConfig.decode_kernel``
(``auto | pallas | xla``) resolved by ``ops.dispatch.resolve_decode_kernel``;
model-structure eligibility lives in
``models.transformer.decode_fusion_eligibility``. Parity is tested in CPU
interpret mode and the kernels are lowering-gated in
``tests/test_mosaic_lowering.py``.
"""

from __future__ import annotations

from typing import Optional

_NEG_INF = -1e30

# activations the fused MLP kernel can LOWER (exact "gelu" is excluded:
# Mosaic has no erf/erfc primitive — verified against jax.export
# platforms=["tpu"]; the tanh family lowers fine). Interpret mode accepts
# anything models.transformer.activation_fn does.
FUSABLE_ACTIVATIONS = ("swiglu", "silu", "relu", "gelu_new",
                       "gelu_pytorch_tanh")


# Mosaic's default scoped-VMEM limit on v5e is 16 MiB; the chip has 128.
# Each kernel sums its own buffers (pipelined blocks count twice: they are
# double-buffered) and asks for that plus headroom, so a wide model at a
# large decode batch compiles instead of dying in "Ran out of memory in
# memory space vmem". Past _VMEM_CAP the kernel raises at trace time.
_VMEM_FLOOR = 32 << 20
_VMEM_CAP = 100 << 20
# most a kernel streams per grid step (both pipeline buffers of every
# weight block): past this the block shrinks rather than the limit grows
_W_STREAM_BYTES = 24 << 20


def _vmem_limit(need_bytes: int, what: str) -> int:
    """``vmem_limit_bytes`` for a kernel whose buffers total ``need_bytes``."""
    limit = max(_VMEM_FLOOR, need_bytes * 3 // 2)
    if limit > _VMEM_CAP:
        raise ValueError(
            f"{what}: buffers need {need_bytes / 2**20:.1f} MiB of VMEM "
            f"(cap {_VMEM_CAP >> 20} MiB) - the decode batch is too large "
            f"for the fused kernels; lower serving.max_running or set "
            f'decode_kernel: "xla"')
    return limit


def _nbytes(shape, dtype) -> int:
    import numpy as np

    n = np.dtype(dtype).itemsize
    for d in shape:
        n *= int(d)
    return n


def _pad_rows(x, rows: int):
    import jax.numpy as jnp

    if x.shape[0] == rows:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _pick_block(dim: int, want: int) -> int:
    """Largest power-of-two-ish divisor of ``dim`` not exceeding ``want``."""
    b = min(want, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def expand_rope_tables(cos, sin, n_heads: int, head_dim: int):
    """Per-position rope rows [B, rd/2] -> flat-layout (cos_f, sin_f)
    [B, n_heads * head_dim] for the fused QKV kernel.

    Layout per head: dims [0, rd/2) and [rd/2, rd) both carry the row's
    cos/sin (rotate-half pairs d and d + rd/2 share an angle); dims >= rd
    (partial rotary pass-through) get cos 1 / sin 0, which makes the
    kernel's masked lane-roll a no-op there.
    """
    import jax.numpy as jnp

    B, rd2 = cos.shape
    pad = head_dim - 2 * rd2
    ones = jnp.ones((B, pad), cos.dtype)
    zeros = jnp.zeros((B, pad), sin.dtype)
    cos_h = jnp.concatenate([cos, cos, ones], axis=-1)     # [B, Dh]
    sin_h = jnp.concatenate([sin, sin, zeros], axis=-1)
    return (jnp.tile(cos_h, (1, n_heads)), jnp.tile(sin_h, (1, n_heads)))


def _rope_flat(x, cos_f, sin_f, head_dim: int, rd2: int):
    """Rotate-half RoPE on the flat [B, H*Dh] projection.

    For head-local dim d < rd2: out = x*cos - x[d + rd2]*sin; for
    rd2 <= d < 2*rd2: out = x*cos + x[d - rd2]*sin. Both partners are a
    lane roll by rd2 (heads are Dh-aligned so the roll never crosses a
    head for dims the sin mask keeps); pass-through dims have sin == 0.
    """
    import jax
    import jax.numpy as jnp

    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    first_half = (col % head_dim) < rd2
    rolled_l = jnp.roll(x, -rd2, axis=-1)    # partner for the first half
    rolled_r = jnp.roll(x, rd2, axis=-1)     # partner for the second half
    partner = jnp.where(first_half, -rolled_l, rolled_r)
    return x * cos_f + partner * sin_f


# ---------------------------------------------------------------------------
# 1. Fused QKV projection + RoPE (+ paged-KV append)
# ---------------------------------------------------------------------------


# rows moved per pool DMA: one (8, 128) HBM tile per kv head. Mosaic
# refuses a DMA slice that is not whole tiles, and a single bf16 row is
# not even contiguous (the (2, 1) sub-tiling interleaves row pairs), so
# the append is a read-modify-write of the 8-row group holding the slot.
_APPEND_ROWS = 8


def qkv_append_route(pool_shape, pool_dtype) -> str:
    """How :func:`fused_qkv_rope_pallas` appends into a pool of this
    geometry: ``"dma"`` (in-kernel, in place) when the pool's rows are whole
    HBM tiles - head_dim a multiple of 128 lanes, block_size a multiple of
    8 rows, 2- or 4-byte storage - else ``"scatter"`` (the same rows written
    by an XLA scatter after the kernel; Mosaic cannot slice a lane-padded
    HBM array at all, which is what a Dh=64 pool is)."""
    import numpy as np

    bs, Dh = pool_shape[-2:]
    if (Dh % 128 == 0 and bs % _APPEND_ROWS == 0
            and np.dtype(pool_dtype).itemsize in (2, 4)):
        return "dma"
    return "scatter"


def fused_qkv_rope_pallas(y, wq, wk, wv, bq=None, bk=None, bv=None,
                          cos=None, sin=None, *, n_heads: int, kv_heads: int,
                          pool_k=None, pool_v=None, blk=None, off=None,
                          layer=None, block_k: int = 512,
                          interpret: bool = False):
    """One token per sequence: q/k/v projections + bias + RoPE, optionally
    appending the new K/V into the paged pool in place.

    y [B, D] (normalized hidden); wq [D, H*Dh]; wk/wv [D, KV*Dh]; biases
    flat [N]; cos/sin [B, rd/2] rope rows at each sequence's position
    (None = no RoPE). Returns (q [B, H, Dh], k [B, KV, Dh], v [B, KV, Dh])
    - plus, when ``pool_k``/``pool_v`` ([nblk, KV, bs, Dh], or the stacked
    [L, ...] pool with ``layer``) and per-sequence ``blk``/``off`` indices
    are given, the pool pair with row (blk[b], :, off[b], :) overwritten
    (``input_output_aliases``: the caller's buffer is updated, not copied;
    see :func:`qkv_append_route` for the two ways the rows get there).

    Weights stream through VMEM once (grid over D); accumulation f32.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, D = y.shape
    Nq = wq.shape[1]
    Nkv = wk.shape[1]
    H, KV = n_heads, kv_heads
    Dh = Nq // H
    assert Nq == H * Dh and Nkv == KV * Dh, (y.shape, wq.shape, wk.shape)
    pooled = pool_k is not None and pool_k.ndim == 5
    if pooled and layer is None:
        raise ValueError("stacked [L, ...] pool needs a layer index")
    append = (pool_k is not None
              and qkv_append_route(pool_k.shape, pool_k.dtype) == "dma")
    has_rope = cos is not None
    rd2 = cos.shape[-1] if has_rope else 0
    rg = _APPEND_ROWS

    Bp = max(8, -(-B // 8) * 8)
    yp = _pad_rows(y, Bp)
    bk_blk = _pick_block(D, block_k)
    nk = D // bk_blk

    rope_in = ()
    if has_rope:
        cq, sq = expand_rope_tables(cos, sin, H, Dh)
        ck_, sk_ = expand_rope_tables(cos, sin, KV, Dh)
        rope_in = tuple(_pad_rows(t.astype(jnp.float32), Bp)
                        for t in (cq, sq, ck_, sk_))
    bias_in = ()
    has_bias = bq is not None
    if has_bias:
        bias_in = (bq.reshape(1, Nq).astype(jnp.float32),
                   bk.reshape(1, Nkv).astype(jnp.float32),
                   bv.reshape(1, Nkv).astype(jnp.float32))

    n_prefetch = 0
    scalar_in = ()
    pool_in = ()
    if append:
        scalar_in = (jnp.asarray(blk, jnp.int32), jnp.asarray(off, jnp.int32))
        n_prefetch = 2
        if pooled:
            scalar_in += (jnp.asarray(layer, jnp.int32).reshape(1),)
            n_prefetch = 3
        pool_in = (pool_k, pool_v)

    def kernel(*refs):
        refs = list(refs)
        scalars = [refs.pop(0) for _ in range(n_prefetch)]
        y_ref, wq_ref, wk_ref, wv_ref = refs[:4]
        rest = refs[4:]
        if has_bias:
            bq_ref, bk_ref, bv_ref, *rest = rest
        if has_rope:
            cq_ref, sq_ref, ck_ref, sk_ref, *rest = rest
        if append:
            _pk_in, _pv_in, *rest = rest
            q_out, k_out, v_out, pk_out, pv_out = rest[:5]
            rest = rest[5:]
        else:
            q_out, k_out, v_out = rest[:3]
            rest = rest[3:]
        qacc, kacc, vacc = rest[:3]
        if append:
            kst, vst, sems = rest[3:6]
        kstep = pl.program_id(0)

        @pl.when(kstep == 0)
        def _init():
            qacc[...] = jnp.zeros_like(qacc)
            kacc[...] = jnp.zeros_like(kacc)
            vacc[...] = jnp.zeros_like(vacc)

        yb = y_ref[...]
        qacc[...] += jax.lax.dot(yb, wq_ref[...],
                                 preferred_element_type=jnp.float32)
        kacc[...] += jax.lax.dot(yb, wk_ref[...],
                                 preferred_element_type=jnp.float32)
        vacc[...] += jax.lax.dot(yb, wv_ref[...],
                                 preferred_element_type=jnp.float32)

        @pl.when(kstep == nk - 1)
        def _emit():
            qv, kv_, vv = qacc[...], kacc[...], vacc[...]
            if has_bias:
                qv = qv + bq_ref[...]
                kv_ = kv_ + bk_ref[...]
                vv = vv + bv_ref[...]
            if has_rope:
                qv = _rope_flat(qv, cq_ref[...], sq_ref[...], Dh, rd2)
                kv_ = _rope_flat(kv_, ck_ref[...], sk_ref[...], Dh, rd2)
            q_out[...] = qv.astype(q_out.dtype)
            k_out[...] = kv_.astype(k_out.dtype)
            v_out[...] = vv.astype(v_out.dtype)
            if not append:
                return
            lead = (scalars[2][0],) if pooled else ()

            def group(pool_ref, b):
                # the aligned _APPEND_ROWS-row group of block blk[b] that
                # holds slot off[b], all kv heads: [KV, rg, Dh]
                g0 = pl.multiple_of((scalars[1][b] // rg) * rg, rg)
                return pool_ref.at[lead + (scalars[0][b], slice(None),
                                           pl.ds(g0, rg), slice(None))]

            def both_ways(to_vmem: bool):
                copies = []
                for b in range(B):
                    for i, (pool_ref, st) in enumerate(((pk_out, kst),
                                                        (pv_out, vst))):
                        src, dst = group(pool_ref, b), st.at[b]
                        if not to_vmem:
                            src, dst = dst, src
                        copies.append(pltpu.make_async_copy(
                            src, dst, sems.at[i, b]))
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()

            both_ways(to_vmem=True)
            # the rows land exactly as k_out/v_out hold them (rounded to
            # the activation dtype first, as the XLA append does)
            knew = kv_.astype(k_out.dtype).astype(jnp.float32)
            vnew = vv.astype(v_out.dtype).astype(jnp.float32)
            row = jax.lax.broadcasted_iota(jnp.int32, (rg, Dh), 0)
            for b in range(B):
                hit = row == scalars[1][b] % rg
                for h in range(KV):
                    cols = slice(h * Dh, (h + 1) * Dh)
                    for st, new in ((kst, knew), (vst, vnew)):
                        st[b, h] = jnp.where(
                            hit, new[b:b + 1, cols],
                            st[b, h].astype(jnp.float32)).astype(st.dtype)
            both_ways(to_vmem=False)

    y_spec = pl.BlockSpec((Bp, bk_blk), lambda k, *_: (0, k))
    w_specs = [pl.BlockSpec((bk_blk, Nq), lambda k, *_: (k, 0)),
               pl.BlockSpec((bk_blk, Nkv), lambda k, *_: (k, 0)),
               pl.BlockSpec((bk_blk, Nkv), lambda k, *_: (k, 0))]
    full = lambda shape: pl.BlockSpec(shape, lambda k, *_: (0,) * len(shape))
    in_specs = [y_spec] + w_specs
    if has_bias:
        in_specs += [full((1, Nq)), full((1, Nkv)), full((1, Nkv))]
    if has_rope:
        in_specs += [full((Bp, Nq)), full((Bp, Nq)),
                     full((Bp, Nkv)), full((Bp, Nkv))]
    N = Nq + 2 * Nkv
    out_shapes = [jax.ShapeDtypeStruct((Bp, Nq), y.dtype),
                  jax.ShapeDtypeStruct((Bp, Nkv), y.dtype),
                  jax.ShapeDtypeStruct((Bp, Nkv), y.dtype)]
    out_specs = [full((Bp, Nq)), full((Bp, Nkv)), full((Bp, Nkv))]
    scratch = [pltpu.VMEM((Bp, Nq), jnp.float32),
               pltpu.VMEM((Bp, Nkv), jnp.float32),
               pltpu.VMEM((Bp, Nkv), jnp.float32)]
    vmem = (2 * (_nbytes((Bp, bk_blk), y.dtype) + _nbytes((bk_blk, N), wq.dtype)
                 + _nbytes((Bp, N), y.dtype))
            + _nbytes((Bp, N), jnp.float32)
            + (2 * 2 * _nbytes((Bp, N), jnp.float32) if has_rope else 0))
    aliases = {}
    if append:
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs += [any_spec, any_spec]
        out_shapes += [jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
                       jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype)]
        out_specs += [any_spec, any_spec]
        scratch += [pltpu.VMEM((B, KV, rg, Dh), pool_k.dtype),
                    pltpu.VMEM((B, KV, rg, Dh), pool_v.dtype),
                    pltpu.SemaphoreType.DMA((2, B))]
        vmem += 2 * _nbytes((B, KV, rg, Dh), pool_k.dtype)
        # operand order: scalar prefetch args come first in the alias count
        base = n_prefetch + len(in_specs) - 2
        aliases = {base: 3, base + 1: 4}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(nk,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        kernel,
        name="sxt_fused_qkv_rope_append",
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=append,
            vmem_limit_bytes=_vmem_limit(vmem, "fused QKV")),
    )(*scalar_in, yp, wq, wk, wv, *bias_in, *rope_in, *pool_in)
    q3 = outs[0][:B].reshape(B, H, Dh)
    k3 = outs[1][:B].reshape(B, KV, Dh)
    v3 = outs[2][:B].reshape(B, KV, Dh)
    if append:
        return q3, k3, v3, outs[3], outs[4]
    if pool_k is None:
        return q3, k3, v3
    # "scatter" route: advanced (blk, off) indices around the KV slice
    # address [B, KV, Dh] rows, matching k3/v3 (inference/paged.py
    # append_token_kv, minus the block-table lookup the caller already did)
    at = ((layer,) if pooled else ()) + (jnp.asarray(blk, jnp.int32),
                                         slice(None),
                                         jnp.asarray(off, jnp.int32))
    return (q3, k3, v3, pool_k.at[at].set(k3.astype(pool_k.dtype)),
            pool_v.at[at].set(v3.astype(pool_v.dtype)))


# ---------------------------------------------------------------------------
# 2. Fused paged flash-decode attention (split-K, all KV heads per step)
# ---------------------------------------------------------------------------


def fused_paged_decode_attention_pallas(q, ck, cv, block_table, kv_len, *,
                                        alibi_slopes=None, layer=None,
                                        k_scale=None, v_scale=None,
                                        num_splits: int = 2,
                                        interpret: bool = False):
    """q [B,1,H,Dh] against the paged pool ck/cv [nblk,KV,bs,Dh] (or the
    stacked [L,...] pool with ``layer``); block_table [B,maxblk] (-1 pad);
    kv_len [B] -> [B,1,H,Dh]. int8/fp8 pools ride with per-token-per-head
    ``k_scale``/``v_scale`` planes [(L,) nblk, KV, bs]: each streamed
    block dequantizes IN-REGISTER, so KV crosses HBM at storage width
    (kv_cache_dtype — decode is KV-bandwidth-bound).

    Differences from ``ops.paged_attention.paged_decode_attention_pallas``
    (which stays as the per-kv-head streaming form):

      - ALL KV heads per grid step: one [KV, bs, Dh] DMA instead of KV
        separate [bs, Dh] DMAs — bigger transfers, KV still read once.
      - split-K (FlashDecoding): the block axis is divided into
        ``num_splits`` independent partial reductions whose (m, l, acc)
        merge in a tiny XLA epilogue; the split grid dim is marked
        ``parallel`` so Megacore chips run splits concurrently.
      - past-the-end table entries clamp to the sequence's last valid
        block in the index map (an unchanged index skips the DMA), and
        their grid steps skip compute entirely — short sequences in a
        padded table stop paying for the padding.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, one, H, Dh = q.shape
    assert one == 1, "decode kernel: one query token per sequence"
    pooled = ck.ndim == 5
    if pooled and layer is None:
        raise ValueError("stacked [L, ...] pool needs a layer index")
    nblk, KV, bs, _ = ck.shape[1:] if pooled else ck.shape
    assert H % KV == 0, "GQA requires H % KV == 0"
    G = H // KV
    maxblk = block_table.shape[1]
    nsplit = max(1, min(int(num_splits), maxblk))
    spb = -(-maxblk // nsplit)
    scale = Dh ** -0.5

    q3 = q.reshape(B, H, Dh)     # heads are kv-major: head h -> kv h // G
    bt = jnp.maximum(block_table, 0).astype(jnp.int32)
    kvl = kv_len.astype(jnp.int32)
    layer_in = ((jnp.asarray(layer, jnp.int32).reshape(1),) if pooled else ())
    n_prefetch = 3 if pooled else 2
    has_alibi = alibi_slopes is not None
    quant = k_scale is not None
    scales_in = ()
    if quant:
        from .paged_attention import _scale_operand

        scales_in = (_scale_operand(k_scale, pooled),
                     _scale_operand(v_scale, pooled))
    slopes_in = ()
    if has_alibi:
        slopes_in = (jnp.asarray(alibi_slopes, jnp.float32).reshape(H, 1),)

    def kernel(bt_ref, kvl_ref, *rest):
        if pooled:
            _layer_ref, q_ref, k_ref, v_ref, *rest = rest
        else:
            q_ref, k_ref, v_ref, *rest = rest
        if quant:
            ks_ref, vs_ref, *rest = rest
        if has_alibi:
            sl_ref, o_ref, m_out, l_out, m_ref, l_ref, acc_ref = rest
        else:
            o_ref, m_out, l_out, m_ref, l_ref, acc_ref = rest
        b = pl.program_id(0)
        s = pl.program_id(1)
        jj = pl.program_id(2)
        j = s * spb + jj

        @pl.when(jj == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        nb = (kvl_ref[b] + bs - 1) // bs

        @pl.when(j < nb)
        def _accumulate():
            kv_blk = (lambda r: r[0, 0]) if pooled else (lambda r: r[0])
            kb = kv_blk(k_ref)                               # [KV, bs, Dh]
            vb = kv_blk(v_ref)
            if quant:
                # per-token-per-head dequant in-register: the streamed
                # block crossed HBM at storage width (kv_cache_dtype)
                ksb = kv_blk(ks_ref)                         # [KV, 1, bs]
                vsb = kv_blk(vs_ref)
            for kv in range(KV):
                rows = slice(kv * G, (kv + 1) * G)
                qv = q_ref[0, rows, :].astype(jnp.float32) * scale   # [G, Dh]
                kk = kb[kv].astype(jnp.float32)                      # [bs, Dh]
                vv = vb[kv].astype(jnp.float32)
                if quant:
                    kk = kk * ksb[kv, 0][:, None]
                    vv = vv * vsb[kv, 0][:, None]
                sc = jax.lax.dot_general(
                    qv, kk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)              # [G, bs]
                token_pos = j * bs + jax.lax.broadcasted_iota(
                    jnp.int32, (G, bs), 1)
                if has_alibi:
                    sc = sc + sl_ref[rows, :] * token_pos.astype(jnp.float32)
                sc = jnp.where(token_pos < kvl_ref[b], sc, _NEG_INF)
                m_prev = m_ref[rows, :]                              # [G, 1]
                m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(sc - m_new)                              # [G, bs]
                l_ref[rows, :] = l_ref[rows, :] * alpha + p.sum(
                    axis=1, keepdims=True)
                pv = jax.lax.dot_general(
                    p, vv, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)              # [G, Dh]
                acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
                m_ref[rows, :] = m_new

        @pl.when(jj == spb - 1)
        def _emit():
            o_ref[0, 0] = acc_ref[...]
            m_out[0, 0] = m_ref[...]
            l_out[0, 0] = l_ref[...]

    def kv_index(b, s, jj, bt_ref, kvl_ref, *maybe_layer):
        j = s * spb + jj
        nb = (kvl_ref[b] + bs - 1) // bs
        jc = jnp.minimum(j, jnp.maximum(nb - 1, 0))
        if pooled:
            return (maybe_layer[0][0], bt_ref[b, jc], 0, 0, 0)
        return (bt_ref[b, jc], 0, 0, 0)

    kv_block = (1, 1, KV, bs, Dh) if pooled else (1, KV, bs, Dh)
    in_specs = [
        pl.BlockSpec((1, H, Dh), lambda b, s, jj, *_: (b, 0, 0)),
        pl.BlockSpec(kv_block, kv_index),
        pl.BlockSpec(kv_block, kv_index),
    ]
    if quant:
        # scale planes ride the same clamped block index; the singleton
        # second-minor axis keeps the (…, 1, bs) block Mosaic-legal
        scale_block = (1, 1, KV, 1, bs) if pooled else (1, KV, 1, bs)
        in_specs += [pl.BlockSpec(scale_block, kv_index)] * 2
    if has_alibi:
        in_specs.append(pl.BlockSpec((H, 1), lambda b, s, jj, *_: (0, 0)))
    part_spec = lambda last: pl.BlockSpec(
        (1, 1, H, last), lambda b, s, jj, *_: (b, s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, nsplit, spb),
        in_specs=in_specs,
        out_specs=[part_spec(Dh), part_spec(1), part_spec(1)],
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        name="sxt_fused_paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nsplit, H, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, nsplit, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, nsplit, H, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bt, kvl, *layer_in, q3, ck, cv, *scales_in, *slopes_in)

    # split-K merge: renormalize each split's partial sums to the global
    # row max, then combine (empty splits carry m=-inf, l=0 -> weight 0)
    m_g = jnp.max(m_part, axis=1, keepdims=True)             # [B, 1, H, 1]
    w = jnp.exp(m_part - m_g)                                # [B, S, H, 1]
    l = jnp.sum(w * l_part, axis=1)                          # [B, H, 1]
    o = jnp.sum(w * o_part, axis=1)                          # [B, H, Dh]
    out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype).reshape(B, 1, H, Dh)


# ---------------------------------------------------------------------------
# 3. Fused residual + norm + MLP
# ---------------------------------------------------------------------------


def _norm_in_kernel(x32, w_ref, b_ref, kind: str, eps: float):
    import jax
    import jax.numpy as jnp

    if kind == "rmsnorm":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return x32 * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mean) * (1.0 / jnp.sqrt(var + eps))
    return out * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)


def _act_fn(activation: str):
    import jax

    if activation in ("swiglu", "silu"):
        return jax.nn.silu
    from ..models.transformer import activation_fn

    return activation_fn(activation)


def fused_mlp_pallas(resid, y_src, ln_w, ln_b, w_up, w_down, w_gate=None,
                     b_up=None, b_down=None, *, norm: str = "rmsnorm",
                     eps: float = 1e-5, activation: str = "swiglu",
                     apply_norm: bool = True, block_f: int = 256,
                     interpret: bool = False):
    """``resid + mlp(norm(y_src))`` in one kernel, streaming dense bf16
    weights once (grid over the hidden dim F).

    resid/y_src [B, D]; w_gate/w_up [D, F]; w_down [F, D]; biases [F]/[D]
    (gelu-family path). ``w_gate`` set => gated (swiglu) form. With
    ``apply_norm=False`` the norm is skipped (GPT-J parallel blocks whose
    y2 is the already-normalized y1). Quantized weights take
    :func:`fused_mlp_quant_pallas`.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, D = resid.shape
    F = w_up.shape[1]
    gated = w_gate is not None
    act = _act_fn(activation)
    Bp = max(8, -(-B // 8) * 8)
    rp = _pad_rows(resid, Bp)
    yp = _pad_rows(y_src, Bp)
    n_w = 3 if gated else 2
    # the streamed weight blocks are double-buffered; halve the F-chunk
    # (down to one lane tile) while a step's worth passes _W_STREAM_BYTES
    bf = _pick_block(F, block_f)
    while bf > 128 and 2 * n_w * _nbytes((D, bf), w_up.dtype) > _W_STREAM_BYTES:
        bf = _pick_block(F, bf // 2)
    nf = F // bf
    vmem = (2 * n_w * _nbytes((D, bf), w_up.dtype)          # weight blocks
            + 2 * 3 * _nbytes((Bp, D), resid.dtype)         # resid, y, out
            + _nbytes((Bp, D), resid.dtype)                 # yn scratch
            + _nbytes((Bp, D), jnp.float32)                 # acc scratch
            + 3 * _nbytes((Bp, bf), jnp.float32))           # u, g, act
    lnw = ln_w.reshape(1, D)
    lnb = (ln_b.reshape(1, D) if (apply_norm and norm == "layernorm"
                                  and hasattr(ln_b, "reshape"))
           else jnp.zeros((1, D), jnp.float32))
    has_bias = b_up is not None
    bias_in = ()
    if has_bias:
        bias_in = (b_up.reshape(1, F).astype(jnp.float32),
                   b_down.reshape(1, D).astype(jnp.float32))

    def kernel(*refs):
        r_ref, y_ref, lnw_ref, lnb_ref = refs[:4]
        rest = list(refs[4:])
        wg_ref = rest.pop(0) if gated else None
        wu_ref, wd_ref = rest.pop(0), rest.pop(0)
        if has_bias:
            bu_ref, bd_ref = rest.pop(0), rest.pop(0)
        o_ref, yn_ref, acc_ref = rest[:3]
        f = pl.program_id(0)

        @pl.when(f == 0)
        def _init():
            x32 = y_ref[...].astype(jnp.float32)
            if apply_norm:
                x32 = _norm_in_kernel(x32, lnw_ref, lnb_ref, norm, eps)
            yn_ref[...] = x32.astype(yn_ref.dtype)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        yn = yn_ref[...]
        u = jax.lax.dot(yn, wu_ref[...], preferred_element_type=jnp.float32)
        if has_bias:
            u = u + bu_ref[...]
        if gated:
            g = jax.lax.dot(yn, wg_ref[...],
                            preferred_element_type=jnp.float32)
            a = act(g) * u
        else:
            a = act(u)
        acc_ref[...] += jax.lax.dot(a.astype(yn.dtype), wd_ref[...],
                                    preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _emit():
            out = r_ref[...].astype(jnp.float32) + acc_ref[...]
            if has_bias:
                out = out + bd_ref[...]
            o_ref[...] = out.astype(o_ref.dtype)

    full = lambda shape: pl.BlockSpec(shape, lambda f: (0,) * len(shape))
    in_specs = [full((Bp, D)), full((Bp, D)), full((1, D)), full((1, D))]
    if gated:
        in_specs.append(pl.BlockSpec((D, bf), lambda f: (0, f)))
    in_specs += [pl.BlockSpec((D, bf), lambda f: (0, f)),
                 pl.BlockSpec((bf, D), lambda f: (f, 0))]
    if has_bias:
        in_specs += [pl.BlockSpec((1, bf), lambda f: (0, f)), full((1, D))]
    weights = ((w_gate, w_up, w_down) if gated else (w_up, w_down))
    out = pl.pallas_call(
        kernel,
        name="sxt_fused_mlp",
        grid=(nf,),
        in_specs=in_specs,
        out_specs=full((Bp, D)),
        out_shape=jax.ShapeDtypeStruct((Bp, D), resid.dtype),
        scratch_shapes=[pltpu.VMEM((Bp, D), resid.dtype),
                        pltpu.VMEM((Bp, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(vmem, "fused MLP")),
    )(rp, yp, lnw, lnb, *weights, *bias_in)
    return out[:B]


def fused_mlp_quant_pallas(resid, y_src, ln_w, ln_b, w_up, w_down,
                           w_gate=None, *, norm: str = "rmsnorm",
                           eps: float = 1e-5, activation: str = "swiglu",
                           apply_norm: bool = True,
                           interpret: bool = False):
    """Quantized-storage variant of :func:`fused_mlp_pallas`: w_gate/w_up/
    w_down are int8 / packed-int4 / fp8(e4m3) :class:`QuantizedMatrix`
    leaves (ops/quant_matmul.py) sharing one group size; blocks dequantize
    in VMEM so the weights cross HBM at storage width (the reference
    mixed_gemm / FP-quantizer serving GEMMs). The hidden dim streams in
    one-scale-group chunks (the quant-matmul kernel's bk == group_size
    discipline, which keeps scale blocks Mosaic-legal).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .quant_matmul import QuantizedMatrix, _unpack_int4

    B, D = resid.shape
    gated = w_gate is not None
    qms = [w for w in (w_gate, w_up, w_down) if w is not None]
    if not all(isinstance(w, QuantizedMatrix) for w in qms):
        raise ValueError("fused_mlp_quant_pallas needs QuantizedMatrix "
                         "weights; use fused_mlp_pallas for dense")
    gs = qms[0].group_size
    bits = qms[0].bits
    if any(w.group_size != gs or w.bits != bits for w in qms):
        raise ValueError("fused MLP: mixed group_size/bits across the MLP "
                         f"weights ({[(w.bits, w.group_size) for w in qms]})")
    F = w_up.shape[1]
    if D % gs or F % gs:
        raise ValueError(f"fused MLP: D={D} and F={F} must be multiples of "
                         f"group_size={gs}")
    int4 = bits == 4
    act = _act_fn(activation)
    Bp = max(8, -(-B // 8) * 8)
    rp = _pad_rows(resid, Bp)
    yp = _pad_rows(y_src, Bp)
    bf = gs                       # one scale group per streamed F-chunk
    nf = F // bf
    nk = D // gs
    lnw = ln_w.reshape(1, D)
    lnb = (ln_b.reshape(1, D) if (apply_norm and norm == "layernorm"
                                  and hasattr(ln_b, "reshape"))
           else jnp.zeros((1, D), jnp.float32))

    def deq(q_blk, s_row):
        """One-K-group block [gs(/2), n] + its scale row [1, n] -> f32."""
        if int4:
            w = _unpack_int4(q_blk, gs).astype(jnp.float32)
        else:
            w = q_blk.astype(jnp.float32)
        return w * s_row

    def kernel(*refs):
        (r_ref, y_ref, lnw_ref, lnb_ref), rest = refs[:4], list(refs[4:])
        if gated:
            qg_ref, sg_ref = rest.pop(0), rest.pop(0)
        qu_ref, su_ref = rest.pop(0), rest.pop(0)
        qd_ref, sd_ref = rest.pop(0), rest.pop(0)
        o_ref, yn_ref, gacc_ref, uacc_ref, acc_ref = rest[:5]
        f = pl.program_id(0)
        k = pl.program_id(1)

        @pl.when((f == 0) & (k == 0))
        def _norm_once():
            x32 = y_ref[...].astype(jnp.float32)
            if apply_norm:
                x32 = _norm_in_kernel(x32, lnw_ref, lnb_ref, norm, eps)
            yn_ref[...] = x32.astype(yn_ref.dtype)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(k == 0)
        def _init():
            gacc_ref[...] = jnp.zeros_like(gacc_ref)
            uacc_ref[...] = jnp.zeros_like(uacc_ref)

        yk = yn_ref[:, pl.ds(k * gs, gs)]                      # [Bp, gs]
        uacc_ref[...] += jax.lax.dot(yk, deq(qu_ref[...], su_ref[0]),
                                     preferred_element_type=jnp.float32)
        if gated:
            gacc_ref[...] += jax.lax.dot(yk, deq(qg_ref[...], sg_ref[0]),
                                         preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _down():
            u = uacc_ref[...]
            a = act(gacc_ref[...]) * u if gated else act(u)
            acc_ref[...] += jax.lax.dot(
                a.astype(yn_ref.dtype), deq(qd_ref[...], sd_ref[0]),
                preferred_element_type=jnp.float32)

        @pl.when((k == nk - 1) & (f == nf - 1))
        def _emit():
            out = r_ref[...].astype(jnp.float32) + acc_ref[...]
            o_ref[...] = out.astype(o_ref.dtype)

    def q_up_spec():
        # K-grid slices one scale group of rows; int4 packs row pairs so
        # the group's packed rows are contiguous and half as tall
        if int4:
            return pl.BlockSpec((gs // 2, bf), lambda f, k: (k, f))
        return pl.BlockSpec((gs, bf), lambda f, k: (k, f))

    # scales ride as [nG, 1, N] (the quant-matmul layout: a (1, n) block
    # over raw [nG, N] scales violates Mosaic's second-minor rule)
    s_up_spec = pl.BlockSpec((1, 1, bf), lambda f, k: (k, 0, f))
    qd_spec = (pl.BlockSpec((bf // 2, D), lambda f, k: (f, 0)) if int4
               else pl.BlockSpec((bf, D), lambda f, k: (f, 0)))
    sd_spec = pl.BlockSpec((1, 1, D), lambda f, k: (f, 0, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda f, k: (0,) * len(shape))

    in_specs = [full((Bp, D)), full((Bp, D)), full((1, D)), full((1, D))]
    operands = [rp, yp, lnw, lnb]
    for qm, spec in (((w_gate, q_up_spec()),) if gated else ()) + (
            (w_up, q_up_spec()), (w_down, None)):
        if spec is None:
            in_specs += [qd_spec, sd_spec]
            operands += [qm.q, qm.scales.reshape(F // gs, 1, D)]
        else:
            in_specs += [spec, s_up_spec]
            operands += [qm.q, qm.scales.reshape(D // gs, 1, -1)]

    # dequantized blocks are f32 in VMEM whatever the storage width
    vmem = (2 * 3 * _nbytes((Bp, D), resid.dtype)           # resid, y, out
            + _nbytes((Bp, D), resid.dtype)                 # yn scratch
            + _nbytes((Bp, D), jnp.float32)                 # acc scratch
            + 4 * _nbytes((Bp, bf), jnp.float32)            # g/u acc, act
            + 2 * (2 * _nbytes((gs, bf), jnp.float32)       # up/gate blocks
                   + _nbytes((bf, D), jnp.float32))         # down block
            + 2 * _nbytes((bf, D), jnp.float32))            # its dequant
    out = pl.pallas_call(
        kernel,
        name="sxt_fused_mlp_quant",
        grid=(nf, nk),
        in_specs=in_specs,
        out_specs=full((Bp, D)),
        out_shape=jax.ShapeDtypeStruct((Bp, D), resid.dtype),
        scratch_shapes=[pltpu.VMEM((Bp, D), resid.dtype),
                        pltpu.VMEM((Bp, bf), jnp.float32),
                        pltpu.VMEM((Bp, bf), jnp.float32),
                        pltpu.VMEM((Bp, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(vmem, "fused quantized MLP")),
    )(*operands)
    return out[:B]


# ---------------------------------------------------------------------------
# Dispatching wrappers (the engines call these; kernels stay testable raw)
# ---------------------------------------------------------------------------


def _interpret_forced() -> bool:
    """Test hook: SXT_FUSED_INTERPRET=1 runs the fused kernels through the
    Pallas interpreter, letting the CPU suite drive the ENGINE-level fused
    path (decode_kernel="pallas") end to end. Alias of
    ``ops/dispatch.interpret_forced`` — one contract, one env var, shared
    with the grouped-GEMM seam (``resolve_grouped_gemm``)."""
    from .dispatch import interpret_forced

    return interpret_forced()


def fused_qkv_rope(y, wq, wk, wv, **kw):
    return fused_qkv_rope_pallas(y, wq, wk, wv,
                                 interpret=_interpret_forced(), **kw)


def fused_paged_decode_attention(q, ck, cv, block_table, kv_len, **kw):
    from ..inference.paged import kv_parts

    kq, ks = kv_parts(ck)
    vq, vs = kv_parts(cv)
    return fused_paged_decode_attention_pallas(
        q, kq, vq, block_table, kv_len, k_scale=ks, v_scale=vs,
        interpret=_interpret_forced(), **kw)


def fused_mlp(resid, y_src, ln_w, ln_b, w_up, w_down, w_gate=None, **kw):
    from .quant_matmul import QuantizedMatrix

    if isinstance(w_up, QuantizedMatrix):
        if kw.get("b_up") is not None or kw.get("b_down") is not None:
            # silently dropping the biases would return wrong values; the
            # engines route this combination to the XLA path instead
            raise ValueError("fused MLP: quantized weights with fc biases "
                             "are not supported (dequantize or use the XLA "
                             "path)")
        kw.pop("b_up", None), kw.pop("b_down", None)
        return fused_mlp_quant_pallas(resid, y_src, ln_w, ln_b, w_up, w_down,
                                      w_gate, interpret=_interpret_forced(),
                                      **kw)
    return fused_mlp_pallas(resid, y_src, ln_w, ln_b, w_up, w_down, w_gate,
                            interpret=_interpret_forced(), **kw)


def mlp_weights_fusable(w_up, w_down, w_gate=None) -> Optional[str]:
    """None when the fused MLP kernel can take these weights; otherwise a
    human-readable reason (the auto path logs it once and keeps XLA)."""
    from .quant_matmul import QuantizedMatrix

    ws = [w for w in (w_gate, w_up, w_down) if w is not None]
    quant = [isinstance(w, QuantizedMatrix) for w in ws]
    if not any(quant):
        return None
    if not all(quant):
        return "mixed dense/quantized MLP weights"
    gs, bits = ws[0].group_size, ws[0].bits
    if any(w.group_size != gs or w.bits != bits for w in ws):
        return "mixed group_size/bits across MLP weights"
    D, F = w_up.shape
    if D % gs or F % gs:
        return (f"D={D}/F={F} not multiples of quant group_size={gs}")
    if bits == 4 and gs % 2:
        return f"odd int4 group_size={gs}"
    return None
