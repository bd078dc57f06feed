"""Sequence parallelism and long context.

Capability parity with the reference's SP stack (SURVEY.md §5.7):

- **Ulysses** (``sequence/layer.py:277,331`` ``_SeqAllToAll`` +
  ``DistributedAttention``): activations arrive sharded on the sequence dim;
  two all-to-alls swap seq↔head sharding around any core attention so each
  device sees full sequence for a subset of heads.
- **Ring attention** (the TPU-idiomatic replacement for FPDT chunked
  attention, ``sequence/fpdt_layer.py:510,971``): KV blocks rotate around
  the "seq" mesh axis via ``ppermute`` while each device keeps its Q shard,
  with online-softmax (log-sum-exp) accumulation — full-sequence attention
  with O(T/sp) activation memory and comm overlapped by XLA.
- **Tiled compute** (``runtime/sequence_parallel/ulysses_sp.py:757,915``
  TiledMLP / tiled loss): lax.map over sequence chunks bounds activation
  memory for the MLP and the logits/loss.
- **Vocab-parallel cross entropy** (``sequence/cross_entropy.py``): CE with
  logits sharded over the "tensor" axis, no full-vocab gather.

All functions are written for use inside ``shard_map`` (axis names must be
bound); pure-jit callers get the same math when the axis is size 1.
"""

from __future__ import annotations

from typing import Callable, Optional

from . import comm


# ----------------------------------------------------------------------
# Ulysses
# ----------------------------------------------------------------------


def seq_to_head_a2a(x, axis_name: str = "seq"):
    """[B, T/sp, H, D] -> [B, T, H/sp, D] (head-scatter, seq-gather).

    H must divide sp here; :class:`DistributedAttention` handles uneven
    head counts by padding before calling this (reference
    ``uneven_heads_all2all``, sequence/layer.py:111)."""
    import jax

    sp = jax.lax.psum(1, axis_name)
    if x.shape[2] % sp:
        raise ValueError(
            f"head count ({x.shape[2]}) not divisible by the sequence-parallel "
            f"degree ({sp}); route through DistributedAttention, which pads")
    return comm.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)


def head_to_seq_a2a(x, axis_name: str = "seq"):
    """[B, T, H/sp, D] -> [B, T/sp, H, D] (seq-scatter, head-gather)."""
    return comm.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)


class DistributedAttention:
    """Ulysses wrapper around any local attention fn (reference
    ``sequence/layer.py:331``): q/k/v sharded on seq dim in, output sharded
    on seq dim out.

    Uneven head counts (reference ``uneven_heads_all2all``,
    sequence/layer.py:111): when H (or the GQA kv count) does not divide the
    sp degree, heads are zero-padded — but GQA KV is NEVER expanded to H
    before the wire. The per-rank q-chunk is rounded up to a multiple of
    the GQA group size ``n_rep`` (Hc = ceil(H / sp / n_rep) * n_rep), so a
    contiguous head scatter keeps every q chunk colocated with exactly its
    kv groups: the kv all-to-all carries Hp/n_rep heads (a ceil-rounding
    factor over KV), not H (which would be n_rep x the bytes). The local
    attention sees unexpanded GQA kv and pad heads attend to zero kv heads
    whose outputs are sliced away after the reverse all-to-all."""

    def __init__(self, local_attention: Callable, sequence_axis: str = "seq",
                 scatter_idx: int = 2, gather_idx: int = 1):
        self.local_attn = local_attention
        self.axis = sequence_axis

    def __call__(self, q, k, v, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        sp = jax.lax.psum(1, self.axis)
        H, KV = q.shape[2], k.shape[2]
        even = H % sp == 0 and KV % sp == 0
        if not even:
            n_rep = H // KV
            # per-rank q chunk, rounded to whole GQA groups
            hc = -(-H // sp // n_rep) * n_rep
            hp, kvp = sp * hc, sp * hc // n_rep
            hp_expand = -(-H // sp) * sp   # old path: expand KV to H, pad
            # >= : on wire-byte ties the expand path wins — group-aligned
            # padding always has at least as much q padding, so it costs
            # strictly more local attention FLOPs for the same bytes.
            if hp + 2 * kvp >= 3 * hp_expand:
                # Group-aligned padding loses when ceil(H/sp) < n_rep
                # (MQA-ish KV with large sp: q pads to sp*n_rep heads).
                # Fall back to expanding KV to H — total wire heads
                # 3*hp_expand — whenever that is cheaper.
                from ..ops.flash_attention import _repeat_kv

                k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
                hp = kvp = hp_expand
            q = jnp.pad(q, ((0, 0), (0, 0), (0, hp - H), (0, 0)))
            k = jnp.pad(k, ((0, 0), (0, 0), (0, kvp - k.shape[2]), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, kvp - v.shape[2]), (0, 0)))
        qh = seq_to_head_a2a(q, self.axis)
        kh = seq_to_head_a2a(k, self.axis)
        vh = seq_to_head_a2a(v, self.axis)
        out = self.local_attn(qh, kh, vh, *args, **kwargs)
        out = head_to_seq_a2a(out, self.axis)
        return out if even else out[:, :, :H]


def ulysses_attention(q, k, v, axis_name: str = "seq", attn_fn: Optional[Callable] = None,
                      causal: bool = True):
    """Functional form of DistributedAttention."""
    from ..ops.flash_attention import flash_attention

    attn = attn_fn or (lambda q, k, v: flash_attention(q, k, v, causal=causal))
    return DistributedAttention(attn, axis_name)(q, k, v)


# ----------------------------------------------------------------------
# Ring attention (causal, online softmax)
# ----------------------------------------------------------------------


def _ring_kv_chunk(Tq: int, requested: int = 1024) -> int:
    """Largest divisor of Tq that is <= requested (flash-style kv tiling).
    Shard lengths with no usable divisor (prime-ish Tq would otherwise
    degrade to ck=1 — a Tq-step scan of rank-1 einsums) fall back to one
    whole-block chunk; remat still bounds backward residuals per hop."""
    c = min(Tq, requested)
    while Tq % c:
        c -= 1
    if c < min(64, Tq):
        return Tq
    return c


def _ring_hop_kernel_ok(q, interpret: bool) -> bool:
    """Can the per-hop Pallas flash kernel serve this ring? (mirrors the
    ALiBi-family gate: MXU-friendly blocks, supported head dim)."""
    from ..ops.dispatch import pallas_enabled
    from ..ops.flash_attention import _pick_block

    if not (pallas_enabled() or interpret):
        return False
    _, Tq, _, D = q.shape
    from ..ops.flash_attention import BLOCK_CANDIDATES

    bq = _pick_block(Tq, q.dtype.itemsize)
    # candidate blocks only — the n-itself fallback would be one giant tile
    return D in (64, 128) and Tq % bq == 0 and bq in BLOCK_CANDIDATES


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = True,
                   kv_chunk: int = 1024, use_kernel: str = "auto",
                   interpret: bool = False, alibi_slopes=None,
                   hop_remat: bool = True):
    """Blockwise full-sequence attention with rotating KV — flash-grade.

    q/k/v: [B, T_local, H|Hkv, D] — this device's sequence shard (layout
    matches ops.flash_attention). Must run inside shard_map with
    ``axis_name`` bound. Accumulation in fp32.

    Memory (VERDICT r3 weak #5): each ring hop is a CHECKPOINTED chunked
    online-softmax — the forward never holds more than one
    [B, H, T/sp, kv_chunk] logits tile, and backward recomputes the tiles
    per hop, so autodiff residuals are the O(T/sp * D) hop inputs
    (q, the rotated kv blocks, and the running (acc, m, l) carry), never
    [T/sp, T/sp] score matrices.

    Compute (round 5, VERDICT r4 #5 / SURVEY §5.7 "splash kernel +
    ppermute"): when the shapes pass :func:`_ring_hop_kernel_ok`, each hop
    runs the Pallas :func:`~..ops.alibi_attention.flash_attention_lse`
    kernel (diagonal hop: causal variant; earlier-source hops: full
    variant; later-source hops skip compute via ``lax.cond``) and partial
    outputs merge by logsumexp — the MXU sees flash tiles, not jnp einsum
    chunks. ``use_kernel``: "auto" | True | False. The jnp chunked path
    remains for shapes the kernel gate rejects.

    ``hop_remat=False`` (ISSUE 15, the ``save_flash_lse`` composition):
    drops the per-hop ``jax.checkpoint`` so an ENCLOSING layer-level
    checkpoint with ``remat_policy="save_flash_lse"`` governs instead —
    each hop's kernel (out, lse) pair carries the ``flash_out``/
    ``flash_lse`` checkpoint names, the policy saves exactly those, and
    the backward ring enters the dq/dkv kernels from SAVED lse with the
    forward kernel DCE'd out of the recompute (the PR 3 discipline, per
    hop). Residuals are then sp x O(T/sp · D) per layer = the unsharded
    activation footprint, vs the default hop checkpoint's O(T/sp · D)
    with a per-hop forward re-run in backward. Kernel path only: the jnp
    chunked path has no named hop outputs for the policy to save, so it
    keeps its per-hop checkpoint regardless (dropping it would just let
    backward linearize all sp hops' score chunks at once).
    """
    import jax
    import jax.numpy as jnp

    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    if alibi_slopes is not None and use_kernel is True:
        raise ValueError("ring hop kernel has no per-hop bias offset; "
                         "ALiBi rings use the jnp chunked path")
    kernel_on = (alibi_slopes is None and
                 (use_kernel is True or
                  (use_kernel == "auto" and _ring_hop_kernel_ok(q, interpret))))
    if use_kernel is True and not _ring_hop_kernel_ok(q, interpret):
        from ..ops.dispatch import pallas_enabled

        if not (pallas_enabled() or interpret):
            raise ValueError(
                "ring hop kernel forced but Pallas is disabled on this "
                "backend — run on TPU, pass interpret=True, or drop "
                "use_kernel=True")
        raise ValueError(
            f"ring hop kernel forced but the shape gate rejects it "
            f"(Tq={Tq}, D={D}; need D in (64,128) and a swept block "
            f"size dividing Tq)")
    if kernel_on:
        return _ring_attention_kernel(q, k, v, axis_name, causal, interpret,
                                      hop_remat=hop_remat)
    # GQA: rotate the UN-repeated kv shards (KV-sized ring hops — repeating
    # first would multiply ppermute bytes by H/KV); expand per chunk inside
    # the accumulate step, where the broadcast stays local (and is
    # recomputed, not saved, under the hop checkpoint).
    n_rep = H // k.shape[2]
    scale = D ** -0.5
    q32 = q.astype(jnp.float32) * scale

    q_pos = my_idx * Tq + jnp.arange(Tq)
    ck = _ring_kv_chunk(Tq, kv_chunk)
    n_chunks = Tq // ck

    def hop_attn(carry, q32, k_blk, v_blk, src_idx):
        """One ring hop: online softmax over the hop's kv block, tiled in
        ``ck``-sized chunks so the score tile is [B,H,Tq,ck]."""
        def chunk_body(c, chunk_idx):
            acc, m_run, l_run = c
            ks = jax.lax.dynamic_slice_in_dim(k_blk, chunk_idx * ck, ck, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v_blk, chunk_idx * ck, ck, axis=1)
            if n_rep > 1:
                from ..ops.flash_attention import _repeat_kv

                ks = _repeat_kv(ks, n_rep)
                vs = _repeat_kv(vs, n_rep)
            logits = jnp.einsum("bthd,bshd->bhts", q32, ks.astype(jnp.float32))
            kv_pos = src_idx * Tq + chunk_idx * ck + jnp.arange(ck)
            if alibi_slopes is not None:
                # BLOOM ALiBi under CP: absolute key positions are global
                # in the ring, so the bias is exact across hops
                logits = logits + (alibi_slopes[None, :, None, None]
                                   * kv_pos.astype(jnp.float32)[None, None, None, :])
            if causal:
                mask = q_pos[:, None] >= kv_pos[None, :]
                logits = jnp.where(mask[None, None], logits, -jnp.inf)
            m_blk = jnp.max(logits, axis=-1)                      # [B,H,Tq]
            m_new = jnp.maximum(m_run, m_blk)
            # guard fully-masked chunks (m_new = -inf): contribute nothing
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(logits - m_safe[..., None])
            p = jnp.where(jnp.isfinite(logits), p, 0.0)
            correction = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0)
            l_new = l_run * correction + p.sum(-1)
            acc_new = (acc * correction[..., None]
                       + jnp.einsum("bhts,bshd->bhtd", p, vs.astype(jnp.float32)))
            return (acc_new, m_new, l_new), None

        if n_chunks == 1:
            carry, _ = chunk_body(carry, jnp.asarray(0, jnp.int32))
            return carry
        carry, _ = jax.lax.scan(chunk_body, carry,
                                jnp.arange(n_chunks, dtype=jnp.int32))
        return carry

    # Remat per hop: backward recomputes one hop's score tiles at a time
    # instead of saving sp of them. Unconditional on this jnp path —
    # hop_remat=False exists for the KERNEL path, whose hop outputs carry
    # the save_flash_lse names an enclosing layer checkpoint saves; here
    # there are no named hop outputs, so dropping the boundary would only
    # let backward linearize all sp hops at once (O(sp) score-chunk
    # residuals on exactly the long-context shapes CP targets).
    hop_attn = jax.checkpoint(hop_attn)

    def rotate(kv):
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        return jax.tree_util.tree_map(lambda x: comm.ppermute(x, axis_name, perm), kv)

    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    # The chunk scan's carry must already be device-varying over the seq
    # axis (its outputs are), or shard_map's vma check rejects the scan.
    acc0, m0, l0 = (jax.lax.pcast(t, (axis_name,), to="varying")
                    for t in (acc0, m0, l0))

    carry = (acc0, m0, l0)
    kv = (k, v)
    # Unrolled python loop over sp hops (sp is static); XLA overlaps each
    # ppermute with the previous block's compute.
    for r in range(sp):
        src_idx = (my_idx - r) % sp
        carry = hop_attn(carry, q32, kv[0], kv[1], src_idx)
        if r != sp - 1:
            kv = rotate(kv)
    acc, m_run, l_run = carry
    out = acc / jnp.maximum(l_run[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B,T,H,D]


def _ring_attention_kernel(q, k, v, axis_name: str, causal: bool,
                           interpret: bool, hop_remat: bool = True):
    """Ring attention with a Pallas flash kernel inside each hop.

    Each hop attends the local Q shard against one rotated KV shard through
    :func:`~..ops.alibi_attention.flash_attention_lse` and the partial
    (out, lse) pairs merge exactly:
    ``out = Σ_h out_h · exp(lse_h − lse_tot)``. For causal rings the hop's
    role is data-dependent per device (the source block's causal offset):
    the r=0 hop is the diagonal (causal kernel, trace-time static), and
    each later hop runs the full kernel iff the source shard precedes this
    one — selected with ``lax.cond`` so skipped devices do no attention
    work. (Load is inherently ring-position-skewed for causal; a zigzag
    block permutation would even it out — future knob.)"""
    import jax
    import jax.numpy as jnp

    from ..ops.alibi_attention import flash_attention_lse

    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape

    def merge(carry, out_h, lse_h):
        out_run, lse_run = carry
        m = jnp.maximum(lse_run, lse_h)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        w1 = jnp.where(jnp.isfinite(lse_run), jnp.exp(lse_run - m_safe), 0.0)
        w2 = jnp.where(jnp.isfinite(lse_h), jnp.exp(lse_h - m_safe), 0.0)
        r = w1 + w2
        r_safe = jnp.maximum(r, 1e-30)
        # lse layout [B,H,T] -> weight layout [B,T,H,1] for the outputs
        as_bth = lambda t: t.transpose(0, 2, 1)[..., None]
        out_new = (out_run * as_bth(w1 / r_safe)
                   + out_h.astype(jnp.float32) * as_bth(w2 / r_safe))
        lse_new = jnp.where(r > 0, m_safe + jnp.log(r_safe), -jnp.inf)
        return out_new, lse_new

    def hop(carry, q, k_blk, v_blk, src_idx):
        if causal:
            def full_branch(q, kb, vb):
                return flash_attention_lse(q, kb, vb, False, interpret)

            def skip_branch(q, kb, vb):
                # constants must carry the same varying-axes set as the
                # kernel branches' outputs or cond rejects the branch types
                vma = frozenset()
                for t in (q, kb, vb):
                    vma = vma | jax.typeof(t).vma

                def mk(z):
                    need = tuple(sorted(vma - jax.typeof(z).vma))
                    return jax.lax.pcast(z, need, to="varying") if need else z

                return (mk(jnp.zeros(q.shape, q.dtype)),
                        mk(jnp.full((B, H, Tq), -jnp.inf, jnp.float32)))

            def diag_branch(q, kb, vb):
                return flash_attention_lse(q, kb, vb, True, interpret)

            # diagonal iff src == me; earlier shards attend fully; later
            # shards are entirely masked -> skip the kernel
            out_h, lse_h = jax.lax.cond(
                src_idx == my_idx, diag_branch,
                lambda q, kb, vb: jax.lax.cond(
                    src_idx < my_idx, full_branch, skip_branch, q, kb, vb),
                q, k_blk, v_blk)
        else:
            out_h, lse_h = flash_attention_lse(q, k_blk, v_blk, False,
                                               interpret)
        return merge(carry, out_h, lse_h)

    # Remat per hop: residuals are the hop inputs (O(Tq·D)), and the
    # kernel's own custom_vjp recomputes score tiles in its dq/dkv passes.
    # hop_remat=False (save_flash_lse composition): no inner boundary —
    # the enclosing layer checkpoint's save_only_these_names policy saves
    # each hop's tagged (flash_out, flash_lse) pair, so the backward ring
    # enters the dq/dkv kernels from saved lse and the forward kernel is
    # DCE'd out of the backward recompute entirely (asserted by pallas-
    # call counting in tests/test_context_parallel.py).
    if hop_remat:
        hop = jax.checkpoint(hop)

    def rotate(kv):
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        return jax.tree_util.tree_map(
            lambda x: comm.ppermute(x, axis_name, perm), kv)

    out0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    lse0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    _pcast = getattr(jax.lax, "pcast", None)
    if _pcast is not None:
        out0, lse0 = (_pcast(t, (axis_name,), to="varying")
                      for t in (out0, lse0))
    carry = (out0, lse0)
    kv = (k, v)
    for r in range(sp):
        src_idx = (my_idx - r) % sp
        carry = hop(carry, q, kv[0], kv[1], src_idx)
        if r != sp - 1:
            kv = rotate(kv)
    out_run, _ = carry
    return out_run.astype(q.dtype)  # [B,T,H,D]


# ----------------------------------------------------------------------
# Tiled compute
# ----------------------------------------------------------------------


def tiled_mlp(fn: Callable, x, n_tiles: int, axis: int = 1):
    """Apply ``fn`` over sequence tiles to bound activation memory
    (reference TiledMLP ulysses_sp.py:757). fn must be pointwise along
    ``axis`` (true for transformer MLPs)."""
    import jax
    import jax.numpy as jnp

    if n_tiles <= 1:
        return fn(x)
    T = x.shape[axis]
    assert T % n_tiles == 0, f"seq {T} not divisible by n_tiles {n_tiles}"
    tiles = jnp.moveaxis(x, axis, 0).reshape((n_tiles, T // n_tiles) + x.shape[:axis] + x.shape[axis + 1:])
    out_tiles = jax.lax.map(lambda t: fn(jnp.moveaxis(t, 0, axis)), tiles)
    # out_tiles: [n_tiles, ..., tile, ...] with tile at `axis`+1
    out = jnp.concatenate([out_tiles[i] for i in range(n_tiles)], axis=axis)
    return out


def tiled_loss(loss_fn: Callable, logits_fn: Callable, x, labels, n_tiles: int):
    """Chunked logits+loss (reference tiled loss ulysses_sp.py:915; FPDT
    chunked logits fpdt_layer.py:1137): never materializes [B, T, vocab]."""
    import jax
    import jax.numpy as jnp

    B, T = labels.shape
    assert T % n_tiles == 0
    chunk = T // n_tiles

    def body(i, acc):
        sl = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        logits = logits_fn(sl)
        loss, count = loss_fn(logits, lb)
        return (acc[0] + loss, acc[1] + count)

    total, count = jax.lax.fori_loop(0, n_tiles, body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)))
    return total / jnp.maximum(count, 1.0)


# ----------------------------------------------------------------------
# Vocab-parallel cross entropy (reference sequence/cross_entropy.py)
# ----------------------------------------------------------------------


def vocab_parallel_cross_entropy(logits_shard, labels, axis_name: str = "tensor",
                                 vocab_shard_size: Optional[int] = None, ignore_index: int = -100):
    """CE where logits [.., V/tp] are sharded on the vocab dim over
    ``axis_name``. Returns mean NLL over non-ignored labels. Runs inside
    shard_map."""
    import jax
    import jax.numpy as jnp

    V_local = logits_shard.shape[-1]
    tp_idx = jax.lax.axis_index(axis_name)
    vocab_start = tp_idx * V_local
    logits32 = logits_shard.astype(jnp.float32)

    local_max = logits32.max(-1)
    global_max = comm.pmax(local_max, axis_name)
    sumexp = jnp.exp(logits32 - global_max[..., None]).sum(-1)
    global_sumexp = comm.psum(sumexp, axis_name)
    lse = global_max + jnp.log(global_sumexp)

    local_label = labels - vocab_start
    in_shard = (local_label >= 0) & (local_label < V_local)
    safe = jnp.clip(local_label, 0, V_local - 1)
    picked = jnp.take_along_axis(logits32, safe[..., None], axis=-1)[..., 0]
    target_logit = comm.psum(jnp.where(in_shard, picked, 0.0), axis_name)

    mask = labels != ignore_index
    nll = jnp.where(mask, lse - target_logit, 0.0)
    return nll.sum() / jnp.maximum(mask.sum(), 1)
