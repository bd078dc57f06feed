"""Learned sparse attention (DeepSeek-Sparse-Attention shaped: Keye-VL-2.0's
``sa_config``): an indexer scores every earlier key, each query keeps its
``topk`` best, the softmax runs over those alone, and the indexer is trained
by a loss of its own, the KL from the main attention's head-averaged
probabilities to the softmax of its scores over the chosen keys.

Everything [T, T]-shaped is KEYS-major (keys along rows, a chunk of ``CHUNK``
queries along columns): the layout the kernels read their tiles in, and the
one in which a per-query statistic is a row vector. No [T, T] float32 array is
held whole but the loss's target on the kernel route (see ``attend``).

  ``select``   I[s, t] = scale x sum_j w[t, j] relu(kI[s] . qI[t, j]) for
               s <= t, a chunk of queries at a time, and the EXACT set S_t of
               the min(t + 1, topk) largest, ties to the earlier key
               (``lax.top_k``'s order), as ``mask_t`` [B, S, T] int8. The k-th
               largest is found by bisection on the scores' bits (32
               compare-and-count rounds a chunk: no sort, no ``approx_max_k``,
               no threshold that admits one key more); the tie rule by a second
               bisection on the position, run only in a chunk where the
               threshold is shared. On a TPU (``select_route``) a chunk's
               rounds are ONE kernel, ``ops/dsa_kernels.select_chunk``: the
               keys stay in VMEM between the rounds, which walk the rows the
               chunk can see and no other, and the kernel writes the chunk's
               columns of ``mask_t`` in place and counts what it chose;
               elsewhere ``topk_mask``, XLA's passes, which is also the tests'
               oracle and, from the kernel's threshold, the tie rule of both.
               The mask goes on to the rest of the layer through a bit-packed
               copy named ``KEPT``: a mixer half under per-half remat keeps
               those 33 MB a layer (16,384 positions) and its replay neither
               scores nor searches again.
  ``attend``   the core (softmax over S_t of q . k / sqrt(Dh), times v) and
               the indexer's loss. On a TPU (``route``) ``ops/dsa_kernels``:
               a flash forward and the fused backward of
               ``ops/splash_backward``'s form, both reading the mask tile by
               tile; the loss's target p (the heads' probabilities averaged)
               by a kernel of its own; the indexer's scores and their backward
               by two more (``index_route``). Elsewhere XLA's forms, a chunk
               at a time.
  ``kl``       sum over t of KL(p[., t] || softmax over S_t of I[., t]). Its
               gradient (to qI, kI and w, and to nothing else) is computed
               WITH its value, in the forward pass, and named ``KEPT`` too:
               under per-half remat the target, the second reading of the
               scores and their backward run once a step.

The mask is data: nothing here knows it when the program is traced, and every
causal block of the core is visited whatever the mask holds
(``block_visit_share`` reads what a step's mask left empty: none, with
weights from a seed).
"""

from __future__ import annotations

# the checkpoint name of what a mixer half keeps for its backward
from .flash_attention import SPLASH_RESIDUALS as KEPT

#: queries a pass of every stage (``sa_config``'s q_chunk_size)
CHUNK = 512
#: the value a masked score takes (the splash kernels'): exp(it - lse) == 0
MASK_VALUE = -0.7 * 3.4028235e38


def chunk_of(T: int) -> int:
    """Queries a pass over ``T`` positions: ``CHUNK`` where it divides T, else
    T whole (a short sequence)."""
    c = min(CHUNK, T)
    return c if T % c == 0 else T


def index_route(qi, ki) -> str:
    """"pallas" (``ops/dsa_kernels``: on a TPU, whole lane tiles of queries
    and keys, 2-byte inputs) or "xla" for an indexer of these shapes."""
    import numpy as np

    from .dispatch import pallas_enabled

    ok = (pallas_enabled() and np.dtype(qi.dtype).itemsize == 2
          and qi.shape[0] % 128 == 0 and ki.shape[0] % 128 == 0)
    return "pallas" if ok else "xla"


def index_scores(qi, ki, w, scale: float, first=None):
    """The indexer's scores of a chunk of queries against every key, float32,
    keys-major: qi [C, Hi, Di], ki [S, Di], w [C, Hi] -> [S, C] =
    ``scale x sum_j w[., j] relu(ki . qi[., j])``. By ``index_route`` the
    kernels of ``ops/dsa_kernels`` (the backward too; ``first``, the chunk's
    first query's position, lets them skip the keys past the chunk) or XLA's
    two contractions, which hold [Hi, S, C] float32."""
    import jax
    import jax.numpy as jnp

    if index_route(qi, ki) == "pallas":
        from . import dsa_kernels

        return dsa_kernels.index_scores(qi, ki, w, scale, first)
    f32 = jnp.float32
    dots = jnp.einsum("sd,chd->hsc", ki, qi, preferred_element_type=f32)
    return scale * jnp.einsum("ch,hsc->sc", w.astype(f32), jax.nn.relu(dots))


def _sort_key(scores):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def _keys(scores, valid):
    """The ``valid`` scores' sort keys; an invalid entry's is 0, below every
    finite score's (key 0 is a NaN's)."""
    import jax.numpy as jnp

    return jnp.where(valid, _sort_key(scores), jnp.uint32(0))


def _count(hit):
    import jax.numpy as jnp

    return jnp.sum(hit, axis=0, dtype=jnp.int32, keepdims=True)


def _kth_largest(key, k: int):
    """Of each column of ``key`` [S, C] uint32 the largest value v with at
    least k keys >= v, built bit by bit: [1, C] uint32."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32

    def value_bit(i, thr):
        cand = thr | (u32(1) << (u32(31) - i.astype(u32)))
        return jnp.where(_count(key >= cand) >= k, cand, thr)

    return jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((1,) + key.shape[1:], u32))


def _at_threshold(key, thr, valid, k: int):
    """The chosen entries under the threshold ``thr`` [1, C] (``_kth_largest``'s
    of ``key``): every valid key above it and, of the valid keys equal to it,
    the earliest that still fit -> ([S, C] bool, whether some column's
    threshold was shared by more keys than fit: the tie rule's search ran)."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    S = key.shape[0]
    above, equal = key > thr, (key == thr) & valid
    room = k - _count(above)                      # >= 1 keys equal to thr fit
    pos = jax.lax.broadcasted_iota(i32, key.shape, 0)

    def earliest(_):
        # the last row taken: the largest x with fewer than ``room`` equal
        # keys before it
        bits = max(1, (S - 1).bit_length())

        def position_bit(i, x):
            cand = x | (i32(1) << (i32(bits - 1) - i))
            return jnp.where(_count(equal & (pos < cand)) < room, cand, x)

        last = jax.lax.fori_loop(0, bits, position_bit, jnp.zeros(room.shape, i32))
        return equal & (pos <= last)

    tied = jnp.any(_count(equal) > room)
    ties = jax.lax.cond(tied, earliest, lambda _: equal, None)
    return above | ties, tied


def topk_mask(scores, valid, k: int):
    """Of each COLUMN of ``scores`` [S, C] float32, the min(valid entries, k)
    largest among the ``valid`` ones, ties to the earlier row: [S, C] bool.
    Exact: the k-th largest key is built bit by bit (the largest value v with
    at least k keys >= v), every key above it is taken, and of the keys equal
    to it the earliest that still fit."""
    key = _keys(scores, valid)
    return _at_threshold(key, _kth_largest(key, k), valid, k)[0]


def _no_scope(name):
    import contextlib

    return contextlib.nullcontext()


def _packed(mask_t):
    """[.., T] int8 of 0 / 1 -> [.., T / 8] int8, eight queries a byte: bit b
    of byte j is query b x T / 8 + j, so the eight are whole slices of the
    lane axis (a last axis that is not whole bytes goes unpacked)."""
    import functools

    import jax.numpy as jnp

    T = mask_t.shape[-1]
    if T % 8:
        return mask_t
    n = T // 8
    return functools.reduce(jnp.bitwise_or,
                            (mask_t[..., b * n:(b + 1) * n] << b for b in range(8)))


def _unpacked(packed, T: int):
    import jax.numpy as jnp

    if packed.shape[-1] == T:
        return packed
    return jnp.concatenate([(packed >> b) & 1 for b in range(8)], axis=-1)


def select_route(scores_dtype, S: int, C: int) -> str:
    """"pallas" (``ops/dsa_kernels.select_chunk``: on a TPU, float32 scores,
    keys and a chunk of whole lane tiles whose column block fits the kernels'
    VMEM budget) or "xla" (``topk_mask``) for the search of a chunk of C
    queries over S keys."""
    import numpy as np

    from .dispatch import pallas_enabled

    if not (pallas_enabled() and np.dtype(scores_dtype) == np.float32):
        return "xla"
    from . import dsa_kernels

    return "pallas" if dsa_kernels.select_lanes(S, C) else "xla"


def _select_xla(qi, ki, w, topk: int, scale: float, scope):
    """``select``'s mask [B, S, T] int8, the chosen keys a (block of keys,
    query) [B, S / C, T] int32 and the chunks that took the tie rule's search,
    by ``topk_mask``'s pieces: the chunks' masks stacked and transposed, the
    counts from a walk of the mask."""
    import jax
    import jax.numpy as jnp

    B, T = qi.shape[:2]
    C = chunk_of(T)
    rows = jnp.arange(T, dtype=jnp.int32)

    def sequence(qi, ki, w):
        def body(c):
            cols = c * C + jnp.arange(C, dtype=jnp.int32)
            valid = rows[:, None] <= cols[None, :]                    # [S, C]
            part = lambda x: jax.lax.dynamic_slice_in_dim(x, c * C, C, axis=0)

            def search(_):
                with scope("dsa_index"):
                    scores = index_scores(part(qi), ki, part(w), scale, c * C)
                with scope("dsa_select"):
                    key = _keys(scores, valid)
                    return _at_threshold(key, _kth_largest(key, topk), valid, topk)

            # a chunk whose every query has topk keys or fewer keeps them all
            mask, tied = jax.lax.cond((c + 1) * C <= topk, lambda _: (valid, False),
                                      search, None)
            with scope("dsa_select"):
                return mask.astype(jnp.int8), tied

        chunks, tied = jax.lax.map(body, jnp.arange(T // C))         # [n, S, C]
        with scope("dsa_select"):
            return chunks.transpose(1, 0, 2).reshape(T, T), jnp.sum(tied, dtype=jnp.int32)

    masks, tied = zip(*(sequence(qi[b], ki[b], w[b]) for b in range(B)))
    mask_t = jnp.stack(masks)
    with scope("dsa_select"):
        counts = jnp.sum(mask_t.reshape(B, T // C, C, T), axis=2, dtype=jnp.int32)
    return mask_t, counts, sum(tied)


def _search_pallas(scores, mask_t, b, first, topk: int):
    """The search of one chunk as ONE kernel (``ops/dsa_kernels.select_chunk``):
    scores [S, C] float32 of the C queries from ``first`` of sequence ``b``
    -> (``mask_t`` [B, S, T] int8 with the chunk's columns written in place,
    the chosen keys a (block of ``select_rows`` keys, query) [S / rows, C]
    int32, whether the tie rule ran its search). A chunk in which some query
    shares its threshold with more keys than fit takes that search in XLA,
    from the kernel's threshold."""
    import jax
    import jax.numpy as jnp

    from . import dsa_kernels

    S, C = scores.shape
    mask_t, held, thr = dsa_kernels.select_chunk(scores, mask_t, b, first, topk)
    tied = jnp.any(jnp.sum(held, axis=0) > topk)

    def earliest(mask_t, held):
        rows = jnp.arange(S, dtype=jnp.int32)
        valid = rows[:, None] <= first + rows[None, :C]
        chosen = _at_threshold(_keys(scores, valid), thr, valid, topk)[0]
        held = jnp.sum(chosen.reshape(held.shape[0], -1, C), axis=1, dtype=jnp.int32)
        return jax.lax.dynamic_update_slice(
            mask_t, chosen.astype(jnp.int8)[None], (b, 0, first)), held

    return jax.lax.cond(tied, earliest, lambda *kept: kept, mask_t, held) + (tied,)


def _select_pallas(qi, ki, w, topk: int, scale: float, scope):
    """``select``'s mask and tied chunks by ``_search_pallas``, a carry the
    chunks' kernels write their columns of, and the chosen keys a (block of
    keys, query) [B, S / rows, T] int32 from the kernels' counts."""
    import jax
    import jax.numpy as jnp

    from . import dsa_kernels

    B, T = qi.shape[:2]
    C = chunk_of(T)

    def chunk(i, carry):
        mask_t, counts, tied_chunks = carry
        b, first = i // (T // C), i % (T // C) * C
        seq = lambda x: jax.lax.dynamic_index_in_dim(x, b, 0, keepdims=False)
        part = lambda x: jax.lax.dynamic_slice_in_dim(seq(x), first, C, axis=0)
        with scope("dsa_index"):
            # (of a chunk whose every query has topk keys or fewer too: the
            # kernel then keeps them all without a round. Zeros from a ``cond``
            # cost more than those few scores: traced under the layer scan's
            # derivative they become one [chunks, S, C] array made before the
            # scan, and every chunk copies its slice of it: PERF.md, PR 62)
            scores = index_scores(part(qi), seq(ki), part(w), scale, first)
        with scope("dsa_select"):
            mask_t, held, tied = _search_pallas(scores, mask_t, b, first, topk)
            counts = jax.lax.dynamic_update_slice(counts, held[None], (b, 0, first))
        return mask_t, counts, tied_chunks + tied

    return jax.lax.fori_loop(0, B * (T // C), chunk, (
        dsa_kernels.unwritten((B, T, T), jnp.int8, ki),     # every chunk writes its columns
        jnp.zeros((B, T // dsa_kernels.select_rows(T, C), T), jnp.int32),
        jnp.zeros((), jnp.int32)))


def select(qi, ki, w, topk: int, scale: float, scope=_no_scope):
    """S_t for every query of qi [B, T, Hi, Di], ki [B, T, Di], w [B, T, Hi]
    as ``mask_t`` [B, S, T] int8, KEYS-major (1 = key s is among query t's
    chosen), and the step's counters: ``selected_min`` / ``selected_max``
    (keys a query past position topk - 2 holds; T < topk: of the last query),
    ``pairs`` (the chosen (t, s) in all) and ``tied_chunks`` (the chunks whose
    tie rule ran its search), int32, and ``block_visit_share``. Nothing here
    carries a gradient. The scores run under ``scope("dsa_index")``, the
    search under ``scope("dsa_select")``: by ``select_route`` one kernel a
    chunk that writes its columns of the mask and counts what it chose
    (``_select_pallas``), or ``topk_mask``'s passes in XLA and a walk of the
    mask (``_select_xla``)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    T = qi.shape[1]
    kernels = select_route(jnp.float32, T, chunk_of(T)) == "pallas"
    mask_t, counts, tied = (_select_pallas if kernels else _select_xla)(
        qi, ki, w, topk, scale, scope)
    with scope("dsa_select"):
        # what the layer keeps of the selection between its passes: a bit a pair
        mask_t = _unpacked(checkpoint_name(_packed(mask_t), KEPT), T)
        held = jnp.sum(counts, axis=1)                               # [B, T]
        bound = held[:, min(max(topk - 1, 0), T - 1):]
        return mask_t, {"selected_min": bound.min(), "selected_max": bound.max(),
                        "pairs": held.sum(), "tied_chunks": tied,
                        "block_visit_share": block_visit_share(counts, CHUNK)}


def _grouped(q, kv_heads: int):
    """q [T, H, D] -> [KV, G, T, D]: query heads g of key head j are
    h = j x G + g (``_repeat_kv``'s convention)."""
    T, H, D = q.shape
    return q.reshape(T, kv_heads, H // kv_heads, D).transpose(1, 2, 0, 3)


def _chunk_scores(qc, k):
    """qc [KV, G, C, D] (scaled), k [S, KV, D] -> float32 [KV, G, C, S]."""
    import jax.numpy as jnp

    return jnp.einsum("jgcd,sjd->jgcs", qc, k, preferred_element_type=jnp.float32)


def core_xla(q, k, v, mask_t):
    """The core over the chosen keys as a masked softmax a chunk of queries:
    q [B, T, H, D], k / v [B, T, KV, D], mask_t [B, S, T] -> (out [B, T, H, D]
    in q's dtype, logsumexp [B, H, T] float32). The softmax is float32; each
    chunk is computed again in the backward."""
    import jax
    import jax.numpy as jnp

    B, T, H, D = q.shape
    KV = k.shape[2]
    C = chunk_of(T)
    scale = D ** -0.5

    def sequence(q, k, v, mask_t):
        q4 = _grouped(q * jnp.asarray(scale, q.dtype), KV)        # [KV, G, T, D]

        @jax.checkpoint
        def body(c):
            part = lambda x, axis: jax.lax.dynamic_slice_in_dim(x, c * C, C, axis=axis)
            s = _chunk_scores(part(q4, 2), k)
            s = jnp.where(part(mask_t, 1).T[None, None] != 0, s, MASK_VALUE)
            lse = jax.nn.logsumexp(s, axis=-1)
            p = jnp.exp(s - lse[..., None]).astype(v.dtype)
            out = jnp.einsum("jgcs,sjd->cjgd", p, v, preferred_element_type=jnp.float32)
            return out.reshape(C, H, D).astype(q.dtype), lse.reshape(H, C)

        out, lse = jax.lax.map(body, jnp.arange(T // C))
        return out.reshape(T, H, D), lse.transpose(1, 0, 2).reshape(H, T)

    return jax.vmap(sequence)(q, k, v, mask_t)


def head_mean_xla(q, k, lse, start, rows: int):
    """p of ``rows`` queries from ``start`` of ONE sequence, keys-major: q
    [T, H, D], k [T, KV, D], lse [H, T] -> float32 [T, rows], the mean over
    heads of exp(q_h . k / sqrt(D) - lse_h) (on every key: the reader takes
    the chosen ones)."""
    import jax
    import jax.numpy as jnp

    T, H, D = q.shape
    part = lambda x, axis: jax.lax.dynamic_slice_in_dim(x, start, rows, axis=axis)
    qc = _grouped(part(q, 0) * jnp.asarray(D ** -0.5, q.dtype), k.shape[1])
    s = _chunk_scores(qc, k).reshape(H, rows, T)
    return jnp.exp(s - part(lse, 1)[..., None]).mean(axis=0).T


def _kl_chunks(qi, ki, w, mask_t, target, scale: float, with_grads: bool, scope):
    """The loss of ONE sequence, a chunk of queries at a time, and (with
    ``with_grads``) its gradient to (qi, ki, w): the scores again
    (``index_scores``), p || softmax over the chosen keys, and the scores'
    backward under d I = softmax - p."""
    import jax
    import jax.numpy as jnp

    T = qi.shape[0]
    C = chunk_of(T)
    f32 = jnp.float32

    def value(qc, ki, wc, chosen, p, first):
        with scope("dsa_index"):
            scores = index_scores(qc, ki, wc, scale, first)
        logq = jax.nn.log_softmax(jnp.where(chosen, scores, MASK_VALUE), axis=0)
        logp = jnp.log(jnp.where(p > 0, p, 1.0))
        return jnp.sum(p * (logp - jnp.where(chosen, logq, 0.0)))

    def body(dki, c):
        part = lambda x: jax.lax.dynamic_slice_in_dim(x, c * C, C, axis=0)
        chosen = jax.lax.dynamic_slice_in_dim(mask_t, c * C, C, axis=1) != 0
        # (a target may hold anything where no key was chosen)
        p = jnp.where(chosen, target(c * C, C), 0.0)
        if not with_grads:
            return dki, (value(part(qi), ki, part(w), chosen, p, c * C),)
        loss, (dq, dk, dw) = jax.value_and_grad(value, argnums=(0, 1, 2))(
            part(qi), ki, part(w), chosen, p, c * C)
        return dki + dk.astype(f32), (loss, dq, dw)

    dki, parts = jax.lax.scan(body, jnp.zeros(ki.shape, f32), jnp.arange(T // C))
    if not with_grads:
        return jnp.sum(parts[0])
    loss, dqi, dw = parts
    return jnp.sum(loss), (dqi.reshape(qi.shape).astype(qi.dtype), dki.astype(ki.dtype),
                           dw.reshape(w.shape).astype(w.dtype))


def kl(qi, ki, w, mask_t, target_of, target_args, scale: float, scope=_no_scope):
    """sum over b, t of KL(p[b, ., t] || softmax over S_t of I[b, ., t]),
    float32. ``target_of(args of sequence b, start, rows)`` -> p [S, rows]
    float32 of those queries from ``target_args`` (arrays with a leading B),
    held constant. The one path a gradient takes is to qi, ki and w, and it is
    computed WITH the value, in the forward pass, and named ``KEPT`` (a mixer
    half's replay then runs none of this again)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    B = qi.shape[0]

    def both(qi, ki, w, mask_t, args, with_grads):
        out = []
        for b in range(B):
            mine = jax.tree.map(lambda a: a[b], args)
            out.append(_kl_chunks(
                qi[b], ki[b], w[b], mask_t[b],
                lambda start, rows: target_of(mine, start, rows), scale, with_grads, scope))
        if not with_grads:
            return sum(out)
        grads = jax.tree.map(lambda *xs: jnp.stack(xs), *[g for _, g in out])
        return sum(v for v, _ in out), grads

    @jax.custom_vjp
    def loss(qi, ki, w, mask_t, args):
        return both(qi, ki, w, mask_t, args, False)

    def fwd(qi, ki, w, mask_t, args):
        value, grads = both(qi, ki, w, mask_t, args, True)
        return checkpoint_name(value, KEPT), jax.tree.map(
            lambda g: checkpoint_name(g, KEPT), grads)

    def bwd(grads, g):
        return jax.tree.map(lambda x: (g * x).astype(x.dtype), grads) + (None, None)

    loss.defvjp(fwd, bwd)
    return loss(qi, ki, w, mask_t, jax.lax.stop_gradient(target_args))


def route(q, k, T: int) -> str:
    """Which core a call of these shapes takes: "pallas"
    (``ops/dsa_kernels``: on a TPU, 2-byte inputs, heads of whole lane tiles,
    T whole blocks within the kernels' VMEM budget) or "xla"."""
    import numpy as np

    from .dispatch import pallas_enabled

    if not (pallas_enabled() and np.dtype(q.dtype).itemsize == 2
            and q.shape[-1] % 128 == 0):
        return "xla"
    from . import dsa_kernels

    return "pallas" if dsa_kernels.fits(T, q.shape[-1]) else "xla"


def block_visit_share(counts, block: int):
    """Of the causal (query block, key block) pairs at ``block``, the
    percentage that hold a chosen key: what a core that skipped empty blocks
    would visit (this one visits them all). ``counts`` [B, S / rows, T]: the
    chosen keys a (``rows`` keys, query), ``rows`` a divisor of the block.
    float32 scalar."""
    import jax.numpy as jnp

    B, _, T = counts.shape
    blk = block if T % block == 0 else T
    n = T // blk
    full = counts.reshape(B, n, -1, n, blk).sum(axis=(2, 4)) > 0
    return 100.0 * jnp.sum(full, dtype=jnp.float32) / (B * n * (n + 1) // 2)


def attend(q, k, v, qi, ki, w, mask_t, *, index_scale: float, scope=_no_scope):
    """The core and the indexer's loss under ``mask_t`` [B, S, T]
    (``select``'s): q [B, T, H, D], k / v [B, T, KV, D] (normed, rotated), the
    indexer's qi [B, T, Hi, Di], ki [B, T, Di], w [B, T, Hi] (from a DETACHED
    input: the caller's) -> (out [B, T, H, D], the indexer's loss summed over
    b and t). The core runs under ``scope("dsa_core")``, the target and the
    loss under ``scope("dsa_kl")`` (the scores' second reading under
    ``dsa_index`` inside it). On the kernel route the target is held whole,
    [B, S, T] float32, between its kernel and the loss's chunks."""
    import jax

    B, T = q.shape[:2]
    kernels = route(q, k, T) == "pallas"
    if kernels:
        from . import dsa_kernels
    with scope("dsa_core"):
        if kernels:
            out, lse = dsa_kernels.core(q, k, v, mask_t)
        else:
            out, lse = core_xla(q, k, v, mask_t)
    with scope("dsa_kl"):
        qd, kd, lsed = (jax.lax.stop_gradient(x) for x in (q, k, lse))
        if kernels:
            args = dsa_kernels.head_mean(qd, kd, lsed, mask_t)
            target_of = lambda whole, start, n: jax.lax.dynamic_slice_in_dim(
                whole, start, n, axis=1)
        else:
            args = (qd, kd, lsed)
            target_of = lambda qkl, start, n: head_mean_xla(*qkl, start, n)
        return out, kl(qi, ki, w, mask_t, target_of, args, index_scale, scope)
