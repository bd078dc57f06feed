"""The Pallas side of a learned sparse attention (``ops/dsa``): the softmax
core and the head-averaged probabilities under a mask that is DATA, and the
indexer's scores with their backward. Everything [T, T]-shaped is keys-major.

Three kernels take one grid step a causal (query block, key block) pair
(``ops/splash_backward.visited_pairs``: the causal pairs are known when the
program is traced, which keys a query chose inside them is not), each reading
its [block, block] tile of the mask (int8, keys along sublanes: ``mask_t``
[B, S, T]) beside its operands:

  ``sxt_dsa_attention_fwd``        the flash forward: scores of a tile with keys
      along sublanes and queries along lanes (the backward's form), the running
      maximum, sum and TRANSPOSED output [Dv, bq] in VMEM across a query
      block's key loop, ``out`` and ``logsumexp`` written once a query block.
  ``sxt_splash_bwd_fused``         ``ops/splash_backward.fused_backward`` with
      ``mask=``: the repository's one backward kernel, the tile's mask read in
      place of the causal arithmetic.
  ``sxt_dsa_attention_head_mean``  p[s, t] = mean over the query heads of
      exp(score - logsumexp) on the chosen keys: the target of the indexer's
      loss, a tile accumulated over the heads in VMEM and written once.

Every causal tile is visited whatever the mask holds (with weights from a
seed the chosen keys scatter and no tile is empty; a trained indexer's picks
would cluster, and a tile with no chosen key could then be skipped by a
flag computed with the mask: ROADMAP R-M16 (d)).

Two more are the indexer's scores of a chunk of queries against every key and
their backward (``index_scores``: ``sxt_dsa_index_fwd`` / ``sxt_dsa_index_bwd``):
a head's [keys, queries] products are weighed and summed in VMEM, so the
[heads, keys, queries] float32 array that XLA's two contractions write and read
back (512 MB a chunk of 512 queries at 16,384 keys and 16 heads: a step of the
cell ``keyevl2-train`` read 1.58 s with it and 0.84 s without, my chip runs, PR
61) never exists; the keys past the chunk's last query are skipped.

The sixth is the selection's search of a chunk (``select_chunk``:
``sxt_dsa_select``): a column block of the chunk's scores becomes sort keys in
a VMEM scratch once, the 32 rounds of ``ops/dsa.topk_mask``'s bisection count
there over the row blocks a query of the block can see (0.83 cycles a vector
register by the compiler's bundles, where XLA's passes over HBM arrays read
2.25 over all rows: PERF.md, PR 62), and the block's columns of the mask
[B, S, T] are written where the three kernels above read them.
"""

from __future__ import annotations

import functools

from .dsa import MASK_VALUE
from .flash_attention import SPLASH_RESIDUALS, _pick_block
from .splash_backward import (_FIRST, _LAST, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES,
                              fused_backward, visited_pairs, vmem_bytes)

FWD_NAME = "sxt_dsa_attention_fwd"
MEAN_NAME = "sxt_dsa_attention_head_mean"
INDEX_FWD_NAME = "sxt_dsa_index_fwd"
INDEX_BWD_NAME = "sxt_dsa_index_bwd"
SELECT_NAME = "sxt_dsa_select"
#: keys a grid step of the indexer's two kernels
INDEX_KEYS = 512
#: keys a trip of the selection's loops
SELECT_ROWS = 512


def block_of(T: int, itemsize: int = 2) -> int:
    """The query and key block of the three kernels over ``T`` positions."""
    return _pick_block(T, itemsize)


def fits(T: int, D: int, itemsize: int = 2) -> bool:
    """The backward's resident dk / dv and the tiles stay within the kernels'
    VMEM budget at ``block_of(T)``, and T is whole blocks of lane tiles."""
    blk = block_of(T, itemsize)
    return (T % 128 == 0 and T % blk == 0
            and vmem_bytes(T, D, D, blk, blk, itemsize) <= VMEM_BUDGET_BYTES)


def _seen(mask_ref):
    """The tile's chosen pairs as a bool [bkv, bq]."""
    import jax.numpy as jnp

    return mask_ref[...].astype(jnp.int32) != 0


def _fwd_kernel(qi_ref, kj_ref, flags_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_acc, l_acc, o_acc):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))      # a @ b.T
    tn = (((0,), (0,)), ((), ()))      # a.T @ b
    flags = flags_ref[pl.program_id(3)]

    @pl.when(flags & _FIRST != 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, MASK_VALUE)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    s = lax.dot_general(k, q, nt, preferred_element_type=f32)            # [bkv, bq]
    s = jnp.where(_seen(mask_ref), s, MASK_VALUE)
    m_prev = m_acc[...]                                                  # [1, bq]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    # (a query with no chosen key in the tiles so far carries exp(0) terms;
    # its first chosen key's alpha = exp(MASK_VALUE - m) = 0 wipes them)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_acc[...] = alpha * l_acc[...] + jnp.sum(p, axis=0, keepdims=True)
    o_acc[...] = alpha * o_acc[...] + lax.dot_general(
        v, p.astype(v.dtype), tn, preferred_element_type=f32)            # [Dv, bq]
    m_acc[...] = m_next

    @pl.when(flags & _LAST != 0)
    def _():
        o_ref[...] = (o_acc[...] / l_acc[...]).astype(o_ref.dtype)
        lse_ref[...] = m_acc[...] + jnp.log(l_acc[...])


def _forward(q, k, v, mask_t, *, blk: int, interpret: bool = False):
    """q [B, KV, G, T, D] (scaled), k [B, KV, S, D], v [B, KV, S, Dv],
    mask_t [B, S, T] int8 -> (out [B, KV, G, T, Dv], logsumexp [B, KV, G, T])."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, T, D = q.shape
    S, Dv = k.shape[2], v.shape[3]
    pairs = visited_pairs(T, S, blk, blk)
    at_q = lambda b, h, g, s, qi, kj, fl: (b, h, g, qi[s], 0)
    at_k = lambda b, h, g, s, qi, kj, fl: (b, h, kj[s], 0)
    at_mask = lambda b, h, g, s, qi, kj, fl: (b, kj[s], qi[s])
    at_out = lambda b, h, g, s, qi, kj, fl: (b, h, g, 0, qi[s])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, G, len(pairs)),
        in_specs=[pl.BlockSpec((None, None, None, blk, D), at_q),
                  pl.BlockSpec((None, None, blk, D), at_k),
                  pl.BlockSpec((None, None, blk, Dv), at_k),
                  pl.BlockSpec((None, blk, blk), at_mask)],
        out_specs=[pl.BlockSpec((None, None, None, Dv, blk), at_out),
                   pl.BlockSpec((None, None, None, 1, blk), at_out)],
        scratch_shapes=[pltpu.VMEM((1, blk), jnp.float32),
                        pltpu.VMEM((1, blk), jnp.float32),
                        pltpu.VMEM((Dv, blk), jnp.float32)])
    with jax.named_scope(FWD_NAME):
        out_t, lse = pl.pallas_call(
            _fwd_kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, KV, G, Dv, T), q.dtype),
                       jax.ShapeDtypeStruct((B, KV, G, 1, T), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=FWD_NAME, interpret=interpret,
        )(*(jnp.asarray(pairs[:, c]) for c in range(3)), q, k, v, mask_t)
    return out_t.swapaxes(-1, -2), lse[..., 0, :]


def _mean_kernel(qi_ref, kj_ref, flags_ref, q_ref, k_ref, lse_ref, mask_ref, p_ref, *,
                 heads: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    nt = (((1,), (1,)), ((), ()))
    h, g = pl.program_id(2), pl.program_id(3)
    first = jnp.logical_and(h == 0, g == 0)
    last = jnp.logical_and(h == pl.num_programs(2) - 1, g == pl.num_programs(3) - 1)

    @pl.when(first)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    s = lax.dot_general(k_ref[...], q_ref[...], nt, preferred_element_type=jnp.float32)
    s = jnp.where(_seen(mask_ref), s, MASK_VALUE)
    p_ref[...] += jnp.exp(s - lse_ref[...])

    @pl.when(last)
    def _():
        p_ref[...] = p_ref[...] * (1.0 / heads)


def _head_mean(q, k, lse, mask_t, *, blk: int, interpret: bool = False):
    """q [B, KV, G, T, D] (scaled), k [B, KV, S, D], lse [B, KV, G, T],
    mask_t [B, S, T] -> float32 [B, S, T]: p, keys-major; 0 on the keys of a
    causal tile that a query did not choose, NEVER WRITTEN above the diagonal
    tiles (a reader takes the chosen pairs only)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, T, D = q.shape
    S = k.shape[2]
    pairs = visited_pairs(T, S, blk, blk)
    at_q = lambda b, s, h, g, qi, kj, fl: (b, h, g, qi[s], 0)
    at_k = lambda b, s, h, g, qi, kj, fl: (b, h, kj[s], 0)
    at_row = lambda b, s, h, g, qi, kj, fl: (b, h, g, 0, qi[s])
    at_tile = lambda b, s, h, g, qi, kj, fl: (b, kj[s], qi[s])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, len(pairs), KV, G),
        in_specs=[pl.BlockSpec((None, None, None, blk, D), at_q),
                  pl.BlockSpec((None, None, blk, D), at_k),
                  pl.BlockSpec((None, None, None, 1, blk), at_row),
                  pl.BlockSpec((None, blk, blk), at_tile)],
        out_specs=pl.BlockSpec((None, blk, blk), at_tile))
    with jax.named_scope(MEAN_NAME):
        tiles = pl.pallas_call(
            functools.partial(_mean_kernel, heads=KV * G), grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, S, T), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=MEAN_NAME, interpret=interpret,
        )(*(jnp.asarray(pairs[:, c]) for c in range(3)), q, k,
          lse.astype(jnp.float32)[..., None, :], mask_t)
    return tiles


def _grouped(q, k, v=None):
    """[B, T, H, D] / [B, S, KV, D] -> the kernels' [B, KV, G, T, D] (q scaled
    by 1 / sqrt(D)) and [B, KV, S, D]."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    q5 = (q * D ** -0.5).reshape(B, T, KV, H // KV, D).transpose(0, 2, 3, 1, 4)
    rest = tuple(x.transpose(0, 2, 1, 3) for x in (k, v) if x is not None)
    return (q5,) + rest


@functools.lru_cache(maxsize=None)
def _core_vjp(blk: int, interpret: bool):
    """(q5, k4, v4, mask_t) -> (out5, logsumexp) as one ``jax.custom_vjp``: the
    forward kernel, whose results the forward rule names ``SPLASH_RESIDUALS``
    (a mixer half under per-half remat keeps them and enters the backward from
    saved state), and the fused backward kernel under the same mask. The
    logsumexp is a residual and a reading (the loss's target), not a
    differentiable output: its cotangent is dropped."""
    import jax
    import numpy as np
    from jax.ad_checkpoint import checkpoint_name

    @jax.custom_vjp
    def attend(q5, k4, v4, mask_t):
        return _forward(q5, k4, v4, mask_t, blk=blk, interpret=interpret)

    def fwd(q5, k4, v4, mask_t):
        out, lse = _forward(q5, k4, v4, mask_t, blk=blk, interpret=interpret)
        out, lse = (checkpoint_name(x, SPLASH_RESIDUALS) for x in (out, lse))
        return (out, lse), (q5, k4, v4, mask_t, out, lse)

    def bwd(kept, cotangents):
        q5, k4, v4, mask_t, out, lse = kept
        dq, dk, dv = fused_backward(q5, k4, v4, out, lse, cotangents[0], bq=blk, bkv=blk,
                                    interpret=interpret, mask=mask_t)
        return dq, dk, dv, np.zeros(mask_t.shape, jax.dtypes.float0)

    attend.defvjp(fwd, bwd)
    return attend


def core(q, k, v, mask_t, interpret: bool = False):
    """The core over the chosen keys: q [B, T, H, D], k / v [B, T, KV, D],
    mask_t [B, S, T] int8 (``ops/dsa.select``'s, keys-major) -> (out
    [B, T, H, D], logsumexp [B, H, T] float32)."""
    B, T, H, D = q.shape
    q5, k4, v4 = _grouped(q, k, v)
    out5, lse = _core_vjp(block_of(T, q.dtype.itemsize), interpret)(q5, k4, v4, mask_t)
    return (out5.transpose(0, 3, 1, 2, 4).reshape(B, T, H, D).astype(q.dtype),
            lse.reshape(B, H, T))


def head_mean(q, k, lse, mask_t, interpret: bool = False):
    """p of the indexer's loss, KEYS-major [B, S, T] float32: the mean over
    the query heads of exp(q_h . k / sqrt(D) - lse_h) on the chosen keys
    (what no query chose in a causal tile reads 0; above the diagonal tiles
    nothing is written). q [B, T, H, D], k [B, T, KV, D], lse [B, H, T],
    mask_t [B, S, T]."""
    B, T, H, D = q.shape
    q5, k4 = _grouped(q, k)
    return _head_mean(q5, k4, lse.reshape(B, k.shape[2], -1, T), mask_t,
                      blk=block_of(T, q.dtype.itemsize), interpret=interpret)


# -- the indexer's scores ------------------------------------------------------------------

def _index_fwd_kernel(last_ref, q_ref, k_ref, w_ref, o_ref, *, heads: int, bk: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    nt = (((1,), (1,)), ((), ()))
    # a block of keys past the chunk's last query scores nothing anyone reads
    live = pl.program_id(0) * bk <= last_ref[0]

    @pl.when(live)
    def _():
        k = k_ref[...]                                                   # [bk, Di]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            d = lax.dot_general(k, q_ref[j], nt, preferred_element_type=jnp.float32)
            acc = acc + w_ref[j:j + 1, :] * jnp.maximum(d, 0.0)          # [bk, C]
        o_ref[...] = acc

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _index_bwd_kernel(last_ref, g_ref, q_ref, k_ref, w_ref, dq_ref, dk_ref, dw_ref, *,
                      heads: int, bk: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))
    step = pl.program_id(0)
    live = step * bk <= last_ref[0]

    @pl.when(step == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live)
    def _():
        k, g = k_ref[...], g_ref[...]                                    # [bk, Di], [bk, C]
        dk = jnp.zeros(dk_ref.shape, f32)
        for j in range(heads):
            qj = q_ref[j]                                                # [C, Di]
            d = lax.dot_general(k, qj, nt, preferred_element_type=f32)   # [bk, C]
            dw_ref[j:j + 1, :] += jnp.sum(g * jnp.maximum(d, 0.0), axis=0, keepdims=True)
            dd = jnp.where(d > 0.0, g * w_ref[j:j + 1, :], 0.0).astype(k.dtype)
            dq_ref[j] += lax.dot_general(dd, k, tn, preferred_element_type=f32)
            dk = dk + lax.dot(dd, qj, preferred_element_type=f32)
        dk_ref[...] = dk

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)


def _index_operands(qi, ki, w, scale):
    """The kernels' operands: heads-major queries [Hi, C, Di], the keys, the
    heads' weights times ``scale`` [Hi, C] float32."""
    import jax.numpy as jnp

    return qi.transpose(1, 0, 2), ki, (scale * w.astype(jnp.float32)).T


def _index_forward(qh, ki, wt, last, *, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hi, C, Di = qh.shape
    S = ki.shape[0]
    bk = INDEX_KEYS if S % INDEX_KEYS == 0 else 128
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, last: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S // bk,),
        in_specs=[whole(Hi, C, Di), pl.BlockSpec((bk, Di), lambda i, last: (i, 0)),
                  whole(Hi, C)],
        out_specs=pl.BlockSpec((bk, C), lambda i, last: (i, 0)))
    with jax.named_scope(INDEX_FWD_NAME):
        return pl.pallas_call(
            functools.partial(_index_fwd_kernel, heads=Hi, bk=bk), grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, C), jnp.float32),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            name=INDEX_FWD_NAME, interpret=interpret)(last, qh, ki, wt)


def _index_backward(g, qh, ki, wt, last, *, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hi, C, Di = qh.shape
    S = ki.shape[0]
    bk = INDEX_KEYS if S % INDEX_KEYS == 0 else 128
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, last: (0,) * len(shape))
    rows = lambda width: pl.BlockSpec((bk, width), lambda i, last: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S // bk,),
        in_specs=[rows(C), whole(Hi, C, Di), rows(Di), whole(Hi, C)],
        out_specs=[whole(Hi, C, Di), rows(Di), whole(Hi, C)])
    f32 = jnp.float32
    with jax.named_scope(INDEX_BWD_NAME):
        return pl.pallas_call(
            functools.partial(_index_bwd_kernel, heads=Hi, bk=bk), grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((Hi, C, Di), f32),
                       jax.ShapeDtypeStruct((S, Di), f32),
                       jax.ShapeDtypeStruct((Hi, C), f32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            name=INDEX_BWD_NAME, interpret=interpret)(last, g, qh, ki, wt)


@functools.lru_cache(maxsize=None)
def _index_vjp(scale: float, interpret: bool):
    import jax
    import jax.numpy as jnp

    def last_of(first, C):
        return (jnp.asarray(first, jnp.int32) + (C - 1)).reshape(1)

    @jax.custom_vjp
    def scores(qi, ki, w, first):
        return _index_forward(*_index_operands(qi, ki, w, scale), last_of(first, qi.shape[0]),
                              interpret=interpret)

    def fwd(qi, ki, w, first):
        return scores(qi, ki, w, first), (qi, ki, w, first)

    def bwd(kept, g):
        qi, ki, w, first = kept
        dq, dk, dw = _index_backward(
            g.astype(jnp.float32), *_index_operands(qi, ki, w, scale),
            last_of(first, qi.shape[0]), interpret=interpret)
        return (dq.transpose(1, 0, 2).astype(qi.dtype), dk.astype(ki.dtype),
                (scale * dw.T).astype(w.dtype), None)

    scores.defvjp(fwd, bwd)
    return scores


def index_scores(qi, ki, w, scale: float, first=None, interpret: bool = False):
    """The indexer's scores of a chunk of queries against every key, float32,
    keys-major: qi [C, Hi, Di], ki [S, Di], w [C, Hi] -> [S, C] = ``scale x
    sum_j w[., j] relu(ki . qi[., j])``, differentiable in qi, ki and w.
    ``first`` (traced int32 scalar): the chunk's first query's position; the
    keys past the chunk's last query read 0 (and get no gradient). None: every
    key is scored."""
    if first is None:
        first = ki.shape[0] - qi.shape[0]
    return _index_vjp(float(scale), interpret)(qi, ki, w, first)


# -- the selection -------------------------------------------------------------------------

def select_lanes(S: int, C: int) -> int:
    """Queries a grid step of ``sxt_dsa_select`` holds (a column block of a
    chunk's [S, C] scores: two float32 buffers, the int32 keys and two int8
    mask buffers in VMEM), the widest within the kernels' budget; 0 where
    the chunk or the keys are not whole lane tiles."""
    if S % 128 or C % 128:
        return 0
    return next((n for n in (256, 128) if C % n == 0 and 14 * S * n <= VMEM_BUDGET_BYTES), 0)


def select_rows(S: int, C: int) -> int:
    """Keys a trip of the kernel's loops, and a row of its counts."""
    return SELECT_ROWS if S % SELECT_ROWS == 0 and C % SELECT_ROWS == 0 else 128


def _select_kernel(at_ref, s_ref, _, mask_ref, counts_ref, thr_ref, key_ref, *,
                   k: int, rows: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    i32 = jnp.int32
    S, L = key_ref.shape
    low = -2 ** 31                                 # an invalid entry's key: below every score's
    col0 = at_ref[1] + pl.program_id(0) * L        # the block's first query's position
    whole = col0 // rows                           # row blocks every query of the block sees whole
    live = (col0 + L + rows - 1) // rows           # ... and those some query sees
    block = lambda r: pl.ds(pl.multiple_of(r * rows, rows), rows)
    cols = col0 + lax.broadcasted_iota(i32, (rows, L), 1)
    valid = lambda r: r * rows + lax.broadcasted_iota(i32, (rows, L), 0) <= cols

    # the scores' bits as int32 keys whose SIGNED order is the floats'
    # (``ops/dsa._sort_key`` with the top bit flipped)
    def keys_of(r, masked):
        bits = lax.bitcast_convert_type(s_ref[block(r), :], i32)
        key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
        key_ref[block(r), :] = jnp.where(valid(r), key, low) if masked else key

    def over_live_rows(visit):
        lax.fori_loop(0, whole, lambda r, _: visit(r, False), None)
        lax.fori_loop(whole, live, lambda r, _: visit(r, True), None)

    over_live_rows(keys_of)

    def at_least(cand):
        """Keys >= cand [1, L] a query, over the live rows: four running
        [8, L] sums a trip, added across sublanes once a round."""
        def trip(r, sums):
            hit = (key_ref[block(r), :] >= cand).astype(i32)
            return tuple(sum((hit[t * 8:(t + 1) * 8] for t in range(j, rows // 8, 4)), s)
                         for j, s in enumerate(sums))

        sums = lax.fori_loop(0, live, trip, (jnp.zeros((8, L), i32),) * 4)
        return jnp.sum(sums[0] + sums[1] + sums[2] + sums[3], axis=0, keepdims=True)

    def value_bit(i, thr):
        cand = thr ^ (i32(1) << (31 - i))          # round 0 flips the sign: low -> 0
        return jnp.where(at_least(cand) >= k, cand, thr)

    # the k-th largest key a query, bit by bit (``ops/dsa.topk_mask``'s); a
    # block whose every query sees k keys or fewer keeps them all
    rounds = jnp.where(col0 + L > k, 32, 0)
    thr = lax.fori_loop(0, rounds, value_bit, jnp.full((1, L), low, i32))
    thr_ref[...] = thr ^ low                       # in ``_sort_key``'s unsigned bits

    counts_ref[...] = jnp.zeros_like(counts_ref)

    def chosen(r, masked):
        hit = key_ref[block(r), :] >= thr
        hit = (hit & valid(r)) if masked else hit
        ones = hit.astype(i32)
        mask_ref[block(r), :] = ones.astype(jnp.int8)
        counts_ref[pl.ds(r, 1), :] = jnp.sum(ones, axis=0, keepdims=True)

    def dead(r, _):
        mask_ref[block(r), :] = jnp.zeros((rows, L), jnp.int8)

    over_live_rows(chosen)
    lax.fori_loop(live, S // rows, dead, None)


def unwritten(shape, dtype, after, interpret: bool = False):
    """An array nobody has written: what a caller that goes on to write every
    element starts from where zeros would cost a pass over it (the int8 mask
    of 16,384 positions: 0.5 ms a layer, my chip run, PR 62). ``after``: an
    array of the caller's, never read; it makes the result ITS layer's
    (with no operand XLA makes one array before the layer scan and copies it
    in every layer: 2.3 ms)."""
    import jax
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda after_ref, o_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="sxt_unwritten",
        interpret=interpret)(after)


def select_chunk(scores, mask_t, b, first, k: int, interpret: bool = False):
    """The selection of one chunk of queries of sequence ``b``: scores [S, C]
    float32 (``index_scores``'s, keys-major), the chunk's first query at
    ``first`` (both traced int32) -> (``mask_t`` [B, S, T] int8 with the chunk's
    columns written IN PLACE: 1 where key s is a valid (s <= t) key of query
    t at or above t's k-th largest, 0 on every other row; counts [S / rows, C]
    int32, the chosen keys a query a block of ``select_rows`` keys; the
    threshold [1, C] uint32 in ``ops/dsa._sort_key``'s bits). A query whose
    counts add up to more than k shares its threshold with more keys than
    fit: the caller's tie rule then decides among them."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, C = scores.shape
    lanes, rows = select_lanes(S, C), select_rows(S, C)
    at = jnp.stack([jnp.asarray(b, jnp.int32), jnp.asarray(first, jnp.int32)])
    column = lambda j, at: (0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(C // lanes,),
        in_specs=[pl.BlockSpec((S, lanes), column), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((None, S, lanes), lambda j, at: (at[0], 0, at[1] // lanes + j)),
                   pl.BlockSpec((S // rows, lanes), column),
                   pl.BlockSpec((1, lanes), column)],
        scratch_shapes=[pltpu.VMEM((S, lanes), jnp.int32)])
    with jax.named_scope(SELECT_NAME):
        mask_t, counts, thr = pl.pallas_call(
            functools.partial(_select_kernel, k=k, rows=rows), grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(mask_t.shape, jnp.int8),
                       jax.ShapeDtypeStruct((S // rows, C), jnp.int32),
                       jax.ShapeDtypeStruct((1, C), jnp.int32)],
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=SELECT_NAME, interpret=interpret)(at, scores, mask_t)
    return mask_t, counts, jax.lax.bitcast_convert_type(thr, jnp.uint32)
