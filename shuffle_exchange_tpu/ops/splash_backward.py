"""The splash attention routes' backward as ONE Pallas kernel.

The library's backward (``jax.experimental.pallas.ops.tpu.splash_attention``)
is two kernels that each recompute the scores: ``dkv`` forms s, dp, dv, dk
and ``dq`` forms s and dp AGAIN and then dq: seven products and two passes
of exp / mask / ``dp - di`` over every visited tile. Its own
``use_fused_bwd_kernel`` writes dq once a KEY block to HBM in q's dtype and
lets XLA sum the copies (``kv_seq_len // bkv`` x q's bytes a layer, rounded).

Here every visited (query block, key block) pair is one grid step of five
products: s, dp, dv += p^T do, dk += ds^T q, dq += ds k. The grid walks the
pairs query block by query block, so dq of the block in hand is a float32
scratch that is cast and written once when its key loop ends, and dk / dv of
the WHOLE key head ([S, D] + [S, Dv] float32, 12-16 MiB at the benchmark's
shapes) stay in VMEM across that head's query blocks and its group's query
heads and are cast and written once: no partial sum of dq, dk or dv ever
reaches HBM, whatever the group size. Scores, softmax and all three
accumulators are float32, as in the two kernels.

Causal and causal-window masks only, by arithmetic: ``visited_pairs`` lists
the pairs the mask touches (the ones ``block_visit_share`` counts), the grid
has one step a pair, and the mask is applied inside the partly visible ones.
``ops/flash_attention.attention_backward_route`` says where this runs.
"""

from __future__ import annotations

import functools

import numpy as np

#: the pallas_call's name: the engagement counter among a trace's device ops
KERNEL_NAME = "sxt_splash_bwd_fused"

#: what the kernel asks Mosaic for (a v5e core has 128 MiB of VMEM) ...
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
#: ... and what ``vmem_bytes`` may estimate before the route declines
VMEM_BUDGET_BYTES = 80 * 1024 * 1024

_FIRST, _LAST, _PARTIAL = 1, 2, 4


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


def vmem_bytes(S: int, D: int, Dv: int, bq: int, bkv: int, itemsize: int = 2) -> int:
    """An estimate of the kernel's VMEM need: the resident float32 dk / dv
    and their double-buffered outputs, the score-sized tiles of one step
    (s, p, dp in float32 and three ``itemsize`` copies), and the double-
    buffered operand blocks with dq's scratch."""
    width = _lanes(D) + _lanes(Dv)
    resident = S * width * (4 + 2 * itemsize)
    tiles = bq * bkv * (3 * 4 + 3 * itemsize)
    operands = (2 * (bq + bkv) * width * itemsize
                + bq * _lanes(D) * (4 + 2 * itemsize))
    return resident + tiles + operands


def visited_pairs(T: int, S: int, bq: int, bkv: int, window: int = 0) -> np.ndarray:
    """The (query block, key block) pairs a causal mask over [T, S] scores
    touches at these blocks, query-major, as int32 rows (query block, key
    block, flags): 1 = the query block's first pair, 2 = its last, 4 = partly
    masked. Key j is visible to query i iff 0 <= i + (S - T) - j, and
    < ``window`` where one is given."""
    off = S - T
    rows = []
    for i in range(T // bq):
        q_lo, q_hi = i * bq + off, (i + 1) * bq - 1 + off
        mine = []
        for j in range(S // bkv):
            k_lo, k_hi = j * bkv, (j + 1) * bkv - 1
            if k_lo > q_hi or (window and q_lo - k_hi >= window):
                continue
            whole = k_hi <= q_lo and (not window or q_hi - k_lo < window)
            mine.append([i, j, 0 if whole else _PARTIAL])
        if not mine:
            raise ValueError(f"query block {i} of [{T}, {S}] sees no key")
        mine[0][2] |= _FIRST
        mine[-1][2] |= _LAST
        rows += mine
    return np.asarray(rows, np.int32)


def _kernel(qi_ref, kj_ref, flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
            di_ref, *rest, bq: int, bkv: int, off: int, window: int):
    # ``rest``: the outputs and scratch, after the tile of a mask that is
    # data where the call has one (``fused_backward``'s ``mask``)
    *mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as library)

    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))      # a @ b.T
    tn = (((0,), (0,)), ((), ()))      # a.T @ b
    g, step = pl.program_id(2), pl.program_id(3)
    i, j, flags = qi_ref[step], kj_ref[step], flags_ref[step]

    @pl.when(jnp.logical_and(g == 0, step == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(flags & _FIRST != 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def pair(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse, di = lse_ref[...], di_ref[...]                         # [1, bq]
        # keys along sublanes, queries along lanes: dv and dk are plain
        # products of the tile, dq alone needs it transposed
        s = lax.dot_general(k, q, nt, preferred_element_type=f32)    # [bkv, bq]
        if mask_ref:
            # (int8, keys along sublanes; it holds the causal mask too)
            s = jnp.where(mask_ref[0][...].astype(jnp.int32) != 0, s,
                          library.DEFAULT_MASK_VALUE)
        elif masked:
            ahead = (i * bq + off - j * bkv
                     + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                     - lax.broadcasted_iota(jnp.int32, s.shape, 0))
            seen = ahead >= 0
            if window:
                seen = jnp.logical_and(seen, ahead < window)
            # (the library's value: exp(it - logsumexp) == 0)
            s = jnp.where(seen, s, library.DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        keys = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
        dv_acc[keys, :] += lax.dot(p.astype(do.dtype), do,
                                   preferred_element_type=f32)
        dp = lax.dot_general(v, do, nt, preferred_element_type=f32)  # [bkv, bq]
        ds = ((dp - di) * p).astype(q.dtype)
        dk_acc[keys, :] += lax.dot(ds, q, preferred_element_type=f32)
        dq_acc[...] += lax.dot_general(ds, k, tn, preferred_element_type=f32)

    partial = flags & _PARTIAL != 0
    pl.when(partial)(functools.partial(pair, True))
    pl.when(jnp.logical_not(partial))(functools.partial(pair, False))

    @pl.when(flags & _LAST != 0)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(g == pl.num_programs(2) - 1,
                             step == pl.num_programs(3) - 1))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def fused_backward(q, k, v, out, logsumexp, do, *, bq: int, bkv: int,
                   window: int = 0, interpret: bool = False, mask=None):
    """(dq, dk, dv) of causal attention from the forward kernel's residuals.

    q [B, KV, G, T, D] (scaled), k [B, KV, S, D], v [B, KV, S, Dv], out and
    do [B, KV, G, T, Dv], logsumexp [B, KV, G, T] float32: query heads g of
    key head j share its k and v. ``window`` > 0: the causal local mask of
    ``ops/flash_attention.splash_mask``. ``mask`` [B, S, T] int8 (keys-major,
    inside the causal mask): the visible (key, query) pairs as DATA, one set
    for every head (a learned sparse attention, ``ops/dsa_kernels``); every
    causal pair of blocks is visited and the tile read beside the operands."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, T, D = q.shape
    S, Dv = k.shape[2], v.shape[3]
    pairs = visited_pairs(T, S, bq, bkv, window)
    # (an einsum, as in the library: the compiled step then holds the same
    # XLA ops around the kernel as with the library's backward)
    di = jnp.einsum("bhgtd,bhgtd->bhgt", out.astype(jnp.float32),
                    do.astype(jnp.float32))
    row = lambda x: x.astype(jnp.float32)[..., None, :]              # [B,KV,G,1,T]

    at_q = lambda b, h, g, s, qi, kj, fl: (b, h, g, qi[s], 0)
    at_k = lambda b, h, g, s, qi, kj, fl: (b, h, kj[s], 0)
    at_row = lambda b, h, g, s, qi, kj, fl: (b, h, g, 0, qi[s])
    whole = lambda b, h, g, s, qi, kj, fl: (b, h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, G, len(pairs)),
        in_specs=[
            pl.BlockSpec((None, None, None, bq, D), at_q),
            pl.BlockSpec((None, None, bkv, D), at_k),
            pl.BlockSpec((None, None, bkv, Dv), at_k),
            pl.BlockSpec((None, None, None, bq, Dv), at_q),
            pl.BlockSpec((None, None, None, 1, bq), at_row),
            pl.BlockSpec((None, None, None, 1, bq), at_row),
        ] + ([] if mask is None else [pl.BlockSpec(
            (None, bkv, bq), lambda b, h, g, s, qi, kj, fl: (b, kj[s], qi[s]))]),
        out_specs=[
            pl.BlockSpec((None, None, None, bq, D), at_q),
            pl.BlockSpec((None, None, S, D), whole),
            pl.BlockSpec((None, None, S, Dv), whole),
        ],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((S, D), jnp.float32),
                        pltpu.VMEM((S, Dv), jnp.float32)])
    with jax.named_scope(KERNEL_NAME):
        return pl.pallas_call(
            functools.partial(_kernel, bq=bq, bkv=bkv, off=S - T, window=window),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=KERNEL_NAME, interpret=interpret,
        )(*(jnp.asarray(pairs[:, c]) for c in range(3)), q, k, v, do,
          row(logsumexp), row(di), *(() if mask is None else (mask,)))
