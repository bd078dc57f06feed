"""The Mamba-2 state-space scan (mixer "ssm" of ``models/transformer.py``;
Nemotron-H's ``M`` layers): what lies between the mixer's convolution and its
gated norm.

With x [B, T, H, P] (H heads of P channels), the step ``dt`` [B, T, H] (after
its softplus, float32), ``A`` [H] (negative, float32), B and C [B, T, G, N] (G
groups of a state of N; head h reads group ``h // (H / G)``) and the skip
``D`` [H]::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g]
    o_t[h] = S_t[h] C_t[g] + D[h] x_t[h]              S_{-1} = 0, S [P, N]

(the mixer leaves the skip to its epilogue, ``ops/ssm_gate_norm.py``, and
calls the scan without ``D``)

Nothing erases: the state only decays and is written to, which is why the
gated DELTA rule's chunked form (``ops/gated_delta.py``: a triangular solve a
chunk) does not compute it.

:func:`ssd_recurrent` is that, a ``lax.scan`` over tokens in float32: the
oracle. :func:`ssd_chunked` is the structured-state-space-duality form the
trainer runs: within a chunk of Q tokens ``(L o (C B^T)) (dt x)`` with
``L[t, s] = exp(cum_t - cum_s)`` for s <= t (cum the running sum of dt A), a
chunk's own end state, the recurrence over the chunks' states in float32, and
the carried state's part of each output. B and C stay at their G groups: a
group's ``C B^T`` is formed once and read by its H / G heads, nothing is
repeated to H heads in memory. It has two bodies with the same arithmetic,
chosen by what the code can observe (:func:`ssd_route`: backend and shape, as
``ops/dispatch.py`` states it): on a TPU at an eligible shape the Pallas
kernels ``ssd_fwd`` / ``ssd_fwd_keep`` / ``ssd_bwd`` behind one
``jax.custom_vjp`` (the end of this file; the CPU suite drives them through
the interpreter, ``SXT_FUSED_INTERPRET=1``; selected, they run or raise),
everywhere else (the CPU, narrow heads or states, another chunk) XLA's
einsums, the kernels' oracle beside the recurrence. Every sequence starts from
an empty state and only the outputs leave: a carried-in state and the last
state as a result come with what needs them (serving, a sequence-parallel
mesh).
"""

from __future__ import annotations

import functools

CHUNK = 128
_LANES = 128


def ssd_route(x, B, chunk: int = CHUNK) -> str:
    """Which form :func:`ssd_chunked` runs for these operands, read off the
    backend and the shapes as ``gated_delta.kernel_route`` does: "pallas" on a
    TPU backend (``ops/dispatch.pallas_enabled``) at an eligible shape (the
    chunk ``CHUNK``; the state a whole number of lane tiles; heads that fill
    lane tiles, a group's heads whole tiles; x, B and C all bf16 or all
    float32), "interpret" at such a shape under ``SXT_FUSED_INTERPRET=1`` (the
    CPU suite's way to the same kernels), else "xla"."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    H, P = x.shape[-2:]
    G, N = B.shape[-2:]
    eligible = (chunk == CHUNK and N % _LANES == 0 and H % G == 0
                and _LANES % P == 0 and (H // G * P) % _LANES == 0
                and x.dtype == B.dtype and x.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def ssd_chunks(seq: int, chunk: int = CHUNK) -> int:
    """Chunks of ``chunk`` tokens the chunked form walks a sequence of ``seq``
    in (the last one padded)."""
    return -(-int(seq) // chunk)


def _grouped(x, dt, A, B):
    """Heads as [G, R] (R = H / G heads a group), so that a group's B and C
    meet their heads by broadcasting."""
    Bt, T, H, P = x.shape
    G = B.shape[2]
    if H % G:
        raise ValueError(f"ssd: {H} heads do not divide into {G} groups")
    R = H // G
    return x.reshape(Bt, T, G, R, P), dt.reshape(Bt, T, G, R), A.reshape(G, R)


def ssd_recurrent(x, dt, A, B, C, D):
    """The recurrence as the module's docstring writes it, a ``lax.scan`` over
    tokens in float32 at HIGHEST matmul precision -> o [B, T, H, P] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    xg, dtg, Ag = _grouped(x.astype(f32), dt.astype(f32), A.astype(f32), B)
    R = H // G
    Dg = D.astype(f32).reshape(G, R)

    def step(S, row):
        xt, dtt, Bt_, Ct = row                  # [Bt,G,R,P] [Bt,G,R] [Bt,G,N] x 2
        S = (jnp.exp(dtt * Ag)[..., None, None] * S
             + (dtt[..., None] * xt)[..., None] * Bt_[:, :, None, None, :])
        o = jnp.einsum("bgrpn,bgn->bgrp", S, Ct,
                       precision=jax.lax.Precision.HIGHEST) + Dg[..., None] * xt
        return S, o

    rows = (jnp.moveaxis(xg, 1, 0), jnp.moveaxis(dtg, 1, 0),
            jnp.moveaxis(B.astype(f32), 1, 0), jnp.moveaxis(C.astype(f32), 1, 0))
    _, o = jax.lax.scan(step, jnp.zeros((Bt, G, R, P, N), f32), rows)
    return jnp.moveaxis(o, 0, 1).reshape(Bt, T, H, P)


def _chunks_xla(x, dt, A, B, C):
    """A sequence's ``n`` chunks of Q tokens at once, from an empty state: x
    [Bt, n, Q, G, R, P] (the compute dtype), dt [Bt, n, Q, G, R] float32, A
    [G, R] float32, B and C [Bt, n, Q, G, N] -> o [Bt, n, Q, G, R, P] float32
    without the skip. The matmuls take their operands in x's dtype and
    accumulate in float32; the states, their recurrence over the chunks and
    the read of a chunk's starting state are float32 (the last at
    ``Precision.HIGH``: a TPU's default would round the state to bf16 in the
    product). All chunks at once (the [heads, Q, Q] matrices and a [P, N]
    state a head and chunk: 0.5 and 0.27 GB in float32 at 2 x 8192 tokens and
    64 heads) compile the benchmark cell's train step to a peak of 14.47 GB
    for a v5e, where blocks of 8 chunks under a checkpointed ``lax.scan`` read
    15.53 (the scan's stacked operands and results cost more than the matrices
    it saves; AOT compiles, PR 46)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    high, highest = jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST
    dtype = x.dtype
    n, Q = x.shape[1:3]
    a = dt * A                                                # log-decay a token, <= 0
    cum = jnp.cumsum(a, axis=2)                               # inclusive, a chunk
    # within a chunk: (L o C B^T) (dt x), a group's C B^T formed once
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", C, B, preferred_element_type=f32)
    rows = jnp.moveaxis(cum, 2, -1)                           # [Bt, n, G, R, Q]
    later = jnp.tril(jnp.ones((Q, Q), bool))                  # s <= t
    decay = jnp.exp(jnp.where(later, rows[..., :, None] - rows[..., None, :], -jnp.inf))
    mixed = (decay * cb[:, :, :, None]).astype(dtype)         # [Bt, n, G, R, Q, Q]
    dtx = dt[..., None] * x.astype(f32)
    o = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", mixed, dtx.astype(dtype),
                   preferred_element_type=f32)
    # a chunk's own end state: what its tokens write, decayed to its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    own = jnp.einsum("bcqgrp,bcqgn->bcgrpn", (to_end[..., None] * dtx).astype(dtype), B,
                     preferred_element_type=f32)
    # the recurrence over chunks: the state before chunk c is chunk j's own,
    # j < c, decayed by the chunks between
    ends = cum[:, :, -1]                                      # a chunk's whole log-decay
    through = jnp.cumsum(ends, axis=1)                        # [Bt, n, G, R], inclusive
    between = (through - ends)[:, :, None] - through[:, None]  # [Bt, c, j, G, R]
    earlier = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]
    weigh = jnp.exp(jnp.where(earlier[None, :, :, None, None], between, -jnp.inf))
    starts = jnp.einsum("bcjgr,bjgrpn->bcgrpn", weigh, own, precision=highest)
    # the starting state's part of each output
    return o + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcqgn,bcgrpn->bcqgrp", C.astype(f32), starts, precision=high)


def ssd_chunked(x, dt, A, B, C, D=None, chunk: int = CHUNK):
    """The chunked form -> o [B, T, H, P] in x's dtype. T is padded to whole
    chunks with steps of 0, which neither decay nor write. ``D`` None: no
    skip, and the kernels' output leaves as it is (the mixer adds the skip in
    its epilogue, ``ops/ssm_gate_norm.py``, where the sum is not rounded on
    its way to the gate); given, ``D x`` is added in float32 and the sum
    rounded to x's dtype."""
    import jax.numpy as jnp

    f32 = jnp.float32
    Bt, T, H, P = x.shape
    route = ssd_route(x, B, chunk)
    if route != "xla":
        o = _ssd_pallas(x, dt, A, B, C, chunk, interpret=route == "interpret")
    else:
        xg, dtg, Ag = _grouped(x, dt.astype(f32), A.astype(f32), B)
        pad = -T % chunk
        n = (T + pad) // chunk

        def cut(a):
            """[Bt, T, ...] -> [Bt, n, chunk, ...], padded with zeros."""
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return a.reshape((Bt, n, chunk) + a.shape[2:])

        o = _chunks_xla(cut(xg), cut(dtg), Ag, cut(B), cut(C))
        o = o.reshape(Bt, T + pad, H, P)[:, :T]
    if D is not None:
        o = o.astype(f32) + D.astype(f32)[:, None] * x.astype(f32)
    return o.astype(x.dtype)


# ----------------------------------------------------------------------
# The chunked form as Pallas kernels (what a TPU runs)
# ----------------------------------------------------------------------
#
# A grid step is one chunk of ONE GROUP of one row: the group's C B^T is formed
# once and read by its R = H / G heads, and dB and dC, sums over those heads,
# leave the step whole. The grid's last axis walks the chunks in order and the
# group's states S [R P, N] float32 stay in a VMEM scratch from one step to the
# next. HBM sees x and o as [B, T, H P] and B, C as [B, T, G N] (the mixer's own
# layouts: a group's lanes are a block), the step and the cumulative log-decay
# as a column a head ([B, G, chunks, Q, R]; the decay also as a row, [.., R, Q],
# for ``L[t, s]``), and, from the forward that a backward follows
# (``ssd_fwd_keep``), each chunk's starting states [B, G, chunks, R P, N]
# float32 (268 MB a layer at 2 x 8,192 tokens and 64 heads of 64 x 128), which
# is all the backward needs beside the inputs. Every [Q, Q] matrix (C B^T, a
# head's L, their product, their cotangents) lives in VMEM only.
#
# A group's lanes are worked on a lane tile at a time (128 lanes: 128 / P
# heads): a head's [Q, Q] matrix multiplies the tile whole and a lane mask
# keeps the head's own columns (at P = 64 the MXU's other half would idle
# anyway), so nothing is sliced or joined inside a tile.
#
# What is rounded where (``mxu`` = the dtype of x, B and C as they arrive), as
# in the XLA form: float32 the step, the decays, dt x, the states as the walk
# carries them, every accumulator; ``mxu`` the operands of C B^T, of (L o C
# B^T) (dt x), of the chunk's own end state and of the cotangents' products. A
# state is READ (C S^T, and in the backward dy^T S and (dt x)^T dS) as its
# three bf16 parts, float32 to the last bit or two: a TPU's plain product would
# round it to bf16.
#
# The backward kernel walks the chunks from the last to the first with dS in
# VMEM and computes the chunk's matrices again from the inputs and the kept
# state. The decay's gradient leaves as d cum (the cumulative sum's and the
# product with A are XLA's, and so are their gradients): with Z = dM o M,
#   d cum_t = sum_s Z[t, s] - sum_s Z[s, t] + dy_t . (e^cum_t C_t S0^T)
#             - (dt x)_t . dU2_t      (dU2: the end state's part of d (dt x))
# and on the chunk's last token also <dS, e^last S0> + sum_s (dt x)_s . dU2_s.


def _ssd_pallas(x, dt, A, B, C, chunk, interpret):
    """``ssd_chunked`` without the skip through the kernels -> o [B, T, H, P]
    in x's dtype. The padding to whole chunks (steps of 0), the decay's
    product and running sum and the layouts are XLA's, and so are their
    gradients. The running sum is taken where the
    heads are the minor axis ([B, n, Q, H]) and only then laid out a column
    and a row a head: a column of 8 heads pads its lane tile sixteenfold, and
    XLA's cumulative sum (and its transpose in the backward) over that layout
    cost more than the kernels (my chip run, PR 46: 17 ms a step)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    R, Q = H // G, chunk
    pad = -T % Q
    n = (T + pad) // Q
    whole = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    chunks = lambda a: a.reshape(Bt, n, Q, G, R)
    cols = lambda a: chunks(a).transpose(0, 3, 1, 2, 4)              # [Bt, G, n, Q, R]
    rows = lambda a: chunks(a).transpose(0, 3, 1, 4, 2)              # [Bt, G, n, R, Q]
    step = whole(dt.astype(f32))
    cum = jnp.cumsum((step * A.astype(f32)).reshape(Bt, n, Q, H), axis=2)
    o = _ssd_core(R, P, interpret)(
        whole(x).reshape(Bt, T + pad, H * P), cols(step), cols(cum), rows(cum),
        whole(B).reshape(Bt, T + pad, G * N), whole(C).reshape(Bt, T + pad, G * N))
    return o.reshape(Bt, T + pad, H, P)[:, :T]


@functools.lru_cache(maxsize=None)
def _ssd_core(R: int, P: int, interpret: bool):
    """The scan on whole chunks as one ``jax.custom_vjp``: (x [B, T, H P], the
    step and the cumulative log-decay a column a head [B, G, n, Q, R] float32,
    the latter a row a head too [B, G, n, R, Q], B and C [B, T, G N]) -> o
    [B, T, H P] in x's dtype. Undifferentiated (and in the pass of a
    ``jax.checkpoint`` that keeps nothing) the forward kernel writes that
    alone; differentiated it also writes each chunk's starting state, all the
    backward kernel needs beside the inputs. The decay's cotangent comes back
    in two parts, what reached its columns and what reached its rows."""
    import jax

    # each launch under its own jit, built once (``gated_delta._delta_core``)
    launch = lambda fn, **static: jax.jit(functools.partial(
        fn, R=R, P=P, interpret=interpret, **static))
    forward, forward_keep = (launch(_forward, keep=keep) for keep in (False, True))
    backward = launch(_backward)

    @jax.custom_vjp
    def core(x, step, cum, cum_rows, B, C):
        return forward(x, step, cum, cum_rows, B, C)[0]

    def fwd(x, step, cum, cum_rows, B, C):
        o, s0 = forward_keep(x, step, cum, cum_rows, B, C)
        return o, (x, step, cum, cum_rows, B, C, s0)

    def bwd(kept, do):
        return tuple(backward(*kept, do))

    core.defvjp(fwd, bwd, optimize_remat=True)
    return core


def _blocks(R, P, G, N, chunk_at):
    """The block specs of a grid step (row b, group g, step n) that works on
    chunk ``chunk_at(n)``: ``wide`` for x, o [B, T, H P], ``narrow`` for B, C
    [B, T, G N], ``col`` / ``row`` for a column / a row of Q numbers a head,
    ``state`` for the kept states."""
    from jax.experimental import pallas as pl

    Q, W = CHUNK, R * P
    at = lambda b, g, n: (b, g, chunk_at(n), 0, 0)
    return dict(
        wide=pl.BlockSpec((1, Q, W), lambda b, g, n: (b, chunk_at(n), g)),
        narrow=pl.BlockSpec((1, Q, N), lambda b, g, n: (b, chunk_at(n), g)),
        col=pl.BlockSpec((1, 1, 1, Q, R), at), row=pl.BlockSpec((1, 1, 1, R, Q), at),
        state=pl.BlockSpec((1, 1, 1, W, N), at))


def _sizes(x, B, R, P):
    Bt, Tp, HP = x.shape
    G = HP // (R * P)
    return Bt, Tp, G, B.shape[-1] // G, Tp // CHUNK


def _forward(x, step, cum, cum_rows, B, C, R, P, interpret, keep):
    """The forward kernel's launch -> [o], and each chunk's starting state
    too where ``keep``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .gated_delta import _compiler_params

    f32 = jnp.float32
    Bt, Tp, G, N, n = _sizes(x, B, R, P)
    W = R * P
    at = _blocks(R, P, G, N, lambda c: c)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [at["wide"]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((Bt, G, n, W, N), f32))
        out_specs.append(at["state"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, R=R, P=P, keep=keep),
        grid=(Bt, G, n),
        in_specs=[at["wide"], at["col"], at["col"], at["row"], at["narrow"], at["narrow"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((W, N), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssd_fwd_keep" if keep else "ssd_fwd",
    )(x, step, cum, cum_rows, B, C)


def _backward(x, step, cum, cum_rows, B, C, s0, do, R, P, interpret):
    """The backward kernel's launch -> [dx, d step, d cum by its columns, d cum
    by its rows, dB, dC]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .gated_delta import _compiler_params

    f32 = jnp.float32
    Bt, Tp, G, N, n = _sizes(x, B, R, P)
    W = R * P
    # the sweep runs over the chunks from the last to the first
    at = _blocks(R, P, G, N, lambda c: n - 1 - c)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, R=R, P=P),
        grid=(Bt, G, n),
        in_specs=[at["wide"], at["col"], at["col"], at["row"], at["narrow"], at["narrow"],
                  at["state"], at["wide"]],
        out_specs=[at["wide"], at["col"], at["col"], at["row"], at["narrow"], at["narrow"]],
        out_shape=[like(x), like(step), like(cum), like(cum_rows), like(B), like(C)],
        scratch_shapes=[pltpu.VMEM((W, N), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssd_bwd",
    )(x, step, cum, cum_rows, B, C, s0, do)


def _state_products(mxu):
    """(prod, read): ``prod`` as ``gated_delta._products``' (operands rounded to
    ``mxu``, a float32 accumulator); ``read(a, s, dims)`` a product whose
    second operand is a float32 STATE, taken as its three bf16 parts laid side
    by side along the contraction against a (which is ``mxu``: nothing of it
    is lost), so that the state enters at float32 accuracy."""
    import jax
    import jax.numpy as jnp

    from .gated_delta import _products

    f32, bf16 = jnp.float32, jnp.bfloat16
    prod, exact = _products(mxu)
    if mxu == f32:
        return prod, exact

    def read(a, s, dims):
        (along_a,), (along_s,) = dims[0]
        hi = s.astype(bf16)
        rest = s - hi.astype(f32)
        mid = rest.astype(bf16)
        lo = (rest - mid.astype(f32)).astype(bf16)
        a = a.astype(bf16)
        return jax.lax.dot_general(
            jnp.concatenate([a, a, a], axis=along_a),
            jnp.concatenate([hi, mid, lo], axis=along_s), dims,
            preferred_element_type=f32)

    return prod, read


def _chunk_tiles(x_ref, step_ref, cumc_ref, cumr_ref, b_ref, c_ref, R, P):
    """What both kernels make of a grid step's blocks before any state is
    read: the group's C B^T, and a lane tile at a time the tile's heads, lane
    masks, x, dt x and the decays widened to the tile's lanes; ``decay(r)``
    is head r's L [Q, Q]."""
    import types

    import jax
    import jax.numpy as jnp

    from .gated_delta import _NT

    f32 = jnp.float32
    Q = CHUNK
    mxu = x_ref.dtype
    prod, read = _state_products(mxu)
    Bm, Cm = b_ref[0], c_ref[0]
    step, cc, cr = step_ref[0, 0, 0], cumc_ref[0, 0, 0], cumr_ref[0, 0, 0]
    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = i >= j
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
    per = _LANES // P                                       # heads a lane tile
    col = lambda a, r: a[:, r:r + 1]                        # [Q, 1]

    def decay(r):
        # exp only of the exponents that are used (<= 0)
        return jnp.where(lower, jnp.exp(jnp.where(
            lower, col(cc, r) - cr[r:r + 1, :], 0.0)), 0.0)

    tiles = []
    for t in range(R // per):
        heads = list(range(t * per, (t + 1) * per))
        lanes = slice(t * _LANES, (t + 1) * _LANES)
        own = [(lane >= k * P) & (lane < (k + 1) * P) for k in range(per)]
        rows = [(sub >= k * P) & (sub < (k + 1) * P) for k in range(per)]

        def widen(pieces, masks):
            """The heads' pieces side by side: each over its own lanes (or
            rows) of the tile."""
            out = pieces[-1]
            for piece, mask in zip(pieces[-2::-1], masks[-2::-1]):
                out = jnp.where(mask, piece, out)
            return out

        dt = widen([col(step, r) for r in heads], own) + jnp.zeros((Q, _LANES), f32)
        cum = widen([col(cc, r) for r in heads], own) + jnp.zeros((Q, _LANES), f32)
        last = [cr[r:r + 1, Q - 1:Q] for r in heads]                     # [1, 1] each
        xs = x_ref[0, :, lanes].astype(f32)
        tiles.append(types.SimpleNamespace(
            heads=heads, lanes=lanes, own=own, rows=rows, x=xs, dt=dt, u=dt * xs,
            head=jnp.exp(cum),                                           # e^cum_t
            tail=jnp.exp(widen(last, own) - cum),                        # e^(last - cum_t)
            last=last,
            # e^last a row of the tile's states: [1, 1] -> [128, 1] with the
            # exp after it (Mosaic broadcasts along one of sublanes and lanes
            # at a time)
            carry=jnp.exp(widen(last, rows) + jnp.zeros((_LANES, 1), f32)),
            pick=lambda parts, own=own: widen(parts, own)))
    return types.SimpleNamespace(
        mxu=mxu, prod=prod, read=read, B=Bm, C=Cm, cb=prod(Cm, Bm, _NT),
        decay=decay, tiles=tiles)


def _fwd_kernel(x_ref, step_ref, cumc_ref, cumr_ref, b_ref, c_ref, o_ref,
                *rest, R, P, keep):
    """One chunk of one group of one row; the grid's last axis walks the
    chunks in order and ``S`` [R P, N] float32 carries the group's states from
    one to the next in VMEM."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .gated_delta import _NT, _TN

    S = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    m = _chunk_tiles(x_ref, step_ref, cumc_ref, cumr_ref, b_ref, c_ref, R, P)
    if keep:
        rest[0][0, 0, 0] = S[...]
    for t in m.tiles:
        S0 = S[t.lanes, :]
        u = t.u.astype(m.mxu)
        within = t.pick([m.prod((m.decay(r) * m.cb).astype(m.mxu), u) for r in t.heads])
        o_ref[0, :, t.lanes] = (within + t.head * m.read(m.C, S0, _NT)).astype(o_ref.dtype)
        S[t.lanes, :] = S0 * t.carry + m.prod(t.tail * t.u, m.B, _TN)


def _bwd_kernel(x_ref, step_ref, cumc_ref, cumr_ref, b_ref, c_ref, s0_ref, do_ref,
                dx_ref, dstep_ref, dcum_ref, dcum_rows_ref, db_ref, dc_ref,
                dS, *, R, P):
    """The same chunk's gradients; the grid's last axis walks the chunks from
    the last to the first and ``dS`` carries the states' cotangent (zeros to
    begin with: nothing reads the last state). The chunk's matrices are
    computed again from the inputs and the kept S0; casts pass a cotangent
    through unrounded."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .gated_delta import _NN, _NT, _TN

    f32 = jnp.float32
    Q = CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS[...] = jnp.zeros_like(dS)

    m = _chunk_tiles(x_ref, step_ref, cumc_ref, cumr_ref, b_ref, c_ref, R, P)
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)        # [Q, 1]
    total = lambda a: jnp.sum(a, axis=0, keepdims=True)         # [1, n]
    at_last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    heads_lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    heads_row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    dcb = jnp.zeros((Q, Q), f32)
    dB = jnp.zeros(m.B.shape, f32)
    dC = jnp.zeros(m.C.shape, f32)
    dstep = jnp.zeros((Q, R), f32)
    dcum = jnp.zeros((Q, R), f32)
    dcum_rows = jnp.zeros((R, Q), f32)
    for t in m.tiles:
        S0, dS1 = s0_ref[0, 0, 0, t.lanes, :], dS[t.lanes, :]
        do = do_ref[0, :, t.lanes].astype(f32)
        u = t.u.astype(m.mxu)
        from_state = t.head * m.read(m.C, S0, _NT)               # the carried state's part of o
        du_end = t.tail * m.read(m.B, dS1, _NT)                  # the end state's part of d (dt x)
        du = []
        for k, r in enumerate(t.heads):
            L = m.decay(r)
            M = L * m.cb
            mine = jnp.where(t.own[k], do, 0.0).astype(m.mxu)
            dM = m.prod(mine, u, _NT)                            # [Q, Q]: dy_t . (dt x)_s
            du.append(m.prod(M.astype(m.mxu), mine, _TN))        # M^T dy
            dcb = dcb + dM * L
            Z = dM * M
            at = heads_lane == r
            dcum = dcum + jnp.where(at, rowsum(Z), 0.0)
            dcum_rows = dcum_rows + jnp.where(heads_row == r, -total(Z), 0.0)
        du = t.pick(du) + du_end
        dx_ref[0, :, t.lanes] = (t.dt * du).astype(dx_ref.dtype)
        moved = do * from_state - t.u * du_end                   # d cum_t, lane by lane
        ended = S0 * dS1 * t.carry                               # d last, row by row
        for k, r in enumerate(t.heads):
            at = heads_lane == r
            end = (total(rowsum(jnp.where(t.rows[k], ended, 0.0)))
                   + total(rowsum(jnp.where(t.own[k], t.u * du_end, 0.0))))      # [1, 1]
            dcum = dcum + jnp.where(at, rowsum(jnp.where(t.own[k], moved, 0.0))
                                    + jnp.where(at_last, end, 0.0), 0.0)
            dstep = dstep + jnp.where(at, rowsum(jnp.where(t.own[k], du * t.x, 0.0)), 0.0)
        headed = (t.head * do).astype(m.mxu)
        tailed = (t.tail * t.u).astype(m.mxu)
        dC = dC + m.read(headed, S0, _NN)
        dB = dB + m.read(tailed, dS1, _NN)
        dS[t.lanes, :] = dS1 * t.carry + m.prod(headed, m.C, _TN)
    dcb = dcb.astype(m.mxu)
    dc_ref[0] = (dC + m.prod(dcb, m.B)).astype(dc_ref.dtype)
    db_ref[0] = (dB + m.prod(dcb, m.C, _TN)).astype(db_ref.dtype)
    dstep_ref[0, 0, 0] = dstep
    dcum_ref[0, 0, 0] = dcum
    dcum_rows_ref[0, 0, 0] = dcum_rows
