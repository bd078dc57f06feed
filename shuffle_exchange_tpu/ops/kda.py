"""The delta rule with a decay a KEY CHANNEL (Kimi Delta Attention, KDA:
"Kimi Linear", arXiv:2510.26692; mixer "kda" of ``models/transformer.py``).

Per head, with a state ``S`` [dk, dv] that starts at 0, a log-decay ``g_t``
[dk] <= 0 for every key channel and a write strength ``beta_t`` in [0, 1]::

    S   <- diag(exp(g_t)) S           (row d of S decays by exp(g_t[d]))
    u_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

With ``g_t[d]`` the same for every d this is ``ops/gated_delta.py``'s rule
(Gated DeltaNet), and the two modules agree there to rounding.

``kda_recurrent`` is that, one token at a time, float32: the plain form, the
tests' oracle (the trainer never runs it). ``kda_chunked`` is the form that
trains: chunks of ``CHUNK`` tokens, everything inside a chunk as matrix
products, and a walk over the chunks that carries ``S``. Two bodies of the
same arithmetic, chosen by what the code can observe (``kernel_route``:
backend and shape, as ``ops/dispatch.py`` states it):

  Pallas kernels  on a TPU backend where dk and dv are whole lane tiles (128),
                  the chunk is ``CHUNK`` and q, k, v are all bf16 or all
                  float32: ``kda_rule_fwd`` / ``kda_rule_fwd_keep`` /
                  ``kda_rule_bwd`` behind one ``jax.custom_vjp``. The CPU
                  suite drives the same kernels through the interpreter
                  (``SXT_FUSED_INTERPRET=1``). Selected, they run or raise.
  XLA ops         everywhere else (the CPU, the 8-device CPU mesh, narrow
                  heads, other chunk sizes): einsums and a ``lax.scan`` over
                  the chunks; autodiff is its backward. The off-TPU path, and
                  the kernels' oracle beside the recurrence.

The chunked form, for one chunk of C tokens with ``Gamma_i = sum_{t<=i} g_t``
[dk] (cumulative inside the chunk) and ``S0`` the state at its start::

    KK_ij  = sum_d beta_i k_i[d] k_j[d] exp(Gamma_i[d] - Gamma_j[d])   (i > j)
    QK_ij  = sum_d      q_i[d] k_j[d] exp(Gamma_i[d] - Gamma_j[d])   (i >= j)
    T      = (I + KK)^-1
    W      = T (K_beta * exp(Gamma))                                   [C, dk]
    U      = T V_beta                                                  [C, dv]
    V_new  = U - W S0                                                  [C, dv]
    O      = (Q * exp(Gamma)) S0 + QK V_new
    S1     = diag(exp(Gamma_C)) S0 + (K * exp(Gamma_C - Gamma))^T V_new

What is new against the scalar rule is KK and QK: the decay between rows i
and j no longer leaves the sum over d as one [C, C] mask, and the naive split
``(K e^Gamma)(K e^-Gamma)^T`` overflows at the init's decays (``e^-Gamma``
passes float32's range inside one chunk). They are taken EXACTLY, nothing
clipped, every exponent <= 0 (``_pair_*``), as the published kernels take
them: the chunk is cut into sub-blocks of ``SUB`` = 16 rows; a sub-block
below the diagonal (rows I, columns J < I) is ONE product of operands decayed
towards the first row of I, ``(X_I exp(Gamma_I - Gamma_ref))(K_J exp(Gamma_ref
- Gamma_J))^T``; the diagonal sub-blocks take the sum over d explicitly. The
XLA form does so as written; the kernels a LAG at a time, row i against row
i - r (one shifted elementwise pass over the whole chunk a lag, where a pass a
column would be 64), on sub-blocks of ``_KSUB`` = 8 rows: 8 lags, and seven
lane groups in the product below the diagonal. The same sums either way.

What is rounded where (``mxu`` = the dtype of q, k and v as they arrive):
  float32   g, Gamma, every decay factor, beta; K_beta, V_beta and the other
            elementwise products; the diagonal sub-blocks of KK and QK (their
            sums over d are the vector unit's); the state S as the walk
            carries it; KK, T and the powers of KK (those products at float32
            accuracy); every product's accumulator and result
  ``mxu``   the two operands of every other product: the sub-blocks below
            the diagonal, T with its right-hand sides, W S0, (Q e^Gamma) S0,
            QK with V_new, and K^T V_new. S is ROUNDED to ``mxu`` as an
            operand and carried unrounded.

Operations the chunked form REQUIRES per head and chunk (what
``chipbench/arith_kda.py`` counts, forward; 2 x m x n x k a product): the
sub-blocks below the diagonal of KK and QK, 2 x 2 x (C^2 - C SUB) / 2 x dk as
products, their diagonal sub-blocks 2 x 3 x C x SUB x dk on the vector unit
(a multiply by the decay, one by the key, an add) and C x SUB x dk
exponentials; T's ten C^3 products (the count of the scalar rule's yardstick);
W and U 2 C^2 (dk + dv); W S0, (Q e^Gamma) S0 and K^T V_new 3 x 2 C dk dv; QK
x V_new 2 C^2 dv. Bytes: q, k, v in, g in float32 ([C, dk] a head and chunk:
twice q's bf16 bytes, where the scalar rule reads C numbers), beta, o out; the
keeping forward also S0 out.

What the kernels keep where: as ``ops/gated_delta.py``'s. A grid step is one
chunk of ``_HEADS_A_STEP`` heads of one row; the grid's last axis walks the
chunks in order and S stays in a VMEM scratch, TRANSPOSED ([dv, dk]: the
decay then scales lanes, and no kernel turns a row into a column). g goes in
as [B, H, T, dk] float32 and is cumulated INSIDE the kernel (a product with
the lower-triangular ones, at float32 accuracy); the backward hands back dg
at g's shape, per channel. The forward that a backward follows also writes
each chunk's starting state [B, H, N, dv, dk] float32; the backward kernel
walks the chunks from the last to the first with dS in VMEM and computes the
chunk's own matrices AGAIN. The decays' gradient needs no sum of its own:
every place Gamma enters is ``x * exp(+-Gamma)``, so ``dGamma = sum x dx``
over those places (``q dq - k dk`` for QK, the published kernels' identity).

``kda_prologue`` (the end of this file) is what lies between the mixer's
projection and the rule: the three causal convolutions, SiLU and the l2 norms
of q and k in one pass forward and one backward, through
``gated_delta.gdn_prologue``'s kernels told where KDA's segments lie.
"""

from __future__ import annotations

import functools
import math

from .gated_delta import (CHUNK, ROWS, _GROUP_LANES, _NT, _SUB, _TN,
                          _compiler_params, _each, _inverse, _products,
                          _prologue_core, _unit_lower_inverse, _whole_chunks,
                          causal_conv1d, l2norm)

# Rows of a sub-block of a chunk (module docstring): the published kernels' 16,
# what the XLA form takes, and with it the benchmark's count
# (``chipbench/arith_kda.py``, held to the XLA form's products by a test)
SUB = 16
# and what the Pallas kernels take, the same sums cut finer: half the lags, each a pass of the vector
# unit over the chunk, for seven lane groups in the ONE product below the
# diagonal where 16 rows make three: the MXU has the room. At the cell's
# [1, 16384, 32, 128] a layer's forward / keeping forward + backward read
# 16.9 / 46.7 ms at (16 rows, 4 heads a step), 13.3 / 35.9 at (8, 4), 11.8 /
# 33.3 at (8, 8), 14.9 / 43.6 at (16, 8), 17.9 / 46.9 at (8, 2) (my chip run,
# PR 67)
_KSUB = 8
# (row, head) pairs a grid step (their chains overlap, as the scalar rule's);
# where the heads do not divide by that, their largest common divisor
_HEADS_A_STEP = 8


def kda_recurrent(q, k, v, g, beta):
    """The rule as written in the module docstring, a ``lax.scan`` over
    tokens, float32 at HIGHEST matmul precision. q, k [B, T, H, dk] (already
    normalised and scaled), v [B, T, H, dv], g [B, T, H, dk], beta [B, T, H]
    -> o [B, T, H, dv] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1)


def kernel_route(q, k, v, chunk: int = CHUNK) -> str:
    """Which form ``kda_chunked`` runs, from what it can observe: "pallas" on
    a TPU backend (``ops/dispatch.pallas_enabled``) at an eligible shape (dk
    and dv whole lane tiles, chunk ``CHUNK``, q, k and v all bf16 or all
    float32), "interpret" at such a shape under ``SXT_FUSED_INTERPRET=1``,
    else "xla"."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    eligible = (chunk == CHUNK and q.shape[-1] % 128 == 0
                and v.shape[-1] % 128 == 0 and q.dtype == k.dtype == v.dtype
                and q.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def _sub_of(chunk: int) -> int:
    """Rows of a sub-block of a chunk of ``chunk`` tokens: ``SUB`` where it
    divides the chunk (the trainer's 64), else the whole chunk (the tests'
    small ones: the diagonal's explicit sum alone)."""
    return SUB if chunk % SUB == 0 else chunk


def _pair_xla(X, k, gamma, prod):
    """[sum_d x_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d]) for x in ``X``] on
    and below the diagonal (zero above), x, k, gamma [..., C, dk] float32 ->
    [..., C, C] float32, as the module docstring takes it: sub-blocks below
    the diagonal as products (``prod``: operands rounded to the compute
    dtype), the diagonal ones summed over d in float32."""
    import jax.numpy as jnp

    C, dk = k.shape[-2:]
    s = _sub_of(C)
    n = C // s
    rows = []
    for I in range(n):
        at = slice(I * s, (I + 1) * s)
        gI, kI = gamma[..., at, :], k[..., at, :]
        i = jnp.arange(s)[:, None, None]
        j = jnp.arange(s)[None, :, None]
        diff = gI[..., :, None, :] - gI[..., None, :, :]       # [.., s, s, dk]
        # exp only of the exponents that are used (<= 0)
        decay = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, diff, 0.0)), 0.0)
        ref = gamma[..., I * s:I * s + 1, :]
        right = k[..., :I * s, :] * jnp.exp(ref - gamma[..., :I * s, :])
        blocks = []
        for x in X:
            xI = x[..., at, :]
            # the vector unit's sum over d, no product
            diag = jnp.sum(xI[..., :, None, :] * kI[..., None, :, :] * decay, axis=-1)
            parts = [diag, jnp.zeros(diag.shape[:-1] + (C - (I + 1) * s,), diag.dtype)]
            if I:
                parts.insert(0, prod("...id,...jd->...ij", xI * jnp.exp(gI - ref), right))
            blocks.append(jnp.concatenate(parts, axis=-1))
        rows.append(blocks)
    return [jnp.concatenate([r[m] for r in rows], axis=-2) for m in range(len(X))]


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The same function as :func:`kda_recurrent` in the chunked
    (matrix-product) form of the module docstring; differentiable (the
    kernels' own backward where :func:`kernel_route` chooses them, else
    autodiff through the products and the scan over chunks), ``dg`` per
    channel. Same shapes; o [B, T, H, dv] float32. T need not divide by
    ``chunk``: the tail is padded with tokens that write nothing (beta 0,
    g 0) and cut off."""
    import jax
    import jax.numpy as jnp

    route = kernel_route(q, k, v, chunk)
    if route != "xla":
        return _kda_pallas(q, k, v, g, beta, interpret=route == "interpret")
    f32 = jnp.float32
    mxu = q.dtype
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    q, k, v, g, beta = _whole_chunks(q, k, v, g, beta, C)
    N = q.shape[1] // C

    def prod(spec, a, b):
        return jnp.einsum(spec, a.astype(mxu), b.astype(mxu),
                          preferred_element_type=f32)

    # [B, T, H, d] -> [B, H, N, C, d]; beta -> [B, H, N, C]
    chunks4 = lambda a: a.reshape(B, N, C, H, -1).transpose(0, 3, 1, 2, 4)
    q, k, v = chunks4(q), chunks4(k), chunks4(v)
    gamma = jnp.cumsum(chunks4(g.astype(f32)), axis=-2)
    beta = beta.astype(f32).reshape(B, N, C, H).transpose(0, 3, 1, 2)
    row = jnp.arange(C)[:, None]
    col = jnp.arange(C)[None, :]

    # as the scalar rule's XLA form: the two parts that are parallel over
    # chunks keep nothing for the backward but their inputs
    @jax.checkpoint
    def within_chunks(k, v, gamma, beta):
        kf = k.astype(f32)
        k_beta = kf * beta[..., None]
        v_beta = v.astype(f32) * beta[..., None]
        (kk,) = _pair_xla([k_beta], kf, gamma, prod)
        Tm = _unit_lower_inverse(jnp.where(row > col, kk, 0.0))
        W = prod("bhnij,bhnjd->bhnid", Tm, k_beta * jnp.exp(gamma))
        U = prod("bhnij,bhnjd->bhnid", Tm, v_beta)
        last = gamma[..., -1:, :]                                # [B,H,N,1,dk]
        k_tail = kf * jnp.exp(last - gamma)
        return W.astype(mxu), U, k_tail.astype(mxu), jnp.exp(last[..., 0, :])

    @jax.checkpoint
    def outputs(q, k, gamma, v_new, inter):
        (qk,) = _pair_xla([q.astype(f32)], k.astype(f32), gamma, prod)
        return inter + prod("bhnij,bhnjd->bhnid", qk, v_new)

    def body(S, x):
        W_i, U_i, k_i, q_i, decay_i = x
        S_op = S.astype(mxu)
        v_new = U_i - prod("bhck,bhkv->bhcv", W_i, S_op)
        inter = prod("bhck,bhkv->bhcv", q_i, S_op)
        S = S * decay_i[..., None] + prod("bhck,bhcv->bhkv", k_i, v_new)
        return S, (v_new, inter)

    W, U, k_tail, decay_last = within_chunks(k, v, gamma, beta)
    q_head = (q.astype(f32) * jnp.exp(gamma)).astype(mxu)
    lead = lambda a: jnp.moveaxis(a, 2, 0)                      # N first
    _, (v_new, inter) = jax.lax.scan(
        body, jnp.zeros((B, H, dk, dv), f32),
        (lead(W), lead(U), lead(k_tail), lead(q_head), lead(decay_last)))
    o = outputs(q, k, gamma, jnp.moveaxis(v_new, 0, 2), jnp.moveaxis(inter, 0, 2))
    return o.transpose(0, 2, 3, 1, 4).reshape(B, N * C, H, dv)[:, :T]


def chunk_sample(x, every: int = 16, chunk: int = CHUNK):
    """The tokens of one whole chunk in ``every`` of x [B, T, ...] (the first
    of each run of ``every``; all T where T is shorter than a chunk) -> [B,
    T', ...]: what the step statistics are read on, so that they cost a
    sixteenth of a pass over the decays and not a pass."""
    B, T = x.shape[:2]
    C = min(chunk, T)
    picked = x[:, :T // C * C].reshape((B, T // C, C) + x.shape[2:])[:, ::every]
    return picked.reshape((B, -1) + x.shape[2:])


def chunk_decay(g, chunk: int = CHUNK):
    """exp of g [B, T, H, dk] summed over each whole chunk of ``chunk`` tokens
    (one chunk of all T where T is shorter) -> [B, chunks, H, dk] float32 in
    (0, 1]: the share of a state's row that outlives a chunk, what the step
    statistics ``kda_decay_mean`` / ``kda_decay_min`` are taken over."""
    import jax.numpy as jnp

    B, T = g.shape[:2]
    C = min(chunk, T)
    n = T // C
    kept = g[:, :n * C].astype(jnp.float32).reshape((B, n, C) + g.shape[2:])
    return jnp.exp(jnp.sum(kept, axis=2))


# ----------------------------------------------------------------------
# The chunked form as Pallas kernels (what a TPU runs)
# ----------------------------------------------------------------------


def _kda_pallas(q, k, v, g, beta, interpret: bool = False):
    """``kda_chunked`` through the kernels. q, k, v and g go in as
    [B, H, T, d] and o comes out so; beta goes in as [B, H / G, N, G, C], a
    row of C numbers a chunk and head. The padding and the transposes are
    XLA's, and so are their gradients. XLA does NOT fold a transpose into
    whatever produces the array: behind ``ssm_conv``'s kernels and its own
    l2 norms it wrote q, k and v twice more on the way here (31 ms of
    ``copy`` a step of ``kimilinear-train``, PR 67's trace). The transposes
    of q, k, v and of their cotangents cancel against ``kda_prologue``'s,
    whose kernels write and read [B, H, T, d] themselves (PR 68); those of
    g, o and do are still XLA's passes."""
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, H, dk = q.shape
    C = CHUNK
    q, k, v, g, beta = _whole_chunks(q, k, v, g, beta, C)
    N = q.shape[1] // C
    G = math.gcd(H, _HEADS_A_STEP)
    rows = lambda a: a.reshape(B, N, C, H // G, G).transpose(0, 3, 1, 4, 2)
    wide = lambda a: jnp.swapaxes(a, 1, 2)                      # [B, H, T, d]
    o = _kda_core(G, interpret)(wide(q), wide(k), wide(v), wide(g.astype(f32)),
                                rows(beta.astype(f32)))
    return jnp.swapaxes(o, 1, 2)[:, :T]


@functools.lru_cache(maxsize=None)
def _kda_core(G: int, interpret: bool):
    """The rule on whole chunks as one ``jax.custom_vjp``: (q, k, v
    [B, H, T, d], g [B, H, T, dk] float32, beta [B, H / G, N, G, C]) ->
    o [B, H, T, dv] float32; built as ``gated_delta._delta_core`` is (each
    launch under its own jit; the pass of a ``jax.checkpoint`` that keeps
    nothing writes o alone)."""
    import jax

    launch = lambda fn, **static: jax.jit(functools.partial(
        fn, G=G, interpret=interpret, **static))
    forward, forward_keep = (launch(_forward, keep=keep) for keep in (False, True))
    backward = launch(_backward)

    @jax.custom_vjp
    def core(q, k, v, g, beta):
        return forward(q, k, v, g, beta)[0]

    def fwd(q, k, v, g, beta):
        o, s0 = forward_keep(q, k, v, g, beta)
        return o, (q, k, v, g, beta, s0)

    def bwd(kept, do):
        return tuple(backward(*kept, do))

    core.defvjp(fwd, bwd, optimize_remat=True)
    return core


def _blocks(G, chunk_at):
    """The block specs of a grid step (row b, head group h, step n) that
    works on chunk ``chunk_at(n)``: ``wide(d)`` for q, k, v, g, o
    [B, H, T, d], ``flat`` for beta [B, H / G, N, G, C], ``state(dv, dk)``
    for the kept states [B, H, N, dv, dk]."""
    from jax.experimental import pallas as pl

    C = CHUNK
    wide = lambda d: pl.BlockSpec((1, G, C, d), lambda b, h, n: (b, h, chunk_at(n), 0))
    flat = pl.BlockSpec((1, 1, 1, G, C), lambda b, h, n: (b, h, chunk_at(n), 0, 0))
    state = lambda dv, dk: pl.BlockSpec(
        (1, G, 1, dv, dk), lambda b, h, n: (b, h, chunk_at(n), 0, 0))
    return wide, flat, state


def _forward(q, k, v, g, beta, G, interpret, keep):
    """The forward kernel's launch -> [o], or [o, S0^T] where ``keep``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, H, Tp, dk = q.shape
    dv, N = v.shape[-1], Tp // CHUNK
    wide, flat, state = _blocks(G, lambda n: n)
    out_shape = [jax.ShapeDtypeStruct((B, H, Tp, dv), f32)]
    out_specs = [wide(dv)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((B, H, N, dv, dk), f32))
        out_specs.append(state(dv, dk))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, G=G, keep=keep),
        grid=(B, H // G, N),
        in_specs=[wide(dk), wide(dk), wide(dv), wide(dk), flat],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, dv, dk), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="kda_rule_fwd_keep" if keep else "kda_rule_fwd",
    )(q, k, v, g, beta)


def _backward(q, k, v, g, beta, s0, do, G, interpret):
    """The backward kernel's launch -> [dq, dk, dv, dg, dbeta]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, H, Tp, dk = q.shape
    dv, N = v.shape[-1], Tp // CHUNK
    # the sweep runs over the chunks from the last to the first
    wide, flat, state = _blocks(G, lambda n: N - 1 - n)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, G=G),
        grid=(B, H // G, N),
        in_specs=[wide(dk), wide(dk), wide(dv), wide(dk), flat, state(dv, dk), wide(dv)],
        out_specs=[wide(dk), wide(dk), wide(dv), wide(dk), flat],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((G, dv, dk), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="kda_rule_bwd",
    )(q, k, v, g, beta, s0, do.astype(f32))


def _chunk_masks():
    """The index masks of a chunk: namespace of [C, C] ``eye`` / ``lower`` /
    ``strict`` / ``lag`` (i - j), and [C, 1] ``row`` / ``sub`` (the row's
    sub-block) / ``within`` (its place inside it)."""
    import types

    import jax
    import jax.numpy as jnp

    C = CHUNK
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    return types.SimpleNamespace(eye=i == j, lower=i >= j, strict=i > j, lag=i - j,
                                 row=row, sub=row // _KSUB)


def _lag_decay(kf, gamma, r, m):
    """(decay, shifted k) of lag ``r``: row i against row i - r of its own
    sub-block, [C, dk] each; ``decay`` is exp(gamma_i - gamma_{i-r}) where
    row i - r is in i's sub-block and 0 elsewhere (no exponent above 0 is
    taken)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    # the row's place inside its sub-block, at the operands' own shape (a
    # [C, 1] mask would be broadcast along the lanes again every lag)
    valid = jax.lax.broadcasted_iota(jnp.int32, gamma.shape, 0) % _KSUB >= r
    shifted = lambda a: pltpu.roll(a, r, 0)
    diff = gamma - shifted(gamma)
    return jnp.where(valid, jnp.exp(jnp.where(valid, diff, 0.0)), 0.0), shifted(kf)


def _pair_setup(kf, gamma, m):
    """What the sub-blocks below the diagonal share, per head: ``left`` [C, dk]
    = exp(gamma - the sub-block's first row's) and ``F`` = [exp(first row of
    sub-block I - gamma) on the rows before I, 0 elsewhere, for I = 1 ..]."""
    import jax.numpy as jnp

    C, dk = gamma.shape
    firsts = [gamma[I * _KSUB:I * _KSUB + 1, :] for I in range(C // _KSUB)]
    ref = jnp.concatenate([jnp.broadcast_to(f, (_KSUB, dk)) for f in firsts], axis=0)
    F = []
    for I in range(1, C // _KSUB):
        before = m.row < I * _KSUB
        F.append(jnp.where(before, jnp.exp(jnp.where(before, firsts[I] - gamma, 0.0)), 0.0))
    return jnp.exp(gamma - ref), F


def _stack(x, m):
    """x [C, dk] -> [C, (C / SUB - 1) dk]: lane group I - 1 holds the rows of
    sub-block I and zeros elsewhere."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.where(m.sub == I, x, 0.0)
                            for I in range(1, CHUNK // _KSUB)], axis=1)


def _unstack(y, m):
    """[C, (C / SUB - 1) dk] -> [C, dk]: each row's own sub-block's group."""
    import jax.numpy as jnp

    dk = y.shape[1] // (CHUNK // _KSUB - 1)
    return sum(jnp.where(m.sub == I, y[:, (I - 1) * dk:I * dk], 0.0)
               for I in range(1, CHUNK // _KSUB))


def _pair_fwd(X, kf, gamma, m, prod):
    """The kernels' ``_pair_xla``, stage by stage over the heads: ``X`` a
    list (one entry a head) of lists of left operands [C, dk] float32 (kb;
    or kb and q), ``kf`` and ``gamma`` lists of [C, dk] float32 -> (a list a
    head of [C, C] matrices, one a left operand, unmasked above the diagonal
    of the diagonal sub-blocks only where nothing was written: zeros; and the
    namespace ``_pair_bwd`` needs)."""
    import types

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    C = CHUNK
    heads = range(len(kf))
    setup = _each(lambda k, g: _pair_setup(k, g, m), kf, gamma)
    left = [s[0] for s in setup]
    F = [s[1] for s in setup]
    right = _each(lambda k, f: jnp.concatenate([k * fi for fi in f], axis=1), kf, F)
    stacks = [[_stack(x * left[h], m) for x in X[h]] for h in heads]
    off = [[prod(s, right[h], _NT) for s in stacks[h]] for h in heads]

    def lag(r, diag):
        out = []
        for h in heads:
            decay, ks = _lag_decay(kf[h], gamma[h], r, m)
            d = decay * ks
            out.append(tuple(
                jnp.where(m.lag == r, jnp.sum(x * d, axis=1, keepdims=True), acc)
                for x, acc in zip(X[h], diag[h])))
        return tuple(out)

    zero = jnp.zeros((C, C), f32)
    diag = jax.lax.fori_loop(0, _KSUB, lag, tuple(tuple(zero for _ in X[h]) for h in heads))
    M = [[o + d for o, d in zip(off[h], diag[h])] for h in heads]
    return M, types.SimpleNamespace(left=left, F=F, right=right, stacks=stacks)


def _pair_bwd(dM, X, kf, gamma, kept, m, prod):
    """The cotangents of ``_pair_fwd``'s operands: ``dM`` a list a head of
    the matrices' cotangents (zero where the forward's were masked) -> (dX, a
    list a head of lists [C, dk]; dk_, a list a head [C, dk]). Gamma's is
    ``sum x dx - k dk`` of these (module docstring), the caller's."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    C = CHUNK
    heads = range(len(kf))
    dk_dim = kf[0].shape[1]
    from jax.experimental.pallas import tpu as pltpu

    # the sub-blocks below the diagonal: dL = dM R, dR = dM^T L
    dX_off = [[_unstack(prod(dm, kept.right[h]), m) * kept.left[h] for dm in dM[h]]
              for h in heads]
    dR = [sum(prod(dm, s, _TN) for dm, s in zip(dM[h], kept.stacks[h])) for h in heads]
    dk_off = [sum(dR[h][:, i * dk_dim:(i + 1) * dk_dim] * f
                  for i, f in enumerate(kept.F[h])) for h in heads]

    def lag(r, acc):
        out = []
        for h in heads:
            dX, dk_ = acc[h]
            decay, ks = _lag_decay(kf[h], gamma[h], r, m)
            d = decay * ks
            cols = [jnp.sum(jnp.where(m.lag == r, dm, 0.0), axis=1, keepdims=True)
                    for dm in dM[h]]
            y = sum(c * x for c, x in zip(cols, X[h])) * decay
            out.append((tuple(a + c * d for a, c in zip(dX, cols)),
                        dk_ + pltpu.roll(y, C - r, 0)))
        return tuple(out)

    zero = jnp.zeros((C, dk_dim), f32)
    diag = jax.lax.fori_loop(
        0, _KSUB, lag, tuple((tuple(zero for _ in X[h]), zero) for h in heads))
    dX = [[a + b for a, b in zip(dX_off[h], diag[h][0])] for h in heads]
    return dX, [dk_off[h] + diag[h][1] for h in heads]


def _within_chunk(q, k, v, g, brow, mxu):
    """Everything of one chunk that does not depend on the state, as the
    module docstring writes and rounds it, for several heads at once: lists
    (one entry a head) of q, k [C, dk], v [C, dv] in ``mxu``, g [C, dk]
    float32 and brow (beta) [1, C] float32 -> a list of namespaces. Stage by
    stage over the heads, as the scalar rule's."""
    import types

    import jax.numpy as jnp

    f32 = jnp.float32
    C = CHUNK
    prod, exact = _products(mxu)
    m = _chunk_masks()
    heads = range(len(q))
    col = lambda row: jnp.sum(jnp.where(m.eye, row, 0.0), axis=1, keepdims=True)
    bcol = _each(col, brow)
    ones = jnp.where(m.lower, 1.0, 0.0)
    gamma = _each(lambda g: exact(ones, g), g)                    # [C, dk]
    last = [x[C - 1:C, :] for x in gamma]                        # [1, dk]
    head = _each(jnp.exp, gamma)
    tail = _each(lambda l, x: jnp.exp(l - x), last, gamma)
    qf, kf, vf = ([x.astype(f32) for x in xs] for xs in (q, k, v))
    kb, vb = _each(jnp.multiply, kf, bcol), _each(jnp.multiply, vf, bcol)
    M, kept = _pair_fwd([[kb[h], qf[h]] for h in heads], kf, gamma, m, prod)
    kk = [jnp.where(m.strict, M[h][0], 0.0) for h in heads]
    qk = [jnp.where(m.lower, M[h][1], 0.0) for h in heads]
    T = _inverse(kk, m.eye, exact)
    kg = _each(jnp.multiply, kb, head)
    W = _each(lambda t, x: prod(t, x).astype(mxu), T, kg)
    U = _each(prod, T, vb)
    return [types.SimpleNamespace(
        m=m, ones=ones, pair=kept, gamma=gamma[h], qf=qf[h], kf=kf[h], vf=vf[h],
        bcol=bcol[h], head=head[h], tail=tail[h], carry=jnp.exp(last[h]),
        kb=kb[h], vb=vb[h], kg=kg[h], qk=qk[h], T=T[h], W=W[h], U=U[h],
        qg=(qf[h] * head[h]).astype(mxu), kt=(kf[h] * tail[h]).astype(mxu))
        for h in heads]


def _chunk_heads(q_ref, k_ref, v_ref, g_ref, beta_ref, G):
    """``_within_chunk`` of the G heads of a grid step's blocks."""
    heads = range(G)
    return _within_chunk(
        [q_ref[0, h] for h in heads],
        [k_ref[0, h] for h in heads],
        [v_ref[0, h] for h in heads],
        [g_ref[0, h] for h in heads],
        [beta_ref[0, 0, 0, h:h + 1, :] for h in heads], q_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, G, keep):
    """One chunk of G heads of one row; the grid's last axis walks the
    chunks in order and ``St`` [G, dv, dk] float32 carries each head's state,
    transposed, from one to the next in VMEM."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    St = rest[-1]
    mxu = q_ref.dtype
    prod, _ = _products(mxu)

    @pl.when(pl.program_id(2) == 0)
    def _():
        St[...] = jnp.zeros_like(St)

    xs = _chunk_heads(q_ref, k_ref, v_ref, g_ref, beta_ref, G)
    S0 = [St[h] for h in range(G)]
    if keep:
        for h in range(G):
            rest[0][0, h, 0] = S0[h]
    S_op = [s.astype(mxu) for s in S0]
    v_new = [x.U - prod(x.W, s, _NT) for x, s in zip(xs, S_op)]
    for h, x in enumerate(xs):
        St[h] = S0[h] * x.carry + prod(v_new[h], x.kt, _TN)
    for h, x in enumerate(xs):
        o_ref[0, h] = prod(x.qg, S_op[h], _NT) + prod(x.qk, v_new[h])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dSt, *, G):
    """The same chunk's gradients; the grid's last axis walks the chunks
    from the last to the first and ``dSt`` carries the (transposed) state's
    cotangent. The chunk's own matrices are computed again from q, k, v, g,
    beta and the kept state; casts pass a cotangent through unrounded,
    ``dKK = -T^T dT T^T`` as in the scalar rule, and dg is the reversed
    cumulative sum of dGamma, per channel."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    C = CHUNK
    mxu = q_ref.dtype
    prod, exact = _products(mxu)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dSt[...] = jnp.zeros_like(dSt)

    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)        # [C, 1]
    total = lambda a: jnp.sum(a, axis=0, keepdims=True)         # [1, n]
    heads = range(G)
    xs = _chunk_heads(q_ref, k_ref, v_ref, g_ref, beta_ref, G)
    m = xs[0].m
    do = [do_ref[0, h] for h in heads]
    S0, dS1 = [s0_ref[0, h, 0] for h in heads], [dSt[h] for h in heads]
    S_op = [s.astype(mxu) for s in S0]
    v_new = _each(lambda x, s: x.U - prod(x.W, s, _NT), xs, S_op)
    # o = qg S0 + qk v_new;  S1 = carry S0 + kt^T v_new  (states transposed)
    dv_new = _each(lambda x, do, ds: prod(x.qk, do, _TN) + prod(x.kt, ds, _NT),
                  xs, do, dS1)
    for h, x in enumerate(xs):
        dSt[h] = (dS1[h] * x.carry + prod(do[h], x.qg, _TN)
                  - prod(dv_new[h], x.W, _TN))
    dqk = _each(lambda do, vn: jnp.where(m.lower, prod(do, vn, _NT), 0.0), do, v_new)
    dqg = _each(prod, do, S_op)
    dkt = _each(prod, v_new, dS1)
    dcarry = _each(lambda ds, s: total(ds * s), dS1, S0)                 # [1, dk]
    # v_new = U - W S0;  W = T kg, U = T vb, T = (I + KK)^-1
    dW = _each(lambda dvn, s: -prod(dvn, s), dv_new, S_op)
    dT = _each(lambda x, dw, dvn: prod(dw, x.kg, _NT) + prod(dvn, x.vb, _NT),
              xs, dW, dv_new)
    dkg = _each(lambda x, dw: prod(x.T, dw, _TN), xs, dW)
    dvb = _each(lambda x, dvn: prod(x.T, dvn, _TN), xs, dv_new)
    dkk = _each(lambda x, dt: exact(dt, x.T, _NT), xs, dT)
    dkk = _each(lambda x, y: jnp.where(m.strict, -exact(x.T, y, _TN), 0.0), xs, dkk)
    pair, dk_pair = _pair_bwd(
        [[dkk[h], dqk[h]] for h in heads], [[x.kb, x.qf] for x in xs],
        [x.kf for x in xs], [x.gamma for x in xs], xs[0].pair, m, prod)
    at_last = m.row == C - 1
    row = lambda c: total(jnp.where(m.eye, c, 0.0))
    for h, x in enumerate(xs):
        dkb_pair, dq_pair = pair[h]
        dkb = dkb_pair + dkg[h] * x.head
        dq = dq_pair + dqg[h] * x.head
        dk_ = dk_pair[h] + dkt[h] * x.tail + dkb * x.bcol
        dq_ref[0, h] = dq.astype(dq_ref.dtype)
        dk_ref[0, h] = dk_.astype(dk_ref.dtype)
        dv_ref[0, h] = (dvb[h] * x.bcol).astype(dv_ref.dtype)
        dbeta_ref[0, 0, 0, h:h + 1, :] = row(rowsum(dkb * x.kf) + rowsum(dvb[h] * x.vf))
        dtail = dkt[h] * x.kf * x.tail                                   # [C, dk]
        dgamma = (x.kb * dkb_pair + x.qf * dq_pair - x.kf * dk_pair[h]
                  + (dkg[h] * x.kb + dqg[h] * x.qf) * x.head - dtail
                  + jnp.where(at_last, total(dtail) + dcarry[h] * x.carry, 0.0))
        # g's cotangent: dg_t = sum_{i >= t} dGamma_i
        dg_ref[0, h] = exact(x.ones, dgamma, _TN)



# ----------------------------------------------------------------------
# The mixer's prologue: from the projection to what the rule takes
# ----------------------------------------------------------------------

# Heads a grid step of the prologue's kernels takes at most (of ONE of q, k,
# v: the step's block of ``qkv`` is that many heads' lanes side by side, and
# the kernel bodies hold that many segments unrolled). Alone at
# [1, 16384, 12288] a forward / backward launch read 3.55 / 6.24 ms at one
# head a step, 2.58 / 4.32 at two, 2.05 / 3.43 at four, 1.85 / 3.25 at eight
# (my chip run, PR 68): eight would take 2.3 ms more off the cell's step and
# cost it twice four's seconds of ``setup_s`` (the bodies are lowered anew for
# every program of a run that holds them: ~3.5 s at four)
_PROLOGUE_HEADS = 4


def _prologue_heads(heads: int, dk: int) -> int:
    """G, the heads a grid step of the prologue's kernels takes: the most
    that divide ``heads``, up to ``_PROLOGUE_HEADS`` and to the widest block
    ``gated_delta``'s kernels were run at (``_GROUP_LANES``); 0 where one
    head is wider than that."""
    fit = min(_PROLOGUE_HEADS, _GROUP_LANES // dk)
    return max((G for G in range(1, fit + 1) if heads % G == 0), default=0)


def prologue_route(qkv, conv_w, dk: int, dv: int) -> str:
    """Which form :func:`kda_prologue` runs, from what it can observe, as
    ``gated_delta.prologue_route`` does for the scalar rule's mixer: "pallas"
    on a TPU backend at an eligible shape, "interpret" at such a shape under
    ``SXT_FUSED_INTERPRET=1``, else "xla". Eligible: q, k and v heads of one
    width that is whole lane tiles (a grid step takes the same block of
    lanes out of each of the three column ranges; the rule's kernels ask
    for whole tiles too) and no wider than a step takes, ``qkv`` and
    ``conv_w`` of the same 2 H dk + H dv columns, a convolution no wider than
    one 8-row sublane tile, bf16 or float32 activations."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    heads = conv_w.shape[1] // (2 * dk + dv)
    eligible = (dk == dv and dk % 128 == 0 and heads > 0
                and qkv.shape[-1] == conv_w.shape[1] == heads * (2 * dk + dv)
                and _prologue_heads(heads, dk) > 0
                and conv_w.shape[0] <= 8
                and qkv.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def kda_prologue(qkv, conv_w, heads: int, dk: int, dv: int, eps: float = 1e-6,
                 rows: int = ROWS):
    """Everything of a KDA layer between its projection and the rule:
    ``qkv`` [B, T, 2 H dk + H dv] as the projection wrote it (all q | all k |
    all v, a head's channels side by side inside each) and ``conv_w``
    [K, 2 H dk + H dv] over the same columns -> q, k [B, T, H, dk] and v
    [B, T, H, dv] in ``qkv``'s dtype: the causal depthwise convolution
    without bias, SiLU, the l2 norm of q (times ``dk ** -0.5``) and of k.

    Two bodies, chosen by :func:`prologue_route`. The kernels are
    ``gated_delta.gdn_prologue``'s, told that the segments lie in three
    column ranges (``_prologue_core(..., parts=3)``; launches
    ``kda_prologue_fwd`` / ``kda_prologue_bwd`` behind one
    ``jax.custom_vjp`` whose residuals are its inputs): the grid is (row of
    the batch, group of G heads, block of ``rows`` rows, part), the part
    innermost, and a step reads the [rows, G d] block of ITS part of ``qkv``
    where the projection wrote it (and the 16 rows before it), so ``qkv`` is
    read once forward and once more, with the three cotangents, backward,
    and d``qkv`` is written once. The convolution's accumulator, SiLU, the
    sum of squares and the rsqrt are float32 and the result is rounded to
    the compute dtype ONCE, at the write. They write q, k, v as [B, H, T, d],
    which is what the rule's kernels read: the transpose back to
    [B, T, H, d] here and the rule's own to [B, H, T, d] cancel in XLA.
    The XLA body is ``silu(causal_conv1d)`` (float32 to one rounding, as
    ``ops/ssm_conv.py``'s) -> split -> ``l2norm`` (rounded again): the
    off-TPU path and the kernels' oracle."""
    route = prologue_route(qkv, conv_w, dk, dv)
    if route == "xla":
        return _kda_prologue_xla(qkv, conv_w, heads, dk, dv, eps)
    return _kda_prologue_pallas(qkv, conv_w, heads, dk, dv, eps, rows,
                                interpret=route == "interpret")


def _kda_prologue_xla(qkv, conv_w, H, dk, dv, eps=1e-6):
    """``kda_prologue`` as XLA ops."""
    import jax
    import jax.numpy as jnp

    B, T, _ = qkv.shape
    mixed = jax.nn.silu(causal_conv1d(qkv.astype(jnp.float32), conv_w)).astype(qkv.dtype)
    q, k, v = jnp.split(mixed, [H * dk, 2 * H * dk], axis=-1)
    q = (l2norm(q.reshape(B, T, H, dk), eps) * dk ** -0.5).astype(qkv.dtype)
    k = l2norm(k.reshape(B, T, H, dk), eps).astype(qkv.dtype)
    return q, k, v.reshape(B, T, H, dv)


def _kda_prologue_pallas(qkv, conv_w, H, dk, dv, eps=1e-6, rows=ROWS,
                         interpret: bool = False):
    """``kda_prologue`` through the kernels, G heads a grid step
    (``_prologue_heads``). ``conv_w`` goes in as [3 H / G, 8, G d] float32:
    the columns as they are, a group of G heads of one part a row, K padded
    to a sublane tile. T is padded to whole blocks of rows with zeros
    (nothing where ``rows`` divides it); the padding and the transposes back
    to [B, T, H, d] are XLA's, and so are their gradients."""
    import jax.numpy as jnp

    T, K = qkv.shape[1], conv_w.shape[0]
    G = _prologue_heads(H, dk)
    assert G and dk == dv and rows % _SUB == 0, (H, dk, dv, rows)
    w = jnp.pad(conv_w.astype(jnp.float32), ((0, 8 - K), (0, 0)))
    w = jnp.swapaxes(w.reshape(8, 3 * H // G, G * dk), 0, 1)
    R = min(rows, -(-T // _SUB) * _SUB)
    x = jnp.pad(qkv, ((0, 0), (0, -T % R), (0, 0)))
    core = _prologue_core(K, dk, dv, 1, float(eps), R, interpret, parts=3)
    return tuple(jnp.swapaxes(a[:, :, :T], 1, 2) for a in core(x, w))
