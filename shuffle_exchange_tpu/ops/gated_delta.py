"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; the linear-attention
mixer of Qwen3-Next) and what surrounds it in a layer: the causal depthwise
convolution and the l2 norm of q and k, and ``gdn_prologue``, which takes a
layer from its projection to the rule's q, k, v in one pass (the end of this
file).

Per head, with a state ``S`` [dk, dv] that starts at 0, a log-decay ``g_t`` <= 0
and a write strength ``beta_t`` in [0, 1]::

    S   <- exp(g_t) * S
    u_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``gated_delta_recurrent`` is that, one token at a time: the plain form, kept
for the tests (the trainer never runs it). ``gated_delta_chunked`` is the form
that trains: chunks of ``chunk`` tokens, everything inside a chunk as matrix
products, and a walk over the chunks that carries ``S``. It has two bodies
with the same arithmetic, chosen by what the code can observe
(``kernel_route``: backend and shape, as ``ops/dispatch.py`` states it):

  Pallas kernels  on a TPU backend where dk and dv are whole lane tiles (128),
                  ``chunk`` is ``CHUNK`` and q, k, v are all bf16 or all
                  float32: ``gdn_rule_fwd`` / ``gdn_rule_fwd_keep`` /
                  ``gdn_rule_bwd`` behind one ``jax.custom_vjp``. The CPU
                  suite drives the same kernels through the interpreter
                  (``SXT_FUSED_INTERPRET=1``). Selected, they run or raise.
                  Heads that are nearly whole tiles (96 / 192, Olmo
                  Hybrid's) take the same kernels on zero-padded lanes
                  (route "pallas_padded"; the pads and o's slice are XLA's).
  XLA ops         everywhere else (the CPU, the 8-device CPU mesh, narrow
                  heads, other chunk sizes): einsums and a ``lax.scan`` over
                  the chunks. The off-TPU path, and the kernels' oracle
                  beside the recurrence.

The chunked form, for one chunk of C tokens with ``gamma_i = sum_{t<=i} g_t``
(cumulative inside the chunk) and ``S0`` the state at the chunk's start::

    A      = strictly_lower((K_beta K^T) * exp(gamma_i - gamma_j))   [C, C]
    T      = (I + A)^-1 = (I - A)(I + A^2)(I + A^4)...               (A^C = 0)
    W      = T (K_beta * exp(gamma))                                  [C, dk]
    U      = T V_beta                                                 [C, dv]
    V_new  = U - W S0                                                 [C, dv]
    O      = (Q * exp(gamma)) S0 + lower((Q K^T) * exp(gamma_i - gamma_j)) V_new
    S1     = exp(gamma_C) S0 + (K * exp(gamma_C - gamma))^T V_new

Every exponent is <= 0, so nothing overflows however strong the decay.

What is rounded where (``mxu`` = the dtype of q, k and v as they arrive:
bf16 in a bf16 trainer, float32 in the float32 tests, where the two forms
then agree to float32 rounding):
  float32   g, beta, gamma and every decay factor; K_beta, V_beta and the
            other elementwise products; the state S as the scan carries it;
            A, the powers of A and T (those products at HIGHEST precision:
            squaring a rounded A five times would compound the rounding);
            every product's accumulator and result
  ``mxu``   the two operands of every other product: K_beta K^T, Q K^T,
            T with its right-hand sides, W S0, (Q e^gamma) S0, the masked
            scores with V_new, and K^T V_new. S is ROUNDED to ``mxu`` as an
            operand and carried unrounded.

Operations the chunked form REQUIRES per head and chunk (2 x m x n x k per
product; what ``chipbench/arith_hybrid.py`` counts, forward): K_beta K^T and
Q K^T 2 x 2 C^2 dk; T's ten C^3 products (five squarings, five factors);
W and U 2 C^2 (dk + dv); W S0, (Q e^gamma) S0 and K^T V_new 3 x 2 C dk dv;
scores x V_new 2 C^2 dv. The kernels take T in six products, not ten (see
``_inverse``); the count above is the yardstick's and stays.

What the kernels keep where. A grid step is one chunk of ``_HEADS_A_STEP``
heads of one row; the grid's last axis walks the chunks in order and S
[dk, dv] float32 stays in a VMEM scratch from one step to the next. Every
[C, C] matrix (the decay, A, its powers, T, the scores), K_beta, V_beta, W,
U and V_new live in VMEM only. HBM sees q, k, v as [B, H, T, d] (XLA's
transpose of the mixer's [B, T, H, d], which it folds into the producing
fusion), gamma and beta as rows of C numbers, and o. The forward that a
backward follows (``gdn_rule_fwd_keep``) also writes each chunk's starting
state S0 [B, H, N, dk, dv] float32 (537 MB a layer at 2 x 8,192 tokens x 32
heads), which is all the backward needs beside the inputs; the pass of a
``jax.checkpoint`` that keeps nothing runs ``gdn_rule_fwd``, which writes o
alone. The backward kernel walks the chunks from the last to the first with
dS in VMEM, computes the chunk's own matrices AGAIN from q, k, v, gamma,
beta and S0 (T included: kept, 134 MB a layer of [C, C] float32 would go
through HBM to save 2.2 ms of a layer's backward; measured and left out,
PERF.md PR 34), passes cotangents through the casts unrounded, and takes
``dA = -T^T dT T^T``. The XLA form's backward is autodiff through all of it
(about twice the forward) except T's, which is that same formula; its two
parts that are parallel over chunks are computed again under
``jax.checkpoint``, its scan is not.
"""

from __future__ import annotations

import functools
import math

# Tokens a chunk of the chunked form: 64, where T = (I + A)^-1 is six products
# of 64 x 64 and the scan over chunks has 128 trips at 8k tokens (ISSUE 33
# fixed it; nothing else was measured). The tests pass smaller ones.
CHUNK = 64


def l2norm(x, eps: float = 1e-6):
    """x * rsqrt(sum(x^2) + eps) over the last axis, in float32."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)


def causal_conv1d(x, w, bias=None):
    """Depthwise causal convolution: the oracle, and the XLA form, of the
    DeltaNet prologue (``_gdn_prologue_xla``), of the gated short convolution
    (``short_conv.sconv_mix``) and, since PR 47, of the state-space mixer's
    convolution (``ssm_conv._ssm_conv_xla``: its off-TPU path, NOT its TPU
    path, which is ``ops/ssm_conv.py``'s kernels). x [B, T, C], w [K, C]:
    ``y[t] = sum_j w[j] * x[t - (K - 1) + j]``, x zero before position 0
    (torch's ``Conv1d(C, C, K, groups=C, padding=K-1)`` cut to T outputs),
    plus ``bias`` [C] where given. Accumulates in float32; returns x's dtype."""
    import jax.numpy as jnp

    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    y = sum(xp[:, j:j + T] * w32[j] for j in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def gated_delta_recurrent(q, k, v, g, beta):
    """The rule as written in the module docstring, a ``lax.scan`` over
    tokens, float32 at HIGHEST matmul precision. q, k [B, T, H, dk] (already
    normalised and scaled), v [B, T, H, dv], g, beta [B, T, H] ->
    o [B, T, H, dv] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1)


def _unit_lower_inverse(A):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular, as the product
    (I - A)(I + A^2)(I + A^4)... (A is nilpotent: A^C = 0), float32 at
    HIGHEST precision: matrix products only, so the MXU does it. Its
    gradient is ``dA = -T^T dT T^T`` from the result T alone (two products
    and one saved matrix, where autodiff through the ten products of the
    forward would keep and revisit every power)."""
    import jax
    import jax.numpy as jnp

    mm = lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def forward(A):
        C = A.shape[-1]
        P = -A
        T = jnp.eye(C, dtype=A.dtype) + P
        power = 1
        while 2 * power < C:
            P = mm(P, P)
            T = T + mm(T, P)
            power *= 2
        return T

    inverse = jax.custom_vjp(forward)

    def bwd(T, dT):
        Tt = jnp.swapaxes(T, -1, -2)
        return (-mm(Tt, mm(dT, Tt)),)

    def fwd(A):
        T = forward(A)
        return T, T

    inverse.defvjp(fwd, bwd)
    return inverse(A)


def _whole_chunks(q, k, v, g, beta, chunk):
    """The five padded along T to a multiple of ``chunk`` with tokens that
    write nothing (beta 0, g 0)."""
    import jax.numpy as jnp

    pad = -q.shape[1] % chunk
    if not pad:
        return q, k, v, g, beta
    tail = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return tuple(tail(a) for a in (q, k, v, g, beta))


def gated_delta_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The same function as :func:`gated_delta_recurrent` in the chunked
    (matrix-product) form of the module docstring; differentiable (the
    kernels' own backward where :func:`kernel_route` chooses them, else
    autodiff through the products and the scan over chunks). Same shapes;
    o [B, T, H, dv] float32. T need not divide by ``chunk``: the tail is
    padded with tokens that write nothing (beta 0, g 0) and cut off."""
    import jax
    import jax.numpy as jnp

    route = kernel_route(q, k, v, chunk)
    if route != "xla":
        return _gated_delta_pallas(q, k, v, g, beta,
                                   interpret=route.startswith("interpret"))
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

    # [B, T, H, d] -> [B, H, N, C, d]; g, beta -> [B, H, N, C]
    chunks4 = lambda a: a.reshape(B, N, C, H, -1).transpose(0, 3, 1, 2, 4)
    chunks3 = lambda a: a.astype(f32).reshape(B, N, C, H).transpose(0, 3, 1, 2)
    q, k, v = chunks4(q), chunks4(k), chunks4(v)
    g, beta = chunks3(g), chunks3(beta)
    row = jnp.arange(C)[:, None]
    col = jnp.arange(C)[None, :]
    lower = row >= col

    def decays(g):
        """gamma [B,H,N,C] and exp(gamma_i - gamma_j) on and below the
        diagonal. exp only of the exponents that are used (<= 0): the others
        would overflow, and an inf times 0 in the backward is a NaN."""
        gamma = jnp.cumsum(g, axis=-1)
        diff = gamma[..., :, None] - gamma[..., None, :]
        return gamma, jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    # The two parts that are parallel over chunks keep nothing for the
    # backward but their inputs (jax.checkpoint): their [C, C] float32
    # matrices are most of what the rule would otherwise hold (2 GB of a
    # DeltaNet layer's 5 at 16,384 tokens, PERF.md PR 33) and are cheap to
    # compute again, unlike the sequential scan between them.
    @jax.checkpoint
    def within_chunks(k, v, g, beta):
        gamma, decay = decays(g)
        k_beta = k.astype(f32) * beta[..., None]
        v_beta = v.astype(f32) * beta[..., None]
        A = jnp.where(row > col, prod("bhnid,bhnjd->bhnij", k_beta, k) * decay, 0.0)
        Tm = _unit_lower_inverse(A)
        W = prod("bhnij,bhnjd->bhnid", Tm, k_beta * jnp.exp(gamma)[..., None])
        U = prod("bhnij,bhnjd->bhnid", Tm, v_beta)
        last = gamma[..., -1]                                   # [B,H,N]
        k_tail = k.astype(f32) * jnp.exp(last[..., None] - gamma)[..., None]
        return W.astype(mxu), U, k_tail.astype(mxu), jnp.exp(last)

    @jax.checkpoint
    def outputs(q, k, g, v_new, inter):
        _, decay = decays(g)
        scores = prod("bhnid,bhnjd->bhnij", q, k) * decay
        return inter + prod("bhnij,bhnjd->bhnid", scores, v_new)

    def body(S, x):
        W_i, U_i, k_i, q_i, decay_i = x
        S_op = S.astype(mxu)
        v_new = U_i - prod("bhck,bhkv->bhcv", W_i, S_op)
        inter = prod("bhck,bhkv->bhcv", q_i, S_op)
        S = S * decay_i[..., None, None] + prod("bhck,bhcv->bhkv", k_i, v_new)
        return S, (v_new, inter)

    W, U, k_tail, decay_last = within_chunks(k, v, g, beta)
    q_head = (q.astype(f32) * jnp.exp(jnp.cumsum(g, axis=-1))[..., None]).astype(mxu)
    lead = lambda a: jnp.moveaxis(a, 2, 0)                      # N first
    _, (v_new, inter) = jax.lax.scan(
        body, jnp.zeros((B, H, dk, dv), f32),
        (lead(W), lead(U), lead(k_tail), lead(q_head), lead(decay_last)))
    o = outputs(q, k, g, jnp.moveaxis(v_new, 0, 2), jnp.moveaxis(inter, 0, 2))
    return o.transpose(0, 2, 3, 1, 4).reshape(B, N * C, H, dv)[:, :T]


# ----------------------------------------------------------------------
# The chunked form as Pallas kernels (what a TPU runs)
# ----------------------------------------------------------------------

# (row, head) pairs a grid step: their chains of small products are
# independent, so the MXU works on one while another's result drains
_HEADS_A_STEP = 8
# what a grid step takes where the heads do not divide by that: the first
# that divides them (30 heads: 6)
_HEAD_GROUPS = (_HEADS_A_STEP, 6, 4, 2, 1)


def kernel_route(q, k, v, chunk: int = CHUNK) -> str:
    """Which form ``gated_delta_chunked`` runs, from what it can observe:
    "pallas" on a TPU backend (``ops/dispatch.pallas_enabled``) at an
    eligible shape (dk and dv whole lane tiles, chunk ``CHUNK``, q, k and v
    all bf16 or all float32), "interpret" at such a shape under
    ``SXT_FUSED_INTERPRET=1`` (the CPU suite's way to the same kernels),
    "pallas_padded" / "interpret_padded" where dk or dv is not whole tiles
    but pads to them cheaply (``_pads_cheaply``: the same kernels on
    zero-padded lanes), else "xla"."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    eligible = (chunk == CHUNK and q.shape[-1] % 128 == 0
                and v.shape[-1] % 128 == 0 and q.dtype == k.dtype == v.dtype
                and q.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        # heads that are not whole lane tiles but nearly (Olmo Hybrid's 96 /
        # 192: 0.75 and 1.5 tiles): the same kernels on zero-padded lanes
        padded = (chunk == CHUNK and _pads_cheaply(q.shape[-1])
                  and _pads_cheaply(v.shape[-1]) and q.dtype == k.dtype == v.dtype
                  and q.dtype in (jnp.bfloat16, jnp.float32))
        if not padded:
            return "xla"
        if interpret_forced():
            return "interpret_padded"
        return "pallas_padded" if pallas_enabled() else "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def _lane_tiles(d: int) -> int:
    """``d`` rounded up to whole lane tiles (128)."""
    return -(-d // 128) * 128


def _pads_cheaply(d: int) -> bool:
    """A head width the kernels take on zero-padded lanes: whole tiles as
    they are, else at most half as many lanes again (96 -> 128, 192 -> 256;
    not the tests' 16 -> 128, which stays XLA's)."""
    return 2 * _lane_tiles(d) <= 3 * d


def _gated_delta_pallas(q, k, v, g, beta, interpret: bool = False):
    """``gated_delta_chunked`` through the kernels. q, k and v go in as
    [B, H, T, d] and o comes out so (read as [B, T, H * d] lane blocks they
    cost a relayout XLA does not fold away: 74 ms a step in
    ``qwen3next-train``, PERF.md PR 34); gamma (the cumulative log-decay
    inside each chunk) and beta go in as [B, H / G, N, G, C], a row of C
    numbers a chunk and head. The padding, the cumulative sum and the
    transposes are XLA's, and so are their gradients."""
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    if dk % 128 or dv % 128:
        # route "pallas_padded": zero lanes up to whole tiles, AFTER the l2
        # norm (the caller's), add nothing to any product (K K^T, Q K^T, the
        # rows and columns of S they would write stay 0) and o's are cut off.
        # Exact, and the pad and slice passes are the rule's own cost
        lanes = lambda a, d: jnp.pad(a, ((0, 0),) * 3 + ((0, _lane_tiles(d) - d),))
        o = _gated_delta_pallas(lanes(q, dk), lanes(k, dk), lanes(v, dv), g, beta,
                                interpret)
        return o[..., :dv]
    C = CHUNK
    q, k, v, g, beta = _whole_chunks(q, k, v, g, beta, C)
    N = q.shape[1] // C
    G = next(n for n in _HEAD_GROUPS if H % n == 0)
    rows = lambda a: a.reshape(B, N, C, H // G, G).transpose(0, 3, 1, 4, 2)
    gamma = jnp.cumsum(g.astype(f32).reshape(B, N, C, H), axis=2)
    wide = lambda a: jnp.swapaxes(a, 1, 2)                      # [B, H, T, d]
    o = _delta_core(G, interpret)(wide(q), wide(k), wide(v), rows(gamma),
                                  rows(beta.astype(f32)))
    return jnp.swapaxes(o, 1, 2)[:, :T]


@functools.lru_cache(maxsize=None)
def _delta_core(G: int, interpret: bool):
    """The rule on whole chunks as one ``jax.custom_vjp``: (q, k, v
    [B, H, T, d], gamma, beta [B, H / G, N, G, C]) -> o [B, H, T, dv]
    float32. Undifferentiated (and in the pass of a ``jax.checkpoint`` that
    keeps nothing) the forward kernel writes o alone; differentiated it also
    writes each chunk's starting state S0 [B, H, N, dk, dv] float32, all the
    backward kernel needs beside the inputs."""
    import jax

    # each launch under its own jit, built once: a program that calls the
    # rule in several layers then traces and lowers each kernel once, not
    # once a layer (0.8 s a layer and lowering of the train step otherwise:
    # 9 s of ``setup_s`` in ``qwen3next-train``, PERF.md PR 34)
    launch = lambda fn, **static: jax.jit(functools.partial(
        fn, G=G, interpret=interpret, **static))
    forward, forward_keep = (launch(_forward, keep=keep) for keep in (False, True))
    backward = launch(_backward)

    @jax.custom_vjp
    def core(q, k, v, gamma, beta):
        return forward(q, k, v, gamma, beta)[0]

    def fwd(q, k, v, gamma, beta):
        o, s0 = forward_keep(q, k, v, gamma, beta)
        return o, (q, k, v, gamma, beta, s0)

    def bwd(kept, do):
        return backward(*kept, do)

    # optimize_remat: the pass of a jax.checkpoint that keeps nothing runs
    # ``core`` (o alone), not ``fwd`` with its S0 thrown away
    core.defvjp(fwd, bwd, optimize_remat=True)
    return core


def _blocks(G, chunk_at):
    """The block specs of a grid step (row b, head group h, step n) that
    works on chunk ``chunk_at(n)``: ``wide(d)`` for q, k, v, o [B, H, T, d],
    ``flat`` for gamma and beta [B, H / G, N, G, C], ``state(dk, dv)`` for S0
    [B, H, N, dk, dv]."""
    from jax.experimental import pallas as pl

    C = CHUNK
    wide = lambda d: pl.BlockSpec((1, G, C, d), lambda b, h, n: (b, h, chunk_at(n), 0))
    flat = pl.BlockSpec((1, 1, 1, G, C), lambda b, h, n: (b, h, chunk_at(n), 0, 0))
    state = lambda dk, dv: pl.BlockSpec(
        (1, G, 1, dk, dv), lambda b, h, n: (b, h, chunk_at(n), 0, 0))
    return wide, flat, state


def _compiler_params(axes: int = 3):
    """The launches' parameters: a grid of ``axes`` axes, the first two
    parallel and every later one walked in order."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") + ("arbitrary",) * (axes - 2),
        vmem_limit_bytes=64 * 1024 * 1024)


def _forward(q, k, v, gamma, beta, G, interpret, keep):
    """The forward kernel's launch -> [o], or [o, S0] where ``keep``."""
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
        out_shape.append(jax.ShapeDtypeStruct((B, H, N, dk, dv), f32))
        out_specs.append(state(dk, dv))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, G=G, keep=keep),
        grid=(B, H // G, N),
        in_specs=[wide(dk), wide(dk), wide(dv), flat, flat],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, dk, dv), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdn_rule_fwd_keep" if keep else "gdn_rule_fwd",
    )(q, k, v, gamma, beta)


def _backward(q, k, v, gamma, beta, s0, do, G, interpret):
    """The backward kernel's launch -> [dq, dk, dv, dgamma, dbeta]."""
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
        in_specs=[wide(dk), wide(dk), wide(dv), flat, flat, state(dk, dv), wide(dv)],
        out_specs=[wide(dk), wide(dk), wide(dv), flat, flat],
        out_shape=[like(q), like(k), like(v), like(gamma), like(beta)],
        scratch_shapes=[pltpu.VMEM((G, dk, dv), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdn_rule_bwd",
    )(q, k, v, gamma, beta, s0, do.astype(f32))


def _each(fn, *lists):
    """[fn(a, b, ...) for a, b, ... in zip(*lists)]: one stage over the heads."""
    return [fn(*xs) for xs in zip(*lists)]


_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _products(mxu):
    """(prod, exact), each over one of the three contractions above. ``prod``:
    operands rounded to ``mxu``, a float32 accumulator. ``exact``: float32
    operands at float32 accuracy, which is what the inverse takes (and every
    product where ``mxu`` is float32 itself): what ``Precision.HIGHEST`` is
    on a TPU, six bf16 products of the operands' three bf16 parts (hi hi,
    hi mid, mid hi, mid mid, hi lo, lo hi; float32 accumulator), written out
    as ONE product over the six parts laid side by side along the
    contraction, so the MXU takes packed bf16 rows and adds the six up
    itself. (Mosaic's own float32 product pushes float32 rows part by part
    and pops six results: 3.4 times the MXU instructions, and those are
    what the forward kernel waits for.)"""
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16

    def parts(x):
        hi = x.astype(bf16)
        rest = x - hi.astype(f32)
        mid = rest.astype(bf16)
        return hi, mid, (rest - mid.astype(f32)).astype(bf16)

    def exact(a, b, dims=_NN):
        (along_a,), (along_b,) = dims[0]
        (ah, am, al), (bh, bm, bl) = parts(a), parts(b)
        return jax.lax.dot_general(
            jnp.concatenate([ah, am, ah, am, ah, al], axis=along_a),
            jnp.concatenate([bh, bh, bm, bm, bl, bh], axis=along_b),
            dims, preferred_element_type=f32)

    def prod(a, b, dims=_NN):
        return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                                   preferred_element_type=f32)

    return (exact if mxu == f32 else prod), exact


def _inverse(A, eye, exact):
    """[(I + A)^-1 for A in ``A``], A [C, C] float32 strictly lower
    triangular, ``eye`` the [C, C] diagonal mask: ...(I + A^4)(I + A^2)(I - A).
    The factors commute, and taken from the left P^2 and P T share P, so one
    product of P with [T | P] (128 lanes, the MXU's width) gives both: six
    products where the XLA form's order takes ten."""
    import jax.numpy as jnp

    C = A[0].shape[-1]
    P = [-a for a in A]
    T = [jnp.where(eye, 1.0, 0.0) + p for p in P]
    P = _each(exact, P, P)
    power = 2
    while 2 * power < C:
        R = _each(lambda p, t: exact(p, jnp.concatenate([t, p], axis=1)), P, T)
        T = _each(lambda t, r: t + r[:, :C], T, R)
        P = [r[:, C:] for r in R]
        power *= 2
    return _each(lambda t, p: t + exact(p, t), T, P)


def _within_chunk(q, k, v, grow, brow, mxu):
    """Everything of one chunk that does not depend on the state, as the
    module docstring writes and rounds it, for several heads at once: lists
    (one entry a head) of q, k [C, dk], v [C, dv] in ``mxu`` and grow
    (gamma), brow (beta) [1, C] float32 -> a list of namespaces. Written
    stage by stage over the heads, not head by head: the heads' chains of
    small products are independent, and side by side in the program they
    overlap on the MXUs. All of it stays in VMEM."""
    import types

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    C = CHUNK
    prod, exact = _products(mxu)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye, lower, strict = i == j, i >= j, i > j
    heads = range(len(q))
    # a row [1, C] as a column [C, 1]: through the diagonal
    col = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)
    gcol, bcol = _each(col, grow), _each(col, brow)
    last = [g[:, C - 1:C] for g in grow]                        # [1, 1]
    # exp only of the exponents that are used (<= 0), as in the XLA form
    decay = _each(lambda gc, gr: jnp.where(
        lower, jnp.exp(jnp.where(lower, gc - gr, 0.0)), 0.0), gcol, grow)
    head = _each(jnp.exp, gcol)                                  # [C, 1]
    tail = _each(lambda l, gc: jnp.exp(l - gc), last, gcol)
    qf, kf, vf = ([x.astype(f32) for x in xs] for xs in (q, k, v))
    kb, vb = _each(jnp.multiply, kf, bcol), _each(jnp.multiply, vf, bcol)
    kk = _each(lambda a, b: prod(a, b, _NT), kb, k)
    qk = _each(lambda a, b: prod(a, b, _NT), q, k)
    T = _inverse(_each(lambda kk, d: jnp.where(strict, kk * d, 0.0), kk, decay),
                 eye, exact)
    kg = _each(jnp.multiply, kb, head)
    W = _each(lambda t, x: prod(t, x).astype(mxu), T, kg)
    U = _each(prod, T, vb)
    return [types.SimpleNamespace(
        eye=eye, strict=strict, qf=qf[h], kf=kf[h], vf=vf[h], bcol=bcol[h],
        head=head[h], tail=tail[h], carry=jnp.exp(last[h]), decay=decay[h],
        # [1, 1] -> [dk, dv] in two steps with the exp between them: Mosaic
        # broadcasts along one of sublanes and lanes at a time
        carry_col=jnp.exp(jnp.broadcast_to(last[h], (k[h].shape[1], 1))),
        kb=kb[h], vb=vb[h], kg=kg[h], kk=kk[h], qk=qk[h], T=T[h], W=W[h],
        U=U[h], qg=(qf[h] * head[h]).astype(mxu),
        kt=(kf[h] * tail[h]).astype(mxu)) for h in heads]


def _chunk_heads(q_ref, k_ref, v_ref, gamma_ref, beta_ref, G):
    """``_within_chunk`` of the G heads of a grid step's blocks."""
    heads = range(G)
    return _within_chunk(
        [q_ref[0, h] for h in heads],
        [k_ref[0, h] for h in heads],
        [v_ref[0, h] for h in heads],
        [gamma_ref[0, 0, 0, h:h + 1, :] for h in heads],
        [beta_ref[0, 0, 0, h:h + 1, :] for h in heads], q_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, o_ref, *rest,
                G, keep):
    """One chunk of G heads of one row; the grid's last axis walks the
    chunks in order and ``S`` [G, dk, dv] float32 carries each head's state
    from one to the next in VMEM."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    S = rest[-1]
    mxu = q_ref.dtype
    prod, _ = _products(mxu)

    @pl.when(pl.program_id(2) == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    xs = _chunk_heads(q_ref, k_ref, v_ref, gamma_ref, beta_ref, G)
    S0 = [S[h] for h in range(G)]
    if keep:
        for h in range(G):
            rest[0][0, h, 0] = S0[h]
    S_op = [s.astype(mxu) for s in S0]
    v_new = [x.U - prod(x.W, s) for x, s in zip(xs, S_op)]
    for h, x in enumerate(xs):
        S[h] = S0[h] * x.carry_col + prod(x.kt, v_new[h], _TN)
    for h, x in enumerate(xs):
        o_ref[0, h] = prod(x.qg, S_op[h]) + prod(x.qk * x.decay, v_new[h])


def _bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref, dS, *, G):
    """The same chunk's gradients; the grid's last axis walks the chunks
    from the last to the first and ``dS`` carries the state's cotangent. The
    chunk's own matrices are computed again from q, k, v, gamma, beta and
    the kept S0; casts pass a cotangent through unrounded, and
    ``dA = -T^T dT T^T`` as in the XLA form."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    C = CHUNK
    mxu = q_ref.dtype
    prod, exact = _products(mxu)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS[...] = jnp.zeros_like(dS)

    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)        # [C, 1]
    total = lambda a: jnp.sum(a, axis=0, keepdims=True)         # [1, n]
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    heads = range(G)
    xs = _chunk_heads(q_ref, k_ref, v_ref, gamma_ref, beta_ref, G)
    q = [q_ref[0, h] for h in heads]
    k = [k_ref[0, h] for h in heads]
    do = [do_ref[0, h] for h in heads]
    S0, dS1 = [s0_ref[0, h, 0] for h in heads], [dS[h] for h in heads]
    # stage by stage over the heads, as in ``_within_chunk``
    S_op = [s.astype(mxu) for s in S0]
    v_new = _each(lambda x, s: x.U - prod(x.W, s), xs, S_op)
    # o = qg S0 + scores v_new;  S1 = carry S0 + kt^T v_new
    dv_new = _each(lambda x, do, ds: prod(x.qk * x.decay, do, _TN) + prod(x.kt, ds),
                  xs, do, dS1)
    for h, x in enumerate(xs):
        dS[h] = (dS1[h] * x.carry_col + prod(x.qg, do[h], _TN)
                 - prod(x.W, dv_new[h], _TN))
    dscores = _each(lambda do, vn: prod(do, vn, _NT), do, v_new)
    dqg = _each(lambda do, s: prod(do, s, _NT), do, S_op)
    dkt = _each(lambda vn, ds: prod(vn, ds, _NT), v_new, dS1)
    dcarry = _each(lambda ds, s: total(rowsum(ds * s)), dS1, S0)         # [1, 1]
    # v_new = U - W S0;  W = T kg, U = T vb, T = (I + A)^-1
    dW = _each(lambda dvn, s: -prod(dvn, s, _NT), dv_new, S_op)
    dT = _each(lambda x, dw, dvn: prod(dw, x.kg, _NT) + prod(dvn, x.vb, _NT),
              xs, dW, dv_new)
    dkg = _each(lambda x, dw: prod(x.T, dw, _TN), xs, dW)
    dvb = _each(lambda x, dvn: prod(x.T, dvn, _TN), xs, dv_new)
    dA = _each(lambda x, dt: exact(dt, x.T, _NT), xs, dT)
    dA = _each(lambda x, y: jnp.where(x.strict, -exact(x.T, y, _TN), 0.0), xs, dA)
    # A = strict(kk decay), scores = qk decay (decay is 0 above the diagonal)
    dkk = _each(lambda x, da: da * x.decay, xs, dA)
    dqk = _each(lambda x, ds: ds * x.decay, xs, dscores)
    # d(gamma_i - gamma_j)
    ddiff = _each(lambda x, da, ds: (da * x.kk + ds * x.qk) * x.decay, xs, dA, dscores)
    dkb = _each(lambda x, dkk, dkg, k: prod(dkk, k) + dkg * x.head, xs, dkk, dkg, k)
    for h, x in enumerate(xs):
        dq_ref[0, h] = (prod(dqk[h], k[h]) + dqg[h] * x.head).astype(dq_ref.dtype)
    for h, x in enumerate(xs):
        dk_ref[0, h] = (
            prod(dkk[h], x.kb, _TN) + prod(dqk[h], q[h], _TN) + dkt[h] * x.tail
            + dkb[h] * x.bcol).astype(dk_ref.dtype)
    for h, x in enumerate(xs):
        dv_ref[0, h] = (dvb[h] * x.bcol).astype(dv_ref.dtype)
    # a column [C, 1] as a row [1, C]: through the diagonal
    row = lambda c: total(jnp.where(xs[0].eye, c, 0.0))
    for h, x in enumerate(xs):
        dbeta_ref[0, 0, 0, h:h + 1, :] = row(
            rowsum(dkb[h] * x.kf) + rowsum(dvb[h] * x.vf))
        dtail = rowsum(dkt[h] * x.kf) * x.tail                          # [C, 1]
        dgcol = (rowsum(ddiff[h]) - dtail
                 + (rowsum(dkg[h] * x.kb) + rowsum(dqg[h] * x.qf)) * x.head)
        dlast = total(dtail) + dcarry[h] * x.carry
        dgamma_ref[0, 0, 0, h:h + 1, :] = (
            row(dgcol) - total(ddiff[h]) + jnp.where(at_last, dlast, 0.0))


# ----------------------------------------------------------------------
# The mixer's prologue: from the projection to what the rule takes
# ----------------------------------------------------------------------

# Rows (tokens) a grid step of the prologue's kernels, and rows a trip of
# the loop inside one (8 float32 vregs a lane tile: the trip's values stay
# in registers). ``_HALO``: the rows before a block that a step also reads,
# one bf16 tile (the convolution looks back K - 1 <= 7 of them).
ROWS = 512
_SUB = 64
_HALO = 16


def _prologue_shapes(qkvz, conv_w, key_heads, dk, dv):
    """(W, rep): the channels of one key head's group in ``qkvz``
    [B, T, key_heads * W] (q dk, k dk, v rep * dv, z rep * dv) and the value
    heads a key head serves."""
    W = qkvz.shape[-1] // key_heads
    rep = (W - 2 * dk) // (2 * dv)
    assert W * key_heads == qkvz.shape[-1] and 2 * dk + 2 * rep * dv == W, (
        qkvz.shape, key_heads, dk, dv)
    assert conv_w.shape[1] == key_heads * (W - rep * dv), (conv_w.shape, qkvz.shape)
    return W, rep


# The widest group of key heads a grid step of the prologue's kernels takes,
# in lanes of ``qkvz``: 9 tiles, Olmo-Hybrid's pair of key heads and the
# widest that was compiled and run. At ``ROWS`` rows the backward launch then
# holds 4.1 MB a buffer, each twice: the x block and d``qkvz``'s 1.18 MB of
# bf16 each, the four cotangents' 1.57 MB (their minor axes padded to whole
# lane tiles), the halo, the weights and dw's [8 K, Cw] float32 block 0.2 MB.
_GROUP_LANES = 1152


def _prologue_group(W: int, key_heads: int) -> int:
    """G, the key heads a grid step of the prologue's kernels takes: the
    fewest whose channels G x W are whole lane tiles (1 where W is, as
    Qwen3-Next's 768; 2 at Olmo-Hybrid's 576: 1152 = 9 tiles). 0 where the
    key heads do not come in such groups (15 heads of 576) or the group is
    wider than ``_GROUP_LANES``."""
    G = 128 // math.gcd(W, 128)
    if key_heads % G or G * W > _GROUP_LANES:
        return 0
    return G


def prologue_route(qkvz, conv_w, dk: int, dv: int) -> str:
    """Which form :func:`gdn_prologue` runs, from what it can observe, as
    :func:`kernel_route` does for the rule: "pallas" on a TPU backend at an
    eligible shape, "interpret" at such a shape under
    ``SXT_FUSED_INTERPRET=1``, else "xla". Eligible: some group of key heads
    is whole lane tiles of ``qkvz`` and no wider than a grid step takes
    (``_prologue_group``; the key heads follow from the shapes: ``qkvz``'s
    width less ``conv_w``'s is Hv dv),
    dk and dv fill the lane tiles a head's segment takes as far as the rule
    asks (``_pads_cheaply``: 128 / 128 and 96 / 192, not the tests' 16), a
    convolution no wider than one 8-row sublane tile, bf16 or float32
    activations."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    key_heads = (2 * conv_w.shape[1] - qkvz.shape[-1]) // (2 * dk)
    eligible = (_pads_cheaply(dk) and _pads_cheaply(dv) and key_heads > 0
                and _prologue_group(
                    _prologue_shapes(qkvz, conv_w, key_heads, dk, dv)[0], key_heads) > 0
                and conv_w.shape[0] <= 8
                and qkvz.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def gdn_prologue(qkvz, conv_w, key_heads: int, dk: int, dv: int,
                 eps: float = 1e-6, rows: int = ROWS):
    """Everything of a DeltaNet layer between its projection and the rule:
    ``qkvz`` [B, T, Hk * W] as the projection wrote it (a key head's group
    of W channels is its q [dk], k [dk], v [rep * dv] and z [rep * dv]) and
    ``conv_w`` [K, Hk dk + Hk dk + Hv dv] in the checkpoint's channel order
    (all q, all k, all v) -> q, k [B, T, Hv, dk] and v, z [B, T, Hv, dv] in
    ``qkvz``'s dtype: the causal depthwise convolution, SiLU, the l2 norm of
    q (times ``dk ** -0.5``) and of k, and each key head repeated to its
    ``rep`` value heads; z (the output gate's input) is handed on as it is.

    Two bodies, chosen by :func:`prologue_route`. The kernels
    (``gdn_prologue_fwd`` / ``gdn_prologue_bwd`` behind one
    ``jax.custom_vjp``) read ``qkvz`` once forward, and once more with the
    three cotangents backward; the convolution's accumulator, SiLU, the sum
    of squares and the rsqrt are float32 and the result is rounded to the
    compute dtype ONCE, at the write. A grid step takes ``rows`` rows of the
    fewest key heads whose channels are whole lane tiles (``_prologue_group``:
    one at 128 / 128, where W = 768; two at Olmo-Hybrid's 96 / 192, where
    W = 576 and a pair is 1152 = 9 tiles) and takes each head's segment
    where it lies in the block, on a tile's boundary or not. They write q,
    k, v as [B, Hv, T, d] (d as it is: 96 and 192 are not padded here),
    which is what the rule's kernels read: the transpose back to
    [B, T, Hv, d] here and the rule's own to [B, Hv, T, d] cancel in XLA.
    z goes through the kernels too, and its cotangent into d``qkvz``'s z
    channels: sliced out of ``qkvz`` by XLA, the layout the output norm
    wants for z cost a copy of all of ``qkvz`` a pass (PR 40).
    The XLA body is ``silu(causal_conv1d)`` -> split -> ``l2norm`` -> repeat
    (the convolution's result rounded to the compute dtype before SiLU, the
    norm's after it): the off-TPU path and the kernels' oracle.
    The same two kernel bodies serve the KDA mixer, whose projection puts all
    q | all k | all v (``ops/kda.py`` ``kda_prologue``; ``_prologue_core``'s
    ``parts``)."""
    route = prologue_route(qkvz, conv_w, dk, dv)
    if route == "xla":
        return _gdn_prologue_xla(qkvz, conv_w, key_heads, dk, dv, eps)
    return _gdn_prologue_pallas(qkvz, conv_w, key_heads, dk, dv, eps, rows,
                                interpret=route == "interpret")


def _gdn_prologue_xla(qkvz, conv_w, Hk, dk, dv, eps=1e-6):
    """``gdn_prologue`` as XLA ops: the channels gathered into the
    checkpoint's order, ``silu(causal_conv1d)``, the split, ``l2norm`` of the
    repeated heads."""
    import jax
    import jax.numpy as jnp

    B, T, _ = qkvz.shape
    W, rep = _prologue_shapes(qkvz, conv_w, Hk, dk, dv)
    x = qkvz.reshape(B, T, Hk, W)
    mixed = jnp.concatenate(
        [x[..., :dk].reshape(B, T, Hk * dk),
         x[..., dk:2 * dk].reshape(B, T, Hk * dk),
         x[..., 2 * dk:2 * dk + rep * dv].reshape(B, T, Hk * rep * dv)], axis=-1)
    mixed = jax.nn.silu(causal_conv1d(mixed, conv_w))
    q, k, v = jnp.split(mixed, [Hk * dk, 2 * Hk * dk], axis=-1)
    # each key head serves ``rep`` value heads
    heads = lambda a: jnp.repeat(a.reshape(B, T, Hk, dk), rep, axis=2)
    q = (l2norm(heads(q), eps) * dk ** -0.5).astype(qkvz.dtype)
    k = l2norm(heads(k), eps).astype(qkvz.dtype)
    wide = lambda a: a.reshape(B, T, Hk * rep, dv)
    return q, k, wide(v), wide(x[..., 2 * dk + rep * dv:])


def _gdn_prologue_pallas(qkvz, conv_w, Hk, dk, dv, eps=1e-6, rows=ROWS,
                         interpret: bool = False):
    """``gdn_prologue`` through the kernels, G key heads a grid step
    (``_prologue_group``). ``conv_w`` goes in as [Hk / G, 8, G W - rep dv]
    float32: permuted to the order the projection left the channels in, a
    group's [q | k | v] of each key head at the lanes they have in the
    group's G W channels of ``qkvz`` (zeros at the z's between two heads;
    the last head's z's, which end the group, are left off: at G = 1 a row
    is one key head's [q | k | v]), K padded to a sublane tile. T is padded
    to whole blocks of rows with zeros (nothing where ``rows`` divides it);
    the padding, the permutation and the transposes back to [B, T, Hv, d]
    are XLA's, and so are their gradients."""
    import jax.numpy as jnp

    B, T, _ = qkvz.shape
    K = conv_w.shape[0]
    W, rep = _prologue_shapes(qkvz, conv_w, Hk, dk, dv)
    G = _prologue_group(W, Hk)
    assert G and rows % _SUB == 0, (W, Hk, rows)
    wq, wk, wv = jnp.split(conv_w.astype(jnp.float32), [Hk * dk, 2 * Hk * dk], axis=1)
    by_head = lambda w: w.reshape(K, Hk, -1)
    w = jnp.concatenate([by_head(wq), by_head(wk), by_head(wv)], axis=-1)
    if G > 1:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, rep * dv)))
        w = w.reshape(K, Hk // G, G * W)[..., :G * W - rep * dv]
    w = jnp.pad(jnp.swapaxes(w, 0, 1), ((0, 0), (0, 8 - K), (0, 0)))
    R = min(rows, -(-T // _SUB) * _SUB)
    x = jnp.pad(qkvz, ((0, 0), (0, -T % R), (0, 0)))
    core = _prologue_core(K, dk, dv, rep, float(eps), R, interpret)
    return tuple(jnp.swapaxes(a[:, :, :T], 1, 2) for a in core(x, w))


@functools.lru_cache(maxsize=None)
def _prologue_core(K, dk, dv, rep, eps, R, interpret, parts=1):
    """The prologue on whole blocks of R rows as one ``jax.custom_vjp``:
    (x [B, T, Hk * W], w [Hk / G, 8, G W - rep dv] float32) -> q, k, v, z
    [B, Hv, T, d]; G follows from the two shapes. The input is the only
    residual; each launch under its own jit, built once (see
    ``_delta_core``). ``parts`` says where a head's segments lie in x
    (``_segments``): 1, a key head's group side by side, as above; 3, all q |
    all k | all v with no z (the KDA mixer's projection, ``ops/kda.py``
    ``kda_prologue``): (x [B, T, 3 H d], w [3 H / G, 8, G d]) -> q, k, v."""
    import jax

    static = dict(K=K, dk=dk, dv=dv, rep=rep, eps=eps, R=R, interpret=interpret,
                  parts=parts)
    forward = jax.jit(functools.partial(_prologue_forward, **static))
    backward = jax.jit(functools.partial(_prologue_backward, **static))

    @jax.custom_vjp
    def core(x, w):
        return tuple(forward(x, w))

    def fwd(x, w):
        return tuple(forward(x, w)), (x, w)

    def bwd(kept, cotangents):
        return tuple(backward(*kept, *cotangents))

    core.defvjp(fwd, bwd)
    return core


def _prologue_blocks(x, w, dk, dv, rep, R, block_at, parts=1):
    """(groups, heads, rows, halo, weights, wide) of a launch on x
    [B, T, Hk * W] and w [Hk / G, 8, Cw]: the ``groups`` of G key heads, the
    ``heads`` = G rep value heads of one, and the block specs of a grid step
    (row b, group h, step n) that works on rows ``block_at(n) * R`` onward:
    ``rows`` of x (the G W channels of the group: whole lane tiles wherever
    the group starts), ``halo`` the ``_HALO`` rows before them (the first
    block reads its own and masks them), ``weights``, and ``wide(d)`` for the
    group's heads of q, k, v, z [B, Hv, T, d] (d is the array's whole minor
    axis, a lane tile or not). At ``parts`` = 3 (x [B, T, 3 H d], w
    [3 H / G, 8, G d]) the grid has a fourth, innermost axis p, the part: a
    step takes the G d lanes of its G heads out of part p's columns of x, and
    ``wide`` blocks do not move with p (a step writes the one of q, k, v
    that is its part's; the block leaves when the group's three are in)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    groups, Cw = w.shape[0] // parts, w.shape[2]
    lanes = x.shape[-1] // w.shape[0]
    heads = lanes // (2 * dk + 2 * rep * dv) * rep if parts == 1 else lanes // dk
    # the lane block of group h (of part p)
    at = lambda h, *p: h if not p else p[0] * groups + h
    rows = pl.BlockSpec((1, R, lanes), lambda b, h, n, *p: (b, block_at(n), at(h, *p)))
    halo = pl.BlockSpec((1, _HALO, lanes), lambda b, h, n, *p: (
        b, jnp.maximum(block_at(n) * (R // _HALO) - 1, 0), at(h, *p)))
    weights = pl.BlockSpec((1, 8, Cw), lambda b, h, n, *p: (at(h, *p), 0, 0))
    wide = lambda d: pl.BlockSpec(
        (1, heads, R, d), lambda b, h, n, *p: (b, h, block_at(n), 0))
    return groups, heads, rows, halo, weights, wide


# The launches' names by ``parts``: the DeltaNet mixer's, the KDA mixer's
_PROLOGUE_STEM = {1: "gdn_prologue", 3: "kda_prologue"}


def _prologue_forward(x, w, K, dk, dv, rep, eps, R, interpret, parts=1):
    """The forward kernel's launch -> [q, k, v, z] ([q, k, v] at ``parts``
    = 3)."""
    import jax
    from jax.experimental import pallas as pl

    B, Tp, _ = x.shape
    groups, heads, rows, halo, weights, wide = _prologue_blocks(
        x, w, dk, dv, rep, R, lambda n: n, parts)
    out = lambda d: jax.ShapeDtypeStruct((B, groups * heads, Tp, d), x.dtype)
    widths = [dk, dk, dv, dv][:4 if parts == 1 else 3]
    grid = (B, groups, Tp // R) + (() if parts == 1 else (parts,))
    return pl.pallas_call(
        functools.partial(_prologue_fwd_kernel, K=K, dk=dk, dv=dv, rep=rep, eps=eps,
                          parts=parts),
        grid=grid,
        in_specs=[rows, halo, weights],
        out_specs=[wide(d) for d in widths],
        out_shape=[out(d) for d in widths],
        compiler_params=_compiler_params(len(grid)), interpret=interpret,
        name=_PROLOGUE_STEM[parts] + "_fwd",
    )(x, x, w)


def _prologue_backward(x, w, *cotangents, K, dk, dv, rep, eps, R, interpret, parts=1):
    """The backward kernel's launch on the cotangents of q, k, v (and z) ->
    [dx, dw]. The sweep runs over the row blocks from the last to the first;
    dw comes out as [B, Hk / G, parts 8 K, Cw] partial sums (one a batch row
    and sublane) and is summed here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, Tp, _ = x.shape
    Cw = w.shape[2]
    N = Tp // R
    groups, _, rows, halo, weights, wide = _prologue_blocks(
        x, w, dk, dv, rep, R, lambda n: N - 1 - n, parts)
    widths = [dk, dk, dv, dv][:len(cotangents)]
    grid = (B, groups, N) + (() if parts == 1 else (parts,))
    dx, dw = pl.pallas_call(
        functools.partial(_prologue_bwd_kernel, K=K, dk=dk, dv=dv, rep=rep, eps=eps,
                          parts=parts),
        grid=grid,
        in_specs=[rows, halo, weights] + [wide(d) for d in widths],
        out_specs=[rows, pl.BlockSpec((1, 1, parts * 8 * K, Cw),
                                      lambda b, h, n, *p: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, groups, parts * 8 * K, Cw), f32)],
        scratch_shapes=[pltpu.VMEM((parts * 8, Cw), f32)],
        compiler_params=_compiler_params(len(grid)), interpret=interpret,
        name=_PROLOGUE_STEM[parts] + "_bwd",
    )(x, x, w, *cotangents)
    if parts == 1:
        dw = jnp.sum(dw.reshape(B, groups, K, 8, Cw), axis=(0, 3))
    else:       # [groups, parts, K, Cw] -> w's order, part-major
        dw = jnp.sum(dw.reshape(B, groups, parts, K, 8, Cw), axis=(0, 4))
        dw = jnp.swapaxes(dw, 0, 1).reshape(parts * groups, K, Cw)
    return dx, jnp.pad(dw, ((0, 0), (0, 8 - K), (0, 0)))


def _segments(dk, dv, rep, lanes, part=None):
    """(start, width, kind, head) of every head's channels among the
    ``lanes`` of ``qkvz`` a grid step takes, a group of key heads side by
    side, W = 2 dk + 2 rep dv each: a key head's q and k (``head``: the
    first of the ``rep`` value heads it serves), then its ``rep`` value
    heads' v; each v's z lies ``rep * dv`` lanes on. Starts are where the
    projection put them: lane tiles' boundaries at 128 / 128, any multiple
    of 32 at 96 / 192. ``part`` 0 / 1 / 2: the step's lanes are G heads' q,
    k or v side by side (all q | all k | all v: every start a lane tile's).
    The kernels unroll the segments inside a trip on purpose: a segment's
    chain (taps, SiLU, row sum, rsqrt) waits on itself, and side by side
    the chains overlap. A loop over the heads with one segment's body, the
    lanes dynamic, read 3.2 / 5.5 ms a forward / backward launch at KDA's
    shape whatever G, where four unrolled read 2.05 / 3.43 and ONE 3.55 /
    6.24 (my chip runs, PR 68)."""
    if part is not None:
        d = (dk, dk, dv)[part]
        return [(g * d, d, "qkv"[part], g) for g in range(lanes // d)]
    W = 2 * dk + 2 * rep * dv
    segs = []
    for g in range(lanes // W):
        segs += [(g * W, dk, "q", g * rep), (g * W + dk, dk, "k", g * rep)]
        segs += [(g * W + 2 * dk + r * dv, dv, "v", g * rep + r) for r in range(rep)]
    return segs


def _each_part(parts, run):
    """``run(part, step, steps)`` for the part a grid step works on:
    ``part`` None where the segments lie side by side (one part), else in
    the branch of the grid's innermost index. ``step()`` / ``steps()``: the
    step's row block and their number, read outside the branches (the
    interpreter resolves no grid index inside one)."""
    from jax.experimental import pallas as pl

    if parts == 1:
        return run(None, lambda: pl.program_id(2), lambda: pl.num_programs(2))
    step, steps = pl.program_id(2), pl.num_programs(2)
    for part in range(parts):
        pl.when(pl.program_id(3) == part)(
            functools.partial(run, part, lambda: step, lambda: steps))


def _rows_before(halo_ref, Cw, at_start):
    """float32 [8, Cw]: the 8 rows before a block's first, zeros where the
    block is the sequence's first (``at_start``)."""
    import jax.numpy as jnp

    return jnp.where(at_start, 0.0, halo_ref[0, _HALO - 8:, :Cw].astype(jnp.float32))


def _chunk_rows(x_ref, before, c, lanes):
    """float32 [8 + _SUB, n]: the 8 rows before trip ``c``'s rows of a
    block (``before``'s ahead of the block's first), then its rows, of the
    lanes ``lanes``; and the slice of the trip's rows."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    c0 = pl.multiple_of(c * _SUB, _SUB)
    back = pl.multiple_of(jnp.maximum(c0 - _HALO, 0), _HALO)
    prev = x_ref[0, pl.ds(back, _HALO), lanes].astype(f32)[_HALO - 8:]
    at = pl.ds(c0, _SUB)
    return jnp.concatenate([jnp.where(c == 0, before[:, lanes], prev),
                            x_ref[0, at, lanes].astype(f32)], axis=0), at


def _taps(ext, K):
    """[ext's rows 8 - s onward, _SUB of them, for s = K - 1 .. 0]: what
    tap j of the convolution multiplies (s = K - 1 - j rows back)."""
    from jax.experimental.pallas import tpu as pltpu

    return [ext[8:] if s == 0 else pltpu.roll(ext, s, 0)[8:]
            for s in range(K - 1, -1, -1)]


def _prologue_fwd_kernel(x_ref, halo_ref, w_ref, q_ref, k_ref, v_ref, z_ref=None, *,
                         K, dk, dv, rep, eps, parts=1):
    """R rows of one group of key heads of one batch row: per head's
    segment (``_segments``) the convolution over the K rows that end at a
    row, SiLU and (q, k) the l2 norm, float32 until the write; q and k are
    written to the key head's ``rep`` value heads, z's channels are
    copied (``parts`` = 1; at 3 the step's segments are one part's, there is
    no z, and the step writes that part's output alone)."""
    import jax
    import jax.numpy as jnp

    from jax.experimental import pallas as pl

    def run(part, step, steps):
        segs = _segments(dk, dv, rep, x_ref.shape[2], part)
        w = [[w_ref[0, j:j + 1, a:a + n] for j in range(K)] for a, n, _, _ in segs]
        before = _rows_before(halo_ref, w_ref.shape[2], step() == 0)

        def trip(c, carry):
            for i, (a, n, kind, h) in enumerate(segs):
                ext, at = _chunk_rows(x_ref, before, c, slice(a, a + n))
                pre = sum(wj * xj for wj, xj in zip(w[i], _taps(ext, K)))
                act = pre * jax.nn.sigmoid(pre)
                if kind != "v":
                    unit = jax.lax.rsqrt(jnp.sum(act * act, axis=-1, keepdims=True) + eps)
                    out = (act * (unit * dk ** -0.5 if kind == "q" else unit)).astype(
                        q_ref.dtype)
                    for r in range(rep):
                        (q_ref if kind == "q" else k_ref)[0, h + r, at, :] = out
                else:
                    v_ref[0, h, at, :] = act.astype(v_ref.dtype)
                    if z_ref is not None:
                        z_ref[0, h, at, :] = x_ref[0, at, pl.ds(a + rep * dv, dv)]
            return carry

        jax.lax.fori_loop(0, x_ref.shape[1] // _SUB, trip, 0)

    _each_part(parts, run)


def _prologue_bwd_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, *rest,
                         K, dk, dv, rep, eps, parts=1):
    """The same block's gradients; the grid's row-block axis walks the row
    blocks from the last to the first, and so do the trips inside a block. The
    pre-activation is computed again in float32; the norm's, SiLU's and the
    repeat's transposes (a sum over the ``rep`` heads) stay in registers.
    The convolution's transpose needs the pre-activation's cotangent of the
    K - 1 rows AFTER a row: ``ahead`` [8, Cw] carries the first rows' of
    the block after this one (zeros at the end), and each trip hands its
    own first 8 rows' to the trip before it. dw sums over all rows: 8
    partial sums (one a sublane) a tap, accumulated in the output block
    over the walk. z's cotangent is copied into its channels of dx. At
    ``parts`` = 3 (``rest`` without dz) ``ahead`` and dw's block hold a part
    after the other in their rows, each its own over the walk."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    dz_ref = rest[0] if len(rest) == 4 else None
    dx_ref, dw_ref, ahead = rest[-3:]
    trips = x_ref.shape[1] // _SUB

    def run(part, step, steps):
        segs = _segments(dk, dv, rep, x_ref.shape[2], part)
        p = part or 0
        mine = slice(8 * p, 8 * p + 8)              # the part's rows of ``ahead``
        w = [[w_ref[0, j:j + 1, a:a + n] for j in range(K)] for a, n, _, _ in segs]
        before = _rows_before(halo_ref, w_ref.shape[2], step() == steps() - 1)

        @pl.when(step() == 0)
        def _():
            ahead[mine, :] = jnp.zeros((8, ahead.shape[1]), f32)
            dw_ref[:, :, 8 * K * p:8 * K * (p + 1), :] = jnp.zeros(
                (1, 1, 8 * K, dw_ref.shape[3]), f32)

        def trip(t, after):
            c = trips - 1 - t
            first = []
            for i, (a, n, kind, h) in enumerate(segs):
                lanes = slice(a, a + n)
                ext, at = _chunk_rows(x_ref, before, c, lanes)
                taps = _taps(ext, K)
                pre = sum(wj * xj for wj, xj in zip(w[i], taps))
                sig = jax.nn.sigmoid(pre)
                if kind != "v":
                    ref = dq_ref if kind == "q" else dk_ref
                    g = sum(ref[0, h + r, at, :].astype(f32) for r in range(rep))
                    act = pre * sig
                    unit = jax.lax.rsqrt(jnp.sum(act * act, axis=-1, keepdims=True) + eps)
                    y = act * unit
                    dact = (g - y * jnp.sum(g * y, axis=-1, keepdims=True)) * (
                        unit * dk ** -0.5 if kind == "q" else unit)
                else:
                    dact = dv_ref[0, h, at, :].astype(f32)
                    if dz_ref is not None:
                        dx_ref[0, at, pl.ds(a + rep * dv, dv)] = dz_ref[0, h, at, :]
                dpre = dact * (sig * (1.0 + pre * (1.0 - sig)))
                for j, xj in enumerate(taps):
                    o, prod = 8 * (K * p + j), dpre * xj
                    dw_ref[0, 0, o:o + 8, lanes] += sum(
                        prod[s:s + 8] for s in range(0, _SUB, 8))
                # dx[t] = sum_j w[j] dpre[t + K - 1 - j]
                ext = jnp.concatenate([dpre, after[i]], axis=0)
                dx = sum(wj * (dpre if u == 0 else pltpu.roll(ext, _SUB + 8 - u, 0)[:_SUB])
                         for wj, u in zip(w[i], range(K - 1, -1, -1)))
                dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
                first.append(dpre[:8])
            return tuple(first)

        after = jax.lax.fori_loop(
            0, trips, trip, tuple(ahead[mine, a:a + n] for a, n, _, _ in segs))
        for (a, n, _, _), rows in zip(segs, after):
            ahead[mine, a:a + n] = rows

    _each_part(parts, run)
