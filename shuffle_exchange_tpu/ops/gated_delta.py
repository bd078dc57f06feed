"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; the linear-attention
mixer of Qwen3-Next) and what surrounds it in a layer: the causal depthwise
convolution and the l2 norm of q and k.

Per head, with a state ``S`` [dk, dv] that starts at 0, a log-decay ``g_t`` <= 0
and a write strength ``beta_t`` in [0, 1]::

    S   <- exp(g_t) * S
    u_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``gated_delta_recurrent`` is that, one token at a time: the plain form, kept
for the tests (the trainer never runs it). ``gated_delta_chunked`` is the form
that trains: chunks of ``chunk`` tokens, everything inside a chunk as matrix
products, and a ``lax.scan`` over the chunks that carries ``S``.

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
scores x V_new 2 C^2 dv. The backward is autodiff through all of it (about
twice the forward) except T's, which is ``-T^T dT T^T``. The two parts that
are parallel over chunks (everything up to W and U; the scores and O) are
computed AGAIN in the backward instead of kept; the scan is not.
"""

from __future__ import annotations

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


def causal_conv1d(x, w):
    """Depthwise causal convolution, no bias. x [B, T, C], w [K, C]:
    ``y[t] = sum_j w[j] * x[t - (K - 1) + j]`` with x zero before position 0
    (torch's ``Conv1d(C, C, K, groups=C, padding=K-1)`` cut to T outputs).
    Accumulates in float32; returns x's dtype."""
    import jax.numpy as jnp

    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    y = sum(xp[:, j:j + T] * w32[j] for j in range(K))
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


def gated_delta_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The same function as :func:`gated_delta_recurrent` in the chunked
    (matrix-product) form of the module docstring; differentiable (autodiff
    through the products and the scan over chunks). Same shapes;
    o [B, T, H, dv] float32. T need not divide by ``chunk``: the tail is
    padded with tokens that write nothing (beta 0, g 0) and cut off."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    mxu = q.dtype
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    pad = -T % C
    if pad:
        p4 = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        p3 = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        q, k, v, g, beta = p4(q), p4(k), p4(v), p3(g), p3(beta)
    N = (T + pad) // C

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
    o = o.transpose(0, 2, 3, 1, 4).reshape(B, N * C, H, dv)
    return o[:, :T] if pad else o
