"""Fused RMSNorm.

TPU replacement for the reference's ``cuda_rms_norm`` kernel
(``inference/v2/kernels/core_ops/cuda_rms_norm/``, SURVEY.md §2.13). The jnp
form below is what XLA fuses already; the Pallas kernel (enabled on TPU for
large rows) keeps the row in VMEM across the two passes and fuses the
optional residual-add, matching the CUDA kernel's fused pre-norm variant.
"""

from __future__ import annotations


def rmsnorm_reference(x, weight, eps: float = 1e-5):
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def rmsnorm(x, weight, eps: float = 1e-5, residual=None):
    """RMSNorm with optional fused residual input: norm(x + residual) * w.
    The Pallas kernel where the backend is a TPU and the row is whole lane
    tiles, else the jnp form; a kernel that was selected runs or raises."""
    from .dispatch import pallas_enabled

    if residual is not None:
        x = x + residual
    if not (pallas_enabled() and x.shape[-1] % 128 == 0):
        return rmsnorm_reference(x, weight, eps)
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import kernel_activation_spec, shard_kernel

    rows = kernel_activation_spec(x.shape,
                                  seq_dim=1 if x.ndim >= 3 else None)
    return shard_kernel(lambda x, w: _rmsnorm_vjp(x, w, eps),
                        (rows, P(None)), rows)(x, weight)


_VJP_CACHE = {}


def _rmsnorm_vjp(x, weight, eps):
    """Differentiable wrapper: Pallas forward, analytic jnp backward.

    A raw pallas_call has no VJP rule (round-3 fix: training any rmsnorm
    model on TPU died in linearization); the backward is a handful of
    elementwise ops + row reduction that XLA fuses into one pass, so a
    Pallas bwd kernel would buy nothing. The custom_vjp function is built
    once (eps is static — a closure per distinct eps, cached) so JAX sees a
    stable primitive identity across layers and traces.
    """
    fn = _VJP_CACHE.get(eps)
    if fn is None:
        fn = _build_vjp(eps)
        _VJP_CACHE[eps] = fn
    return fn(x, weight)


def _build_vjp(eps):
    import jax

    @jax.custom_vjp
    def _f(x, w):
        return _rmsnorm_pallas(x, w, eps)

    def _fwd(x, w):
        return _rmsnorm_pallas(x, w, eps), (x, w)

    def _bwd(res, g):
        import jax.numpy as jnp

        x, w = res
        x32, g32, w32 = (t.astype(jnp.float32) for t in (x, g, w))
        r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        xhat = x32 * r
        gw = g32 * w32
        dx = r * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
        dw = jnp.sum(g32 * xhat, axis=tuple(range(x.ndim - 1)))
        return dx.astype(x.dtype), dw.astype(w.dtype)

    _f.defvjp(_fwd, _bwd)
    return _f


def _rmsnorm_pallas(x, weight, eps):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    # in and out blocks are both double-buffered: 4 x block bytes must stay
    # well inside Mosaic's 16 MiB default scoped VMEM (256 rows of f32 at
    # d 4096 is 4 MiB a block and was refused by 16 KiB)
    fit = max(8, (2 << 20) // (d * x.dtype.itemsize) // 8 * 8)
    block_rows = min(rows, 256, fit)

    def kernel(x_ref, w_ref, o_ref):
        xv = x_ref[:].astype(jnp.float32)
        var = jnp.mean(xv * xv, axis=-1, keepdims=True)
        o_ref[:] = (xv * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)

    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        kernel,
        name="sxt_rmsnorm",
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
    )(x2, weight)
    return out.reshape(orig_shape)
