"""Pallas fused AdamW.

TPU replacement for the reference's multi-tensor fused CUDA optimizers
(FusedAdamBuilder — ``ops/adam/fused_adam.py:15`` — plus the CPUAdam AVX
path for offload, SURVEY.md §2.13). One kernel reads p, g, m, v once from
HBM and writes p, m, v once — the update is purely HBM-bandwidth-bound, so
a single fused pass is the roofline. ``input_output_aliases`` makes the
update in-place (no extra HBM footprint), which XLA's generic fusion cannot
guarantee across optax's multi-op chain when buffers are donated through a
jit boundary.

Exposed two ways:
- ``fused_adamw_update(p, g, m, v, ...)`` — the raw per-leaf kernel.
- ``pallas_adamw(lr, ...)`` — an optax.GradientTransformation drop-in used
  by the engine when ``optimizer.type`` is a Fused* name and we're on TPU.
"""

from __future__ import annotations

from typing import NamedTuple

LANES = 128
SUBLANES = 8
_BLOCK = 1024  # rows of 128 lanes per grid step → 512KB fp32 per operand


def _pad_to_2d(x, lanes=LANES):
    """Flatten to [rows, 128], padding the tail."""
    import jax.numpy as jnp

    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // lanes)
    pad = rows * lanes - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, lanes), n


def fused_adamw_update(p, g, m, v, *, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0, step=None):
    """Returns (new_p, new_m, new_v). ``step`` is the 1-based step count used
    for bias correction (traced scalar ok)."""
    import jax
    import jax.numpy as jnp

    from .dispatch import pallas_enabled

    if not pallas_enabled():
        return _reference_update(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                                 weight_decay=weight_decay, step=step)
    from jax.experimental import pallas as pl

    orig_shape, orig_dtype = p.shape, p.dtype
    p2, n = _pad_to_2d(p.astype(jnp.float32))
    g2, _ = _pad_to_2d(g.astype(jnp.float32))
    m2, _ = _pad_to_2d(m.astype(jnp.float32))
    v2, _ = _pad_to_2d(v.astype(jnp.float32))
    rows = p2.shape[0]
    block = min(_BLOCK, rows)
    from jax.experimental.pallas import tpu as pltpu

    step_f = jnp.asarray(step if step is not None else 1, jnp.float32)
    bc1 = 1.0 - b1 ** step_f
    bc2 = 1.0 - b2 ** step_f
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32), bc1, bc2]).reshape(1, 3)

    def kernel(s_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref):
        lr_ = s_ref[0, 0]
        bc1_ = s_ref[0, 1]
        bc2_ = s_ref[0, 2]
        gv = g_ref[:]
        mv = b1 * m_ref[:] + (1.0 - b1) * gv
        vv = b2 * v_ref[:] + (1.0 - b2) * gv * gv
        m_hat = mv / bc1_
        v_hat = vv / bc2_
        pv = p_ref[:]
        upd = m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * pv
        po_ref[:] = pv - lr_ * upd
        mo_ref[:] = mv
        vo_ref[:] = vv

    grid = (pl.cdiv(rows, block),)
    bspec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    new_p, new_m, new_v = pl.pallas_call(
        kernel,
        name="sxt_fused_adamw",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            bspec, bspec, bspec, bspec,
        ],
        out_specs=(bspec, bspec, bspec),
        out_shape=(
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        ),
        input_output_aliases={1: 0, 3: 1, 4: 2},
    )(scalars, p2, g2, m2, v2)
    unpad = lambda x: x.reshape(-1)[:n].reshape(orig_shape)
    return unpad(new_p).astype(orig_dtype), unpad(new_m), unpad(new_v)


def _reference_update(p, g, m, v, *, lr, b1, b2, eps, weight_decay, step):
    import jax.numpy as jnp

    p32, g32 = p.astype(jnp.float32), g.astype(jnp.float32)
    step_f = jnp.asarray(step if step is not None else 1, jnp.float32)
    mv = b1 * m + (1.0 - b1) * g32
    vv = b2 * v + (1.0 - b2) * g32 * g32
    m_hat = mv / (1.0 - b1 ** step_f)
    v_hat = vv / (1.0 - b2 ** step_f)
    new_p = p32 - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p32)
    return new_p.astype(p.dtype), mv, vv


class PallasAdamState(NamedTuple):
    count: "jax.Array"
    mu: any
    nu: any


def pallas_adamw(learning_rate, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                 leaf_specs=None):
    """optax.GradientTransformation whose update runs the fused kernel.

    Note: returns *updates* (new_p - p) so it composes with
    ``optax.apply_updates`` like any transformation; XLA folds the add away.

    ``leaf_specs``: the PartitionSpec of every parameter leaf (the engine's
    master shardings). In a program that spans several devices each device
    then runs the kernel on its own shard of p/g/m/v
    (``parallel.mesh.shard_kernel``: XLA cannot partition a Mosaic kernel);
    the update is elementwise, so any layout is right.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return PallasAdamState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params),
        )

    def update(grads, state, params=None):
        assert params is not None, "pallas_adamw needs params (AdamW decoupled decay)"
        # the schedule is read at the number of updates ALREADY made, as
        # optax's scale_by_learning_rate does (and as the reference steps
        # its scheduler after the optimizer): a warm-up's first step is
        # lr(0). Only the bias correction counts this update in. "FusedAdam"
        # is this on a TPU and optax.adamw elsewhere: the two must agree.
        count = state.count + 1
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)

        from ..parallel.mesh import shard_kernel

        def kernel(p, g, m, v, lr, count):
            return fused_adamw_update(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                                      weight_decay=weight_decay, step=count)

        def leaf(p, g, m, v, spec=None):
            spec = spec if spec is not None else P(*([None] * p.ndim))
            new_p, new_m, new_v = shard_kernel(
                kernel, (spec,) * 4 + (P(), P()), (spec,) * 3)(
                    p, g, m, v, jnp.asarray(lr, jnp.float32), count)
            return (new_p.astype(jnp.float32) - p.astype(jnp.float32)), new_m, new_v

        trees = (params, grads, state.mu, state.nu)
        if leaf_specs is not None:
            trees += (leaf_specs,)
        out = jax.tree_util.tree_map(leaf, *trees)
        treedef = jax.tree_util.tree_structure(params)
        leaves = jax.tree_util.tree_leaves(out, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3)
        updates = jax.tree_util.tree_unflatten(treedef, [l[0] for l in leaves])
        mu = jax.tree_util.tree_unflatten(treedef, [l[1] for l in leaves])
        nu = jax.tree_util.tree_unflatten(treedef, [l[2] for l in leaves])
        return updates, PallasAdamState(count=count, mu=mu, nu=nu)

    import optax

    return optax.GradientTransformation(init, update)
