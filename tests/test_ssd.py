"""The Mamba-2 state-space scan (``ops/ssd.py``): the chunked form the trainer
runs against the recurrence as written, outputs and every gradient, at
sequence lengths that are whole chunks and not, with fewer groups than heads,
at several chunk sizes, in float32 and from bf16 operands; the XLA form, and
the Pallas kernels a TPU runs through the interpreter
(``SXT_FUSED_INTERPRET=1``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.ops.ssd import (CHUNK, ssd_chunked, ssd_chunks, ssd_recurrent,
                                          ssd_route)


def drawn(T, H=4, P=8, G=2, N=16, Bt=2, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (Bt, T, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)) - 2)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0, maxval=2.7))
    B = jax.random.normal(ks[3], (Bt, T, G, N)).astype(dtype)
    C = jax.random.normal(ks[4], (Bt, T, G, N)).astype(dtype)
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, B, C, D


def both(fn, args):
    """(output, the gradients of all six operands under one scalar of it)."""
    scalar = lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()
    return fn(*args), jax.grad(scalar, argnums=tuple(range(6)))(*args)


# (T, chunk): whole chunks; a ragged tail; the same in fewer chunks; one chunk
# exactly; shorter than a chunk
SHAPES = [(64, 16), (50, 16), (50, 32), (16, 16), (7, 16)]


@pytest.mark.parametrize("T, chunk", SHAPES)
@pytest.mark.parametrize("groups", [1, 2, 4], ids=lambda g: f"G{g}")
def test_the_chunked_form_is_the_recurrence(T, chunk, groups):
    args = drawn(T, G=groups)
    o, grads = both(ssd_recurrent, args)
    o2, grads2 = both(lambda *a: ssd_chunked(*a, chunk=chunk), args)
    assert o2.shape == o.shape == (2, T, 4, 8)
    np.testing.assert_allclose(o2, o, rtol=2e-5, atol=2e-5)
    for name, a, b in zip("x dt A B C D".split(), grads2, grads):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(jnp.abs(b).max()), name


@pytest.mark.parametrize("T, chunk", SHAPES[:3])
def test_bf16_operands_round_their_products_and_keep_the_state(T, chunk):
    """x, B and C in bf16: the output in bf16, within bf16's rounding of the
    recurrence on the same (rounded) numbers (a state kept in bf16 between the
    chunks would not be: ``chipbench/nemotron3_band.py``)."""
    args = drawn(T, dtype=jnp.bfloat16)
    o, grads = both(ssd_recurrent, args)
    o2, grads2 = both(lambda *a: ssd_chunked(*a, chunk=chunk), args)
    assert o2.dtype == jnp.bfloat16
    gap = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    assert gap(o2, o) < 1e-2
    for name, a, b in zip("x dt A B C D".split(), grads2, grads):
        assert gap(a, b) < 3e-2, name


def test_a_head_reads_its_own_group_and_no_row_reaches_the_next():
    """Head h reads group h // (H / G); a sequence's state does not leak into
    the next row of the batch."""
    x, dt, A, B, C, D = drawn(24, H=4, G=2)
    o = ssd_chunked(x, dt, A, B, C, D, chunk=8)
    moved = ssd_chunked(x, dt, A, B.at[0, 3, 1].add(1.0), C, D, chunk=8)
    changed = np.abs(np.asarray(moved - o)).max(axis=-1) > 0        # [Bt, T, H]
    assert not changed[1].any()                                     # the other row
    assert not changed[0, :, :2].any() and changed[0, 3:, 2:].any()  # group 1 = heads 2, 3
    assert not changed[0, :3].any()                                 # causal


# (T, heads, head size, groups): two heads a lane tile and one; whole chunks
# and a ragged tail; one group and several; a single chunk
KERNEL_SHAPES = [(256, 4, 64, 2), (200, 4, 64, 1), (256, 2, 128, 1), (90, 2, 64, 1)]


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")


@pytest.mark.parametrize("T, H, P, G", KERNEL_SHAPES)
def test_the_kernels_are_the_recurrence(interpreted, T, H, P, G):
    """Float32 operands through the kernels: outputs and every gradient."""
    args = drawn(T, H=H, P=P, G=G, N=128)
    assert ssd_route(args[0], args[3]) == "interpret"
    o, grads = both(ssd_recurrent, args)
    o2, grads2 = both(ssd_chunked, args)
    np.testing.assert_allclose(o2, o, rtol=5e-5, atol=5e-5)
    for name, a, b in zip("x dt A B C D".split(), grads2, grads):
        assert float(jnp.abs(a - b).max()) <= 5e-5 * float(jnp.abs(b).max()), name


@pytest.mark.parametrize("T, H, P, G", KERNEL_SHAPES[:3])
def test_the_kernels_round_bf16_operands_as_the_xla_form_does(interpreted, monkeypatch,
                                                              T, H, P, G):
    """x, B and C in bf16 through the kernels: within bf16's rounding of the
    recurrence, and no further from it than the XLA form on the same numbers
    (twice its distance, at the least 1e-3)."""
    args = drawn(T, H=H, P=P, G=G, N=128, dtype=jnp.bfloat16)
    o, grads = both(ssd_recurrent, args)
    o2, grads2 = both(ssd_chunked, args)
    monkeypatch.delenv("SXT_FUSED_INTERPRET")
    assert ssd_route(args[0], args[3]) == "xla"
    o3, grads3 = both(ssd_chunked, args)
    assert o2.dtype == jnp.bfloat16
    gap = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    for name, ours, xla, exact in zip(["o"] + "x dt A B C D".split(),
                                      (o2,) + grads2, (o3,) + grads3, (o,) + grads):
        assert gap(ours, exact) <= max(2 * gap(xla, exact), 1e-3), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("route", ["xla", "interpret"])
def test_without_the_skip_the_scan_is_that_less_d_x(monkeypatch, route, dtype):
    """``D`` None (the mixer's call: its epilogue adds the skip): the output
    in x's dtype is the scan's with ``D`` less ``D x`` (to one rounding of the
    sum in bf16), and x's gradient lacks ``D`` times the cotangent; with zeros
    for ``D`` the two calls are the same numbers."""
    if route == "interpret":
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    shape = dict(T=140, H=4, P=64, G=2, N=128) if route == "interpret" else dict(T=50)
    x, dt, A, B, C, D = drawn(dtype=dtype, **shape)
    assert ssd_route(x, B, CHUNK if route == "interpret" else 16) == route
    chunk = CHUNK if route == "interpret" else 16
    f32 = jnp.float32
    bare, back = jax.vjp(lambda *a: ssd_chunked(*a, chunk=chunk), x, dt, A, B, C)
    full, back_full = jax.vjp(lambda *a: ssd_chunked(*a, chunk=chunk), x, dt, A, B, C, D)
    zero = ssd_chunked(x, dt, A, B, C, jnp.zeros_like(D), chunk=chunk)
    assert bare.dtype == full.dtype == dtype
    np.testing.assert_array_equal(bare.astype(f32), zero.astype(f32))
    skip = D[:, None] * x.astype(f32)
    tol = 1e-5 if dtype == f32 else 2e-2
    np.testing.assert_allclose(full.astype(f32), bare.astype(f32) + skip, rtol=tol, atol=tol)
    push = jnp.ones_like(bare)
    got, want = back(push), back_full(push)
    np.testing.assert_allclose(got[0].astype(f32) + D[:, None] * push.astype(f32),
                               want[0].astype(f32), rtol=tol, atol=tol)
    for a, b in zip(got[1:], want[1:5]):
        np.testing.assert_allclose(a.astype(f32), b.astype(f32), rtol=tol, atol=tol)


def test_route_and_chunk_count(monkeypatch):
    x, _, _, B, *_ = drawn(8)
    assert ssd_route(x, B) == "xla"
    wide = lambda **kw: [drawn(8, **{"P": 64, "N": 128, **kw})[i] for i in (0, 3)]
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    assert ssd_route(*wide()) == ssd_route(*wide(dtype=jnp.bfloat16)) == "interpret"
    assert ssd_route(*wide(), chunk=64) == "xla"                  # another chunk
    assert ssd_route(*wide(N=64)) == ssd_route(*wide(P=32, H=2, G=2)) == "xla"
    assert ssd_route(x, B) == "xla"                               # narrow heads
    monkeypatch.delenv("SXT_FUSED_INTERPRET")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd_route(*wide()) == "pallas"
    assert (ssd_chunks(8192), ssd_chunks(8193), ssd_chunks(50, 16)) == (64, 65, 4)
    assert CHUNK == 128
    with pytest.raises(ValueError, match="do not divide"):
        ssd_chunked(*drawn(8, H=4, G=3))
