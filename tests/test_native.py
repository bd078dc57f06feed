"""Native runtime library: build, aio round-trips, CPU optimizer parity,
packbits. Parity strategy follows SURVEY.md §4(b): native kernels are
compared against independent references (NumPy fallbacks + optax)."""

import os

import numpy as np
import pytest

from shuffle_exchange_tpu.ops.native import (AsyncIOEngine, adagrad_step,
                                             adam_step, lamb_step, lion_step,
                                             native_available, packbits,
                                             unpackbits)
from shuffle_exchange_tpu.ops.native import cpu_optimizer as cpuopt


def test_native_builds():
    # The image ships g++; the native library must actually build here.
    assert native_available()


def test_native_library_is_named_by_content_and_built_portably(
        tmp_path, monkeypatch):
    """The tree reaches the chip machine as a copy: mtimes are reset and a
    library built for this CPU would be loaded on another. So the build is
    portable (no -march=native) and staleness is a question of content: the
    library's name carries the digest of sources + flags."""
    import shutil
    import time

    from shuffle_exchange_tpu.ops.native import builder

    assert not any("march" in flag for flag in builder._CXX)
    src = tmp_path / "csrc"
    shutil.copytree(builder.CSRC_DIR, src,
                    ignore=shutil.ignore_patterns("*.so"))
    monkeypatch.setattr(builder, "CSRC_DIR", str(src))
    monkeypatch.delenv("SXT_NATIVE_CACHE", raising=False)
    first = builder._compile()
    assert first and os.path.dirname(first) == str(src)
    built = os.path.getmtime(first)
    # sources that look NEWER than the library (a fresh copy of the tree)
    # are still the same sources: no rebuild
    later = time.time() + 3600
    for name in builder._SOURCES:
        os.utime(src / name, (later, later))
    assert builder._compile() == first and os.path.getmtime(first) == built
    # other content is another library; the stale one is simply not it
    with open(src / "packbits.cc", "a") as f:
        f.write("// edited\n")
    second = builder._compile()
    assert second and second != first and os.path.exists(first)


# ---------------------------------------------------------------------------
# aio
# ---------------------------------------------------------------------------


def test_aio_write_read_roundtrip(tmp_path):
    eng = AsyncIOEngine(num_threads=2)
    rng = np.random.default_rng(0)
    arrays = [rng.standard_normal(n).astype(np.float32) for n in (17, 1024, 100_003)]
    paths = [str(tmp_path / f"a{i}.bin") for i in range(len(arrays))]
    reqs = [eng.submit_write(p, a) for p, a in zip(paths, arrays)]
    for r, a in zip(reqs, arrays):
        assert eng.wait(r) == a.nbytes
    outs = [np.empty_like(a) for a in arrays]
    reqs = [eng.submit_read(p, o) for p, o in zip(paths, outs)]
    eng.wait_all()
    for a, o in zip(arrays, outs):
        np.testing.assert_array_equal(a, o)
    eng.close()


def test_aio_offset_io(tmp_path):
    path = str(tmp_path / "seg.bin")
    with AsyncIOEngine(num_threads=1) as eng:
        a = np.arange(64, dtype=np.float32)
        b = np.arange(64, 128, dtype=np.float32)
        eng.wait(eng.submit_write(path, a, offset=0))
        eng.wait(eng.submit_write(path, b, offset=a.nbytes))
        out = np.empty(128, dtype=np.float32)
        eng.wait(eng.submit_read(path, out))
    np.testing.assert_array_equal(out, np.arange(128, dtype=np.float32))


def test_aio_read_error(tmp_path):
    eng = AsyncIOEngine(num_threads=1)
    if not eng.native:
        pytest.skip("native aio unavailable")
    buf = np.empty(8, dtype=np.float32)
    req = eng.submit_read(str(tmp_path / "missing.bin"), buf)
    with pytest.raises(OSError):
        eng.wait(req)
    eng.close()


# ---------------------------------------------------------------------------
# CPU optimizers: native vs numpy fallback vs optax
# ---------------------------------------------------------------------------


def _numpy_ref(step_fn, n=1337, steps=3, **kw):
    """Run the same trajectory through the native path and the NumPy path."""
    rng = np.random.default_rng(1)
    p0 = rng.standard_normal(n).astype(np.float32)
    grads = [rng.standard_normal(n).astype(np.float32) for _ in range(steps)]
    return p0, grads


def _run_adam(native: bool, p0, grads, **kw):
    import shuffle_exchange_tpu.ops.native.builder as b

    p = p0.copy()
    m = np.zeros_like(p)
    v = np.zeros_like(p)
    bf16 = np.empty(p.size, dtype=np.uint16)
    saved = b._LIB, b._TRIED
    try:
        if not native:
            b._LIB, b._TRIED = None, True
        for i, g in enumerate(grads):
            adam_step(p, m, v, g, lr=1e-2, step=i + 1, weight_decay=0.01, bf16_out=bf16, **kw)
    finally:
        b._LIB, b._TRIED = saved
    return p, m, v, bf16


@pytest.mark.parametrize("adamw", [True, False])
def test_adam_native_matches_numpy(adamw):
    if not native_available():
        pytest.skip("no native lib")
    p0, grads = _numpy_ref(adam_step)
    pn, mn, vn, bf16n = _run_adam(True, p0, grads, adamw=adamw)
    pf, mf, vf, bf16f = _run_adam(False, p0, grads, adamw=adamw)
    # fp32 reassociation/contraction noise only.
    np.testing.assert_allclose(pn, pf, rtol=1e-4, atol=5e-7)
    np.testing.assert_allclose(vn, vf, rtol=1e-4, atol=5e-7)
    # 1-ulp fp32 differences flip bf16 rounding only at half-way points.
    assert np.mean(bf16n != bf16f) < 0.01


def test_adam_matches_optax():
    import jax
    import jax.numpy as jnp
    import optax

    p0, grads = _numpy_ref(adam_step, n=257)
    p, m, v = p0.copy(), np.zeros_like(p0), np.zeros_like(p0)
    for i, g in enumerate(grads):
        adam_step(p, m, v, g, lr=1e-2, weight_decay=0.0, step=i + 1, adamw=False)

    tx = optax.adam(1e-2)
    jp = jnp.asarray(p0)
    state = tx.init(jp)
    for g in grads:
        updates, state = tx.update(jnp.asarray(g), state, jp)
        jp = optax.apply_updates(jp, updates)
    np.testing.assert_allclose(p, np.asarray(jp), rtol=2e-5, atol=2e-6)


def test_lion_and_adagrad_and_lamb_run():
    rng = np.random.default_rng(2)
    n = 513
    g = rng.standard_normal(n).astype(np.float32)
    p = rng.standard_normal(n).astype(np.float32)
    p1, m1 = p.copy(), np.zeros(n, np.float32)
    lion_step(p1, m1, g, lr=1e-3, weight_decay=0.1)
    assert not np.allclose(p1, p)
    p2, v2 = p.copy(), np.zeros(n, np.float32)
    adagrad_step(p2, v2, g, lr=1e-2)
    assert not np.allclose(p2, p)
    p3, m3, v3 = p.copy(), np.zeros(n, np.float32), np.zeros(n, np.float32)
    lamb_step(p3, m3, v3, g, lr=1e-2, step=1)
    assert np.isfinite(p3).all() and not np.allclose(p3, p)


def test_lamb_native_matches_numpy():
    if not native_available():
        pytest.skip("no native lib")
    import shuffle_exchange_tpu.ops.native.builder as b

    rng = np.random.default_rng(3)
    n = 2049
    p0 = rng.standard_normal(n).astype(np.float32)
    g = rng.standard_normal(n).astype(np.float32)

    def run(native):
        p, m, v = p0.copy(), np.zeros(n, np.float32), np.zeros(n, np.float32)
        saved = b._LIB, b._TRIED
        try:
            if not native:
                b._LIB, b._TRIED = None, True
            lamb_step(p, m, v, g, lr=1e-2, weight_decay=0.01, step=1)
        finally:
            b._LIB, b._TRIED = saved
        return p

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5, atol=1e-6)


def test_bf16_mirror_matches_jax_cast():
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    p = rng.standard_normal(301).astype(np.float32)
    bf16 = np.empty(p.size, dtype=np.uint16)
    cpuopt._as_bf16_bits(p, bf16)
    expect = np.asarray(jnp.asarray(p).astype(jnp.bfloat16)).view(np.uint16)
    np.testing.assert_array_equal(bf16, expect)
    if native_available():
        m = np.zeros_like(p)
        v = np.zeros_like(p)
        bf16n = np.empty(p.size, dtype=np.uint16)
        pn = p.copy()
        adam_step(pn, m, v, np.zeros_like(p), lr=0.0, step=1, bf16_out=bf16n)
        expect2 = np.asarray(jnp.asarray(pn).astype(jnp.bfloat16)).view(np.uint16)
        np.testing.assert_array_equal(bf16n, expect2)


# ---------------------------------------------------------------------------
# packbits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 7, 8, 9, 1024, 4097])
def test_packbits_roundtrip(n):
    rng = np.random.default_rng(5)
    x = rng.standard_normal(n).astype(np.float32)
    packed = packbits(x)
    assert packed.size == (n + 7) // 8
    out = unpackbits(packed, n, scale=2.5)
    np.testing.assert_array_equal(np.sign(out), np.where(x >= 0, 1.0, -1.0))
    np.testing.assert_allclose(np.abs(out), 2.5)


def test_packbits_matches_numpy():
    rng = np.random.default_rng(6)
    x = rng.standard_normal(123).astype(np.float32)
    np.testing.assert_array_equal(packbits(x), np.packbits(x >= 0, bitorder="little"))
