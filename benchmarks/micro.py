#!/usr/bin/env python
"""Micro-benchmarks: matmul roofline, collective bandwidth, kernel sweeps.

The reference ships no benchmarks/ (SURVEY §6); this harness is the
framework's own perf evidence. Timing uses the bench.py discipline: a
dependency chain of iterations with ONE ``block_until_ready`` at the end.

    python benchmarks/micro.py [matmul|collectives|attention|all]

On a CPU-mesh box the collective sweep still runs (8 virtual devices;
numbers are only meaningful relative to each other); matmul/attention
need the real chip to say anything about the hardware.
"""

import sys
import time

import numpy as np


def _sync(x) -> None:
    import jax

    jax.block_until_ready(x)


def _timeit(fn, *args, iters: int = 10) -> float:
    """Median of 3: chain `iters` calls, sync once; returns sec/call."""
    out = fn(*args)
    _sync(out)  # compile + warm
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        x = args[0]
        for _ in range(iters):
            out = fn(x, *args[1:])
            x = out if x.shape == out.shape and x.dtype == out.dtype else x
        _sync(out)
        best.append((time.perf_counter() - t0) / iters)
    return sorted(best)[1]


def bench_matmul():
    """bf16 matmul roofline ladder."""
    import jax
    import jax.numpy as jnp

    print("== matmul roofline (bf16) ==")
    for n in (1024, 2048, 4096, 8192):
        a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)), jnp.bfloat16)
        b = jnp.asarray(np.random.default_rng(1).standard_normal((n, n)), jnp.bfloat16) * (n ** -0.5)
        f = jax.jit(lambda a, b: (a @ b).astype(jnp.bfloat16))
        dt = _timeit(lambda a: f(a, b), a)
        print(f"  {n:5d}^3: {2 * n**3 / dt / 1e12:8.1f} TFLOP/s  ({dt*1e3:.2f} ms)")


def bench_collectives():
    """psum / all_gather / reduce_scatter / all_to_all / ppermute bandwidth
    over the mesh (ICI on a pod; loopback on the virtual CPU mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices())
    n = len(devs)
    if n < 2:
        print("== collectives: single device; skipped ==")
        return
    mesh = Mesh(devs, ("x",))
    # virtual CPU mesh on one core: big shards stall the 8-thread
    # rendezvous; keep it small there
    mb = 64 if jax.default_backend() == "tpu" else 4
    elems = mb * 1024 * 1024 // 4
    x = jnp.ones((n, elems), jnp.float32)
    print(f"== collectives over {n} devices ({mb} MiB/shard) ==")

    cases = {
        "psum": (lambda t: jax.lax.psum(t, "x"), P("x"), P("x")),
        "all_gather": (lambda t: jax.lax.all_gather(t, "x", axis=0, tiled=True),
                       P("x"), P()),
        "reduce_scatter": (lambda t: jax.lax.psum_scatter(
            t, "x", scatter_dimension=0, tiled=True), P(), P("x")),
        "ppermute": (lambda t: jax.lax.ppermute(
            t, "x", [(i, (i + 1) % n) for i in range(n)]), P("x"), P("x")),
    }
    for name, (op, in_s, out_s) in cases.items():
        # check_vma off: the replication of gathered outputs can't be
        # statically inferred (same setting the engine uses)
        f = jax.jit(jax.shard_map(lambda t: op(t) * 1.0, mesh=mesh,
                                  in_specs=in_s, out_specs=out_s,
                                  check_vma=False))
        try:
            dt = _timeit(lambda t: jnp.sum(f(t)).reshape(1), x, iters=5)
            gbps = mb / 1024 * (n - 1) / dt  # ring-algorithm per-link estimate
            print(f"  {name:15s}: {dt*1e3:8.2f} ms   (~{gbps:6.1f} GiB/s/link est.)")
        except Exception as e:
            print(f"  {name:15s}: failed ({type(e).__name__})")


def bench_attention():
    """flash (MHA) vs splash (GQA) vs reference at training shapes."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import flash_attention

    print("== attention (B=4, T=4096, D=128) ==")
    rng = np.random.default_rng(0)
    for H, KV, label in ((16, 16, "mha"), (16, 4, "gqa-4:1")):
        q = jnp.asarray(rng.standard_normal((4, 4096, H, 128)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((4, 4096, KV, 128)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((4, 4096, KV, 128)), jnp.bfloat16)
        for impl in ("pallas", "reference"):
            try:
                f = jax.jit(lambda q, k, v: flash_attention(q, k, v, impl=impl))
                dt = _timeit(lambda q: f(q, k, v), q, iters=5)
                flops = 4 * 4 * 4096 * 4096 * H * 128 / 2  # causal halves it
                print(f"  {label} {impl:10s}: {dt*1e3:8.2f} ms  "
                      f"({flops / dt / 1e12:6.1f} TFLOP/s)")
            except Exception as e:
                print(f"  {label} {impl:10s}: failed ({type(e).__name__})")


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    import jax

    print(f"backend: {jax.default_backend()}, devices: {len(jax.devices())}")
    if which in ("matmul", "all"):
        bench_matmul()
    if which in ("collectives", "all"):
        bench_collectives()
    if which in ("attention", "all"):
        bench_attention()


if __name__ == "__main__":
    main()
