"""On-chip throughput: Pallas flash_attention_lse hop kernel vs the jnp
chunked online-softmax hop (the two ring-attention inner loops), at long
context on a single chip.

This is the single-chip measurable core of VERDICT r4 #5's "ring-vs-Ulysses
tokens/s at seq >= 32k": a ring step is sp sequential hops of exactly this
compute, so the hop speedup bounds the ring speedup. The true multi-chip
ring-vs-Ulysses comparison additionally needs a live seq axis (>= 2 chips)
— run it on a pod slice when one is available (`mesh: {seq: N}` with
`sp_attention: ring|ulysses` through the engine).

Writes one JSON line per config to stdout.
"""

import json
import sys
import time

sys.path.insert(0, ".")


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shuffle_exchange_tpu.ops.alibi_attention import flash_attention_lse

    rng = np.random.default_rng(0)
    for T, H, D in ((8192, 8, 128), (32768, 4, 128)):
        B = 1
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)

        def kernel_hop(q, k, v):
            out, lse = flash_attention_lse(q, k, v, True, False)
            return out

        def jnp_hop(q, k, v, ck=1024):
            # the pre-round-5 ring hop: chunked online softmax in jnp
            scale = D ** -0.5
            q32 = q.astype(jnp.float32) * scale
            q_pos = jnp.arange(T)
            acc = jnp.zeros((B, H, T, D), jnp.float32)
            m = jnp.full((B, H, T), -jnp.inf, jnp.float32)
            l = jnp.zeros((B, H, T), jnp.float32)

            def body(c, ci):
                acc, m_run, l_run = c
                ks = jax.lax.dynamic_slice_in_dim(k, ci * ck, ck, 1)
                vs = jax.lax.dynamic_slice_in_dim(v, ci * ck, ck, 1)
                logits = jnp.einsum("bthd,bshd->bhts", q32,
                                    ks.astype(jnp.float32))
                kv_pos = ci * ck + jnp.arange(ck)
                mask = q_pos[:, None] >= kv_pos[None, :]
                logits = jnp.where(mask[None, None], logits, -jnp.inf)
                m_blk = jnp.max(logits, -1)
                m_new = jnp.maximum(m_run, m_blk)
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.where(jnp.isfinite(logits),
                              jnp.exp(logits - m_safe[..., None]), 0.0)
                corr = jnp.where(jnp.isfinite(m_run),
                                 jnp.exp(m_run - m_safe), 0.0)
                l_new = l_run * corr + p.sum(-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bhts,bshd->bhtd", p, vs.astype(jnp.float32))
                return (acc_new, m_new, l_new), None

            (acc, m, l), _ = jax.lax.scan(body, (acc, m, l),
                                          jnp.arange(T // ck))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            return out.transpose(0, 2, 1, 3).astype(q.dtype)

        sync = jax.block_until_ready

        for name, fn in (("kernel", kernel_hop), ("jnp-chunk", jnp_hop)):
            f = jax.jit(fn)
            sync(f(q, k, v))
            n = 5
            t0 = time.perf_counter()
            for _ in range(n):
                o = f(q, k, v)
            sync(o)
            dt = (time.perf_counter() - t0) / n
            # causal flops: 2 matmuls * B*H*T^2/2*D MACs * 2 flops
            flops = 2 * 2 * B * H * (T * T / 2) * D
            print(json.dumps({
                "bench": "ring_hop", "impl": name, "seq": T, "heads": H,
                "ms": round(dt * 1e3, 2),
                "tflops": round(flops / dt / 1e12, 2),
                "tok_per_s": round(B * T / dt, 1)}))


if __name__ == "__main__":
    main()
