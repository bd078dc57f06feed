"""Where ``olmoe-train``'s ``loss_tol``, ``route_tol`` and ``grad_tol`` come
from: the reference (``chipbench/reference_olmoe.py``) on the cell's own first
batch and weights, computed in a lower precision and held against itself in
float32, exactly as the driver holds the program against it. Run on the chip
by hand when a tolerance is in question; no cell and no check runs it:

    python chipbench/olmoe_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``grad_gap``
and the leaf it is on):
  bf16          what the configuration states: weights and activations in
                bf16, the router, norms, softmaxes and the loss in float32.
                The band a sound program lies in: the tolerances sit above it
  bf16_router   the same with the router's matmul in bf16 too: the nearest
                precision below the stated one for the routing. Must come out
                above ``route_tol``
  fp8_experts   the same as bf16 with the expert matmuls' operands rounded to
                e4m3 (4 exponent, 3 mantissa bits) under a per-tensor scale: the nearest precision
                below for the experts. Must come out above ``loss_tol`` or
                ``grad_tol``
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_moe as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402


def variants() -> dict:
    """{name: the reference's functions to replace while tracing}."""
    import functools

    import jax
    import jax.numpy as jnp

    from chipbench import reference_olmoe as ref

    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=jnp.bfloat16)}

    # a lower precision is forced with lax.reduce_precision: the chip's
    # compiler drops a pair of converts that only loses precision
    def route_bf16(w, prefix, y, cfg):
        to_bf16 = lambda x: jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)
        logits = to_bf16(to_bf16(y) @ to_bf16(w[prefix + "gate.weight"]).T)
        p = jax.nn.softmax(logits, axis=-1)
        weight, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
        return p, chosen.astype(jnp.int32), weight

    plain_linear, plain_experts = ref.linear, ref.experts

    def fp8(x):
        # 4 exponent and 3 mantissa bits (largest finite value 240) under a
        # per-tensor scale; the gradient passes straight through
        scale = (jnp.max(jnp.abs(x)).astype(jnp.float32) / 240.0).astype(x.dtype)
        rounded = jax.lax.reduce_precision(x / scale, 4, 3) * scale
        return x + jax.lax.stop_gradient(rounded - x)

    def experts_fp8(w, prefix, y, cfg):
        ref.linear = lambda x, m: plain_linear(fp8(x), fp8(m.astype(x.dtype)))
        try:
            return plain_experts(w, prefix, y, cfg)
        finally:
            ref.linear = plain_linear

    return {"float32": {}, "bf16": bf16,
            "bf16_router": {**bf16, "route": route_bf16},
            "fp8_experts": {**bf16, "experts": experts_fp8}}


def measure(cell: dict, seeds, names, rehearsal=None) -> list:
    """One record per (variant, seed): the variant against float32."""
    import jax.numpy as jnp

    from chipbench import reference_olmoe as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants()
    exact, out = {}, []
    for name in ["float32"] + [n for n in names if n != "float32"]:
        plain = {k: getattr(ref, k) for k in every[name]}
        for k, fn in every[name].items():
            setattr(ref, k, fn)
        try:
            program = driver.reference_program(src)
            for seed in seeds:
                ids = next(batches(mcfg.vocab_size, int(traffic["batch_per_chip"]),
                                   int(traffic["seq"]), seed))["input_ids"]
                weights = driver.to_source_names(
                    driver.initial_params(model, harness.seed32(seed)), src)
                got = driver.reference_first_step(program, weights, jnp.asarray(ids))
                del weights
                base = exact.setdefault(seed, got)       # float32 comes first
                gaps = driver.grad_gaps(got.pop("grads") if got is not base
                                        else got["grads"], base["grads"])
                worst = max(gaps, key=gaps.get)
                out.append({"variant": name, "seed": seed, "loss": got["loss"],
                            "loss_gap": abs(got["loss"] - base["loss"]),
                            "route_gap": driver.route_gap(got["expert_tokens"],
                                                          base["expert_tokens"]),
                            "grad_gap": gaps[worst], "grad_gap_leaf": worst,
                            "grad_gaps": gaps})
        finally:
            for k, fn in plain.items():
                setattr(ref, k, fn)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["bf16", "bf16_router", "fp8_experts"])
    args = ap.parse_args(argv)
    harness.cache_programs()
    for record in measure(harness.load_cell("olmoe-train"), args.seeds,
                          args.variants):
        harness.emit(phase="band", **record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
