#!/usr/bin/env python
"""On-chip MoE implementation shootout for the config-3 bench shape:
"capacity" (round-5 INDEX dispatch: slot scatter + row gathers) vs
"capacity_einsum" (the GShard dense one-hot einsums, the r2-r4 path) vs
"ragged" (dropless Pallas megablox grouped GEMM), all under the scanned
layer stack.

VERDICT r4 next #2 asked for the MoE row to come from the on-chip
megablox dropless path if it wins. Measured round 5 (bs8x2048, v5e):
index 23.1% / einsum 12.5% / megablox-under-scan 5.3% active-param MFU.

Prints one JSON line per impl and a WINNER line.
"""
import dataclasses
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_one(impl: str) -> dict:
    import jax

    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from bench import bench_train, chip_peak_flops
    from shuffle_exchange_tpu.models import Transformer, TransformerConfig

    dev = jax.devices()[0]
    peak = chip_peak_flops(dev)
    mcfg = TransformerConfig(
        vocab_size=32768, d_model=1024, n_layers=8, n_heads=8,
        n_kv_heads=2, max_seq_len=2048, activation="swiglu",
        norm="rmsnorm", position="rope", tie_embeddings=True,
        n_experts=8, moe_top_k=2, moe_impl=impl, remat=True,
        remat_policy="nothing_saveable")
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10**9,
    }
    return bench_train(f"moe impl={impl}", Transformer(mcfg), cfg,
                       batch_size=8, seq_len=2048, steps=10, warmup=3,
                       peak_flops=peak, n_chips=1)


def main():
    if len(sys.argv) > 1:          # child: one impl per process (an OOM or
        row = run_one(sys.argv[1])  # Mosaic failure must not kill the sweep)
        print("ROW " + json.dumps(row), flush=True)
        return
    best = None
    for impl in ("capacity", "capacity_einsum", "ragged"):
        try:
            p = subprocess.run([sys.executable, os.path.abspath(__file__), impl],
                               capture_output=True, text=True, timeout=1800)
        except subprocess.TimeoutExpired:
            print(json.dumps({"impl": impl, "error": "timeout after 1800s"}))
            continue
        line = next((l for l in p.stdout.splitlines()
                     if l.startswith("ROW ")), None)
        if line is None:
            print(json.dumps({"impl": impl, "error": p.stderr[-300:]}))
            continue
        row = json.loads(line[len("ROW "):])
        row["impl"] = impl
        print(json.dumps(row), flush=True)
        if best is None or row["tokens_per_sec_chip"] > best["tokens_per_sec_chip"]:
            best = row
    if best:
        print("WINNER " + json.dumps({"impl": best["impl"],
                                      "tokens_per_sec_chip": best["tokens_per_sec_chip"],
                                      "mfu_pct": best["mfu_pct"]}))


if __name__ == "__main__":
    main()
