#!/usr/bin/env python
"""Sweep remat policy x batch size for the north-star config (#2) on the
real chip, one candidate per subprocess (an OOM or Mosaic failure must not
kill the sweep). Prints one JSON line per candidate and a final WINNER line.

Usage: python scripts/tune_config2.py [--quick]
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CANDIDATES = [
    # (remat_policy, batch_size, seq_len, env)
    ("nothing_saveable", 8, 4096, {}),      # current bench default (baseline)
    ("save_attn_seams", 8, 4096, {}),
    ("save_ffn", 8, 4096, {}),
    ("save_ffn", 4, 4096, {}),
    ("save_attn_seams", 16, 4096, {}),
    # attention-BACKWARD block sweep (VERDICT r3 #3: an unexplored axis —
    # the dkv/dq passes hold more VMEM residents than forward)
    ("nothing_saveable", 8, 4096, {"SXT_ATTN_BLOCK_BWD": "512"}),
    ("nothing_saveable", 8, 4096, {"SXT_ATTN_BLOCK_BWD": "256"}),
    ("save_attn_seams", 8, 4096, {"SXT_ATTN_BLOCK_BWD": "512"}),
    # forward block x bwd block interaction
    ("nothing_saveable", 8, 4096, {"SXT_ATTN_BLOCK": "512",
                                   "SXT_ATTN_BLOCK_BWD": "512"}),
    # round-5 profile insight: the 6N·tok MFU formula bills neither the
    # quadratic attention matmuls nor remat recompute — at bs8 seq4096
    # nothing_saveable the chip executes ~1.9x the billed FLOPs (~64%
    # real utilization). Shorter seq and no remat convert that unbilled
    # work into billed tokens/s:
    ("nothing_saveable", 16, 2048, {}),
    ("save_attn_seams", 16, 2048, {}),
    ("none", 4, 2048, {}),          # no remat at all (fits: ~6GB acts)
    ("none", 8, 2048, {}),
    ("none", 4, 4096, {}),
]


def run_one(policy: str, bs: int, seq: int) -> dict:
    import dataclasses

    import jax

    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from bench import bench_train, chip_peak_flops, hbm_bytes, pick_config2
    from shuffle_exchange_tpu.models import Transformer

    dev = jax.devices()[0]
    peak = chip_peak_flops(dev)
    name, mcfg = pick_config2(hbm_bytes(dev))
    mcfg = dataclasses.replace(mcfg, remat=(policy != "none"),
                               remat_policy=(policy if policy != "none"
                                             else "nothing_saveable"),
                               max_seq_len=seq)
    cfg = {
        "train_batch_size": bs,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9,
    }
    row = bench_train(f"{name} z3 {policy} bs{bs}", Transformer(mcfg), cfg,
                      batch_size=bs, seq_len=seq, steps=8, warmup=2,
                      peak_flops=peak, n_chips=1)
    return row


def main():
    if len(sys.argv) >= 5 and sys.argv[1] == "--one":
        policy, bs, seq = sys.argv[2], int(sys.argv[3]), int(sys.argv[4])
        row = run_one(policy, bs, seq)
        print("TUNE_ROW " + json.dumps(row), flush=True)
        return

    cands = CANDIDATES[:3] if "--quick" in sys.argv else CANDIDATES
    best = None
    for policy, bs, seq, env_extra in cands:
        t0 = time.time()
        try:
            env = dict(os.environ, **env_extra)
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 policy, str(bs), str(seq)],
                # compiles land in the persistent cache, so only the first
                # visit to a program pays for it
                capture_output=True, text=True, timeout=1800, env=env)
            line = next((l for l in reversed(proc.stdout.splitlines())
                         if l.startswith("TUNE_ROW ")), None)
            if proc.returncode == 0 and line:
                row = json.loads(line[len("TUNE_ROW "):])
                row["wall_s"] = round(time.time() - t0, 1)
                if env_extra:
                    row["env"] = env_extra
                print(json.dumps(row), flush=True)
                if best is None or row["tokens_per_sec_chip"] > best["tokens_per_sec_chip"]:
                    best = row
            else:
                tail = " ".join((proc.stderr or proc.stdout).split())[-200:]
                print(json.dumps({"config": f"{policy} bs{bs}", "env": env_extra,
                                  "error": tail}), flush=True)
        except subprocess.TimeoutExpired:
            print(json.dumps({"config": f"{policy} bs{bs}", "env": env_extra,
                              "error": "timeout 1800s"}), flush=True)
    print("WINNER " + json.dumps(best), flush=True)


if __name__ == "__main__":
    main()
