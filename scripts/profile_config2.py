#!/usr/bin/env python
"""Capture an XLA trace of one config-2 train step and print the top device
ops by total self-time (parsed from the profiler's trace.json.gz), so the
MFU ceiling can be attributed to actual kernels instead of guesses.

The trace-breakdown machinery (``collect_trace``, ``device_op_totals``,
``print_top_ops``) is shared with ``scripts/profile_config1.py``.

Usage: python scripts/profile_config2.py [policy] [bs] [seq]
"""
import dataclasses
import glob
import gzip
import json
import os
import sys
from collections import defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def collect_trace(logdir, step_fn):
    """Run ``step_fn`` under the XLA profiler; return the parsed trace dict
    (or None when no trace.json.gz landed)."""
    from shuffle_exchange_tpu.profiling import xla_trace

    os.makedirs(logdir, exist_ok=True)
    with xla_trace(logdir):
        step_fn()
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        print("no trace.json.gz found under", logdir)
        return None
    with gzip.open(paths[-1], "rt") as f:
        return json.load(f)


def device_op_totals(trace):
    """(total_us_by_op, count_by_op) over the device lanes of a trace.

    Device-lane complete events ("ph" == "X"); group by op name. TPU device
    PIDs are the ones whose process_name mentions TPU/device; when nothing
    matches (CPU runs), fall back to all pids."""
    pid_names = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev.get("args", {}).get("name", "")
    dev_pids = {pid for pid, n in pid_names.items()
                if "TPU" in n or "/device" in n.lower() or "XLA" in n}
    total = defaultdict(float)
    count = defaultdict(int)
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("pid") not in dev_pids:
            continue
        name_ = ev.get("name", "?")
        total[name_] += ev.get("dur", 0.0)
        count[name_] += 1
    if not total:
        print("process names seen:", sorted(set(pid_names.values()))[:20])
        print("no device events matched; dumping top events from ALL pids")
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") == "X":
                total[ev.get("name", "?")] += ev.get("dur", 0.0)
                count[ev.get("name", "?")] += 1
    return total, count


def print_top_ops(total, count, header, top=25):
    step_us = sum(total.values())
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    print(f"\n== {header}; total device-op time {step_us/1e3:.1f} ms ==")
    for name_, us in rows:
        print(f"{us/1e3:9.2f} ms  {100*us/max(step_us,1):5.1f}%  "
              f"x{count[name_]:<5d} {name_[:90]}")
    return step_us


def main():
    policy = sys.argv[1] if len(sys.argv) > 1 else "nothing_saveable"
    bs = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    seq = int(sys.argv[3]) if len(sys.argv) > 3 else 4096

    import numpy as np

    import jax

    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import shuffle_exchange_tpu as sxt
    from bench import hbm_bytes, host_sync, pick_config2
    from shuffle_exchange_tpu.models import Transformer

    name, mcfg = pick_config2(hbm_bytes(jax.devices()[0]))
    mcfg = dataclasses.replace(mcfg, remat=True, remat_policy=policy,
                               max_seq_len=seq)
    engine, *_ = sxt.initialize(model=Transformer(mcfg), config={
        "train_batch_size": bs,
        "optimizer": {"type": "FusedAdam", "params": {"lr": 3e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9,
    })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, mcfg.vocab_size,
                                       size=(bs, seq)).astype(np.int32)}
    for _ in range(2):
        host_sync(engine.train_batch(batch))

    trace = collect_trace(os.path.join(REPO, ".cache", "trace_config2"),
                          lambda: host_sync(engine.train_batch(batch)))
    if trace is None:
        return
    total, count = device_op_totals(trace)
    print_top_ops(total, count, f"top ops ({policy} bs{bs} seq{seq})")


if __name__ == "__main__":
    main()
