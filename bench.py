#!/usr/bin/env python
"""Benchmark driver: prints ONE JSON line with the headline metric.

ROADMAP S0 replaces this file with a workloads table and a runner; until
then it keeps the pre-PR-1 rows and this measurement discipline:

- **One chip, one process at a time.** The parent never imports JAX: the
  hardware probe and the calibration run in a child (``--calibrate``), then
  each config in its own child, one after the other. A chip belongs to one
  process; a parent that had touched JAX would keep its children off it.
- **No chip, no number.** Without a TPU, with a ``device_kind`` that is not
  in the peaks table, or without ``memory_stats()``, the probe fails and the
  bench exits non-zero. A failed calibration or config also exits non-zero,
  after the headline line has named the error.
- **Sync**: every timed region ends in ``block_until_ready`` on the result
  (``host_sync``), so the clock stops after the device does.
- **Calibration microbench**: a chain of bf16 matmuls of known FLOPs is
  timed with the same discipline. If the implied FLOP/s exceeds the chip's
  peak, timing is broken: the line is emitted with ``"valid": false`` and NO
  ``vs_baseline``.
- **MFU gate**: any config whose MFU exceeds 100% is marked invalid.
- **Throughput** is measured over a dependency chain (step N+1 consumes the
  donated state of step N) with a single final sync; **p50 step time** is
  measured with a sync after every step.

Configs benched (BASELINE.json):
  #1 GPT-2 125M ZeRO-1 bf16            (bring-up config, round-over-round)
  #2 Llama-3-style ZeRO-3 + fused Pallas Adam — north star. 8B does not fit
     one chip (8B * 14 B/param of bf16+master+adam state = 112 GB), so the
     largest ladder entry that fits this chip's HBM is used and labeled.
  #5 Paged serving (engine_v2): prefill + decode tokens/s.

Results for all configs are published into BASELINE.json["published"];
the printed headline line is config #2 when it ran, else #1.

vs_baseline: our MFU / 0.45 — the reference snapshot publishes no rigorous
numbers, so the denominator is the 45% MFU an H100 DeepSpeed run is assumed
to reach on the same model; MFU-normalizing makes the ratio chip-agnostic.
(ROADMAP retires this headline with S0.)
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# Hardware discovery
# ---------------------------------------------------------------------------

# Published per-chip peaks, keyed by a substring of ``device_kind`` with the
# spaces taken out ("TPU v5 lite" -> "tpuv5lite"): (bf16 dense FLOP/s, HBM
# bytes/s). Source: Google Cloud TPU documentation, the system-architecture
# page of each generation. A device that is not here is an error, never a
# default.
_PEAKS = {
    "v5p": (459e12, 2765e9),
    "v6e": (918e12, 1640e9), "v6lite": (918e12, 1640e9),
    "trillium": (918e12, 1640e9),
    "v4": (275e12, 1228e9),
    "v5e": (197e12, 819e9), "v5lite": (197e12, 819e9),
}


def _chip_peaks(dev):
    kind = dev.device_kind.lower().replace(" ", "")
    for key, peaks in _PEAKS.items():
        if key in kind:
            return peaks
    raise RuntimeError(
        f"no published peaks for device_kind {dev.device_kind!r} (platform "
        f"{dev.platform!r}): bench.py measures TPUs; add the chip to "
        f"bench._PEAKS with its source")


def chip_peak_flops(dev) -> float:
    """bf16 dense peak FLOP/s for the chip kind."""
    return _chip_peaks(dev)[0]


def chip_hbm_bandwidth(dev) -> float:
    """HBM bandwidth (bytes/s) for the chip kind — the denominator for the
    serving bandwidth-utilization figure (decode is weight-bandwidth
    bound)."""
    return _chip_peaks(dev)[1]


def hbm_bytes(dev) -> int:
    stats = dev.memory_stats()
    limit = (stats or {}).get("bytes_limit") or (stats or {}).get(
        "bytes_reservable_limit")
    if not limit:
        raise RuntimeError(
            f"{dev.device_kind!r} reports no memory limit through "
            f"memory_stats() ({stats!r}): the configs are sized from it")
    return int(limit)


# ---------------------------------------------------------------------------
# Timing primitives
# ---------------------------------------------------------------------------

def _short_err(e: BaseException) -> str:
    """One line, bounded — multi-KB XLA/Mosaic dumps would otherwise swamp
    the single-JSON-line contract."""
    msg = " ".join(str(e).split())
    return f"{type(e).__name__}: {msg[:300]}"


def host_sync(x) -> float:
    """Wait for the program that produced ``x``; return its first element."""
    import jax

    return float(np.asarray(jax.block_until_ready(x)).reshape(-1)[0])


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def calibrate(peak_flops: float):
    """Time a known-FLOPs bf16 matmul chain with the same sync discipline.

    Returns (achieved_flops_per_s, rtt_s, ok). ok=False means the
    measurement pipeline reports more FLOP/s than the chip can do -> timing
    is broken. The chain is ~17.6 TFLOP (>=90ms even at peak) so the
    dispatch+sync round trip (measured separately as rtt_s and reported)
    stays a small fraction of the measurement.
    """
    import jax
    import jax.numpy as jnp

    n, chain = 8192, 16

    @jax.jit
    def f(a, b):
        x = a
        for _ in range(chain):
            x = jnp.dot(x, b)
        return x.astype(jnp.float32).sum()

    @jax.jit
    def noop(a):
        return a + 1.0

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    # keep magnitudes ~1 through the chain so the sum stays finite
    b = jax.random.normal(key, (n, n), jnp.bfloat16) * (n ** -0.5)
    z = jnp.zeros((), jnp.float32)
    host_sync(f(a, b))  # compile + warm
    host_sync(noop(z))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        host_sync(noop(z))
        rtts.append(time.perf_counter() - t0)
    rtt = min(rtts)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        host_sync(f(a, b))
        times.append(time.perf_counter() - t0)
    best = min(times)
    achieved = 2.0 * n * n * n * chain / max(best - rtt, 1e-9)
    return achieved, rtt, achieved <= 1.05 * peak_flops


# ---------------------------------------------------------------------------
# Config #2 model ladder (largest Llama-3-style model that fits one chip)
# ---------------------------------------------------------------------------

def _param_count(cfg) -> int:
    d, ff = cfg.d_model, cfg.ff_dim
    kv_dim = cfg.kv_heads * cfg.head_dim
    attn = d * d + 2 * d * kv_dim + d * d
    mlp = 3 * d * ff if cfg.activation == "swiglu" else 2 * d * ff
    per_layer = attn + mlp + 2 * d
    embed = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    return cfg.n_layers * per_layer + embed + d


def pick_config2(hbm: int):
    """Largest ladder entry with params*14B (bf16 fwd + fp32 master + adam
    m/v) under 55% of HBM (activations under remat take the rest)."""
    from shuffle_exchange_tpu.models import TransformerConfig, llama3_8b

    ladder = [
        ("llama3-8b", llama3_8b()),
        ("llama3-3b-style", TransformerConfig(
            vocab_size=128256, d_model=3072, n_layers=28, n_heads=24, n_kv_heads=8,
            d_ff=8192, max_seq_len=8192, activation="swiglu", norm="rmsnorm",
            position="rope", rope_theta=500000.0, tie_embeddings=False)),
        # Scaled entries keep the 8B HEAD GEOMETRY (head_dim 128, GQA group
        # 4) so the attention kernels measure the north-star's shapes:
        # Dh-64 scaling ran splash at ~18% MXU (25.5% MFU); Dh 128 / G 4
        # measured 35.3% MFU on the same d_model/layers (v5e, seq 4096).
        ("llama3-1b-style", TransformerConfig(
            vocab_size=128256, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=4,
            d_ff=8192, max_seq_len=8192, activation="swiglu", norm="rmsnorm",
            position="rope", rope_theta=500000.0, tie_embeddings=True)),
        ("llama-750m-style", TransformerConfig(
            vocab_size=32768, d_model=1536, n_layers=16, n_heads=12, n_kv_heads=3,
            max_seq_len=8192, activation="swiglu", norm="rmsnorm",
            position="rope", rope_theta=500000.0, tie_embeddings=True)),
        ("llama-350m-style", TransformerConfig(
            vocab_size=32768, d_model=1024, n_layers=16, n_heads=8, n_kv_heads=2,
            max_seq_len=8192, activation="swiglu", norm="rmsnorm",
            position="rope", rope_theta=500000.0, tie_embeddings=True)),
    ]
    budget = 0.55 * hbm
    for name, cfg in ladder:
        if 14 * _param_count(cfg) <= budget:
            return name, cfg
    return ladder[-1]


def host_offload_ladder_entry(toy: bool = False):
    """The host-offload-fitted ladder entry: ~1.7B params on a 16 GB chip.

    Resident training needs 14 B/param (bf16 fwd + fp32 master + adam m/v)
    — caps one chip at ~750M. The cpu offload tier keeps master+moments in
    host RAM (runtime/zero/host_optimizer.py) so the device holds only the
    2 B/param bf16 weights plus the fp32 grad transient (~6 B/param peak
    during the step) — a ~1.7B entry fits, where arithmetic intensity is
    higher and the remat tax relatively smaller (the ZeRO-Offload fit
    argument, Ren et al. 2021). ``offload_overlap`` runs the grad-D2H /
    host-Adam / param-H2D pipeline concurrently with step compute;
    ``save_flash_lse`` remat keeps the flash forward out of the backward
    recompute.

    Returns (name, model_cfg, ds_config, batch_size, seq_len). ``toy=True``
    is the CPU-runnable miniature of the SAME config shape, used by
    ``tests/test_bench_smoke.py`` so the entry cannot rot.
    """
    from shuffle_exchange_tpu.models import TransformerConfig

    ds = {
        "train_batch_size": 8,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1, "offload_optimizer": {
            "device": "cpu", "offload_overlap": True}},
        "steps_per_print": 10**9,
    }
    if toy:
        mcfg = TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=2, n_kv_heads=1,
            d_ff=256, max_seq_len=64, activation="swiglu", norm="rmsnorm",
            position="rope", rope_theta=500000.0, tie_embeddings=True,
            remat=True, remat_policy="save_flash_lse")
        # batch 8: divides the CI harness's 8 virtual CPU devices
        return ("host-offload-toy", mcfg, dict(ds, train_batch_size=8), 8, 64)
    # North-star head geometry (head_dim 128, GQA group 4); 24 layers x
    # d2048 x ff8192 + 128k vocab = ~1.72B params -> 3.4 GB bf16 resident.
    mcfg = TransformerConfig(
        vocab_size=128256, d_model=2048, n_layers=24, n_heads=16,
        n_kv_heads=4, d_ff=8192, max_seq_len=2048, activation="swiglu",
        norm="rmsnorm", position="rope", rope_theta=500000.0,
        tie_embeddings=True, remat=True, remat_policy="save_flash_lse")
    return ("llama-1.7b-host-offload", mcfg, ds, 8, 2048)


# ---------------------------------------------------------------------------
# Benches
# ---------------------------------------------------------------------------

def bench_train(label, model, ds_config, batch_size, seq_len, steps, warmup,
                peak_flops, n_chips, offload_budget=False):
    """For MoE models (model.config.n_experts > 0) the 6*N*T FLOPs model
    bills only the ACTIVATED expert params (top-k routing runs k/E of the
    expert FLOPs). ``offload_budget=True`` (host-offload configs) attaches
    the per-step time budget the engine's overlap pipeline publishes
    through the monitor: D2H grad wait / host fused-Adam / H2D dispatch."""
    import jax.tree_util as jtu

    import shuffle_exchange_tpu as sxt

    engine, *_ = sxt.initialize(model=model, config=ds_config)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, model.config.vocab_size,
                                       size=(batch_size, seq_len)).astype(np.int32)}

    for _ in range(warmup):
        host_sync(engine.train_batch(batch))

    # p50 step time: a sync after every step (includes one host round trip)
    per_step = []
    for _ in range(max(5, steps // 2)):
        t0 = time.perf_counter()
        host_sync(engine.train_batch(batch))
        per_step.append(time.perf_counter() - t0)
    p50 = sorted(per_step)[len(per_step) // 2]

    # throughput: donated-state dependency chain, single final sync
    t0 = time.perf_counter()
    last = None
    for _ in range(steps):
        last = engine.train_batch(batch)
    host_sync(last)
    total = time.perf_counter() - t0

    tokens_per_step = batch_size * (seq_len - 1)
    tps_chip = tokens_per_step * steps / total / n_chips
    if getattr(engine, "_host_opt", None) is not None:
        # cpu offload tier: master/moments live on host, not in state
        engine._join_host_update()   # land the in-flight overlapped step
        n_params = sum(int(p.size) for p in engine._host_opt.params)
        expert = 0
    else:
        master = engine.state.master
        n_params = sum(int(np.prod(l.shape)) for l in jtu.tree_leaves(master))
        expert = sum(int(np.prod(l.shape))
                     for name, l in master.get("layers", {}).items()
                     if name.startswith("moe_") and name != "moe_gate")
    if engine.ensemble:   # leading replica dim on every leaf
        n_params //= engine.replicas
        expert //= engine.replicas
    n_active = n_params
    mcfg = getattr(model, "config", None)
    if mcfg is not None and getattr(mcfg, "n_experts", 0) > 0:
        n_active = n_params - expert + expert * mcfg.moe_top_k // mcfg.n_experts
    mfu = 6.0 * n_active * tps_chip / peak_flops
    row = {
        "config": label,
        "params_m": round(n_params / 1e6, 1),
        "batch_size": batch_size,
        "seq_len": seq_len,
        "tokens_per_sec_chip": round(tps_chip, 1),
        "step_p50_ms": round(p50 * 1000, 2),
        "mfu_pct": round(mfu * 100, 2),
        "valid": bool(mfu <= 1.0),
        "unit": "tokens/s/chip",
    }
    if offload_budget:
        mm = engine.monitor.memory_monitor
        budget = {k: mm.latest(f"offload/{k}")
                  for k in ("d2h_wait_s", "host_adam_s", "h2d_dispatch_s",
                            "pipeline_s")}
        # D2H wait starts at dispatch, so it absorbs the device step's tail;
        # compute_s here is the step wall minus the post-grad pipeline
        # stages (host adam + h2d) — the overlapped portion of those is
        # exactly what the pipeline hides.
        budget["step_p50_s"] = round(p50, 4)
        budget["overlap"] = bool(getattr(engine, "_host_pipeline", None))
        row["offload_budget"] = budget
    return row


def _trace_record(seed, prompts, max_new, load, arrivals, capacity=None):
    """The reproducibility record every Poisson serving row returns
    (ISSUE 14): the seed regenerates the workload, the prompt lengths and
    arrival offsets audit what was actually offered, and an autotuner
    trial citing the same record is PAIRED with the row — same prompts,
    same arrivals, variance-controlled comparison. One shape everywhere:
    this wraps ``PoissonTrace.describe()``, the same record the
    serving_autotune row and the CLI trial logs emit."""
    from shuffle_exchange_tpu.autotuning import PoissonTrace

    return PoissonTrace(
        seed=int(seed), prompts=tuple(tuple(int(t) for t in p)
                                      for p in prompts),
        max_new=int(max_new), arrivals=tuple(float(a) for a in arrivals),
        load=load,
        capacity_tokens_per_sec=(float(capacity) if capacity else None),
    ).describe()


def serving_goodput_row(model, params, icfg, vocab, *, n_requests=24,
                        prompt_lo=64, prompt_hi=512, max_new=32,
                        load=2.0, seed=0):
    """Config-5 serving-goodput row (ISSUE 5): sustained tokens/s through
    the continuous-batching scheduler under a Poisson arrival trace.

    Two passes over the same request set on ONE engine: pass 1 submits
    everything up front — it warms the shape-bin ladder's programs and its
    sustained tokens/s is the scheduler's peak CAPACITY; pass 2 replays the
    requests as a Poisson process offered at ``load``x that capacity (the
    "heavy traffic" regime: arrivals outpace service, the queue stays
    nonempty, and sustained tokens/s measures what mixed prefill+decode
    ticks actually deliver under pressure, with TTFT/TPOT p50 showing the
    queueing cost). The row is seed-reproducible and returns its ``trace``
    (seed + prompt lengths + arrival offsets) so autotuner trials and
    later reruns can pair against the exact workload (ISSUE 14). Reused
    at toy size by tests/test_bench_smoke.py so the published bench
    config cannot rot on the CPU driver box."""
    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                InferenceEngineV2)

    rng = np.random.default_rng(seed)
    eng = InferenceEngineV2(model, params, icfg)
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]

    # throwaway pass: compiles the shape-bin ladder's programs so neither
    # measured pass carries JIT wall-time (same trace -> same shapes)
    ContinuousBatchingScheduler(eng).serve(prompts, max_new_tokens=max_new)
    warm = ContinuousBatchingScheduler(eng)
    warm.serve(prompts, max_new_tokens=max_new)
    cap = warm.stats()["sustained_tokens_per_sec"]

    span = n_requests * max_new / cap / load
    arrivals = poisson_arrivals(rng, n_requests, span)
    sched = ContinuousBatchingScheduler(eng)
    sched.serve(prompts, max_new_tokens=max_new, arrivals=arrivals)
    st = sched.stats()
    fills = sched.memory_monitor.values("serving/budget_fill")
    sv = icfg.serving
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cap),
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "token_budget": sv.token_budget,
        "max_running": sv.max_running,
        "chunk_bins": list(sv.bins()),
        "offered_load_x": load,
        "capacity_tokens_per_sec": round(cap, 1),
        "sustained_tokens_per_sec": round(st["sustained_tokens_per_sec"], 1),
        "ttft_p50_s": round(st["ttft_p50_s"], 4),
        "ttft_p95_s": round(st["ttft_p95_s"], 4),
        "tpot_p50_s": round(st["tpot_p50_s"], 4),
        "tpot_p95_s": round(st["tpot_p95_s"], 4),
        "budget_fill_mean": round(float(np.mean(fills)), 3),
        "ticks": st["ticks"],
        "preemptions": st["preemptions"],
        "compiled_programs": st["compiled_programs"],
        # random prompts share nothing, so this is None unless the icfg
        # opted into prefix_caching AND the trace repeats content — the
        # shared-system-prompt regime is measured by prefix_cache_row
        "prefix_hit_rate": st["prefix_cache"]["hit_rate"],
    }


def prefix_cache_row(model, params, icfg, vocab, *, n_requests=16,
                     sys_prompt_len=256, suffix_lo=16, suffix_hi=96,
                     max_new=32, load=2.0, seed=0):
    """Config-5 prefix-cache row (ISSUE 6): the SAME shared-system-prompt
    Poisson trace served twice — prefix_caching off, then on — on fresh
    engines of the same config. Production traffic is dominated by shared
    system prompts and multi-turn prefixes; with the cache on, every
    admission past the first reuses the committed system-prompt blocks
    (zero new allocations for the shared span) and prefills only its
    suffix, so TTFT falls and per-tick prefill spend shrinks. The row
    reports the hit-rate and the TTFT delta vs the no-cache path, and is
    seed-reproducible with its ``trace`` returned (ISSUE 14). Reused
    at toy size by tests/test_bench_smoke.py."""
    import dataclasses as _dc

    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                InferenceEngineV2)

    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, vocab, size=sys_prompt_len).tolist()
    prompts = [sys_prompt + rng.integers(
        1, vocab, size=int(n)).tolist()
        for n in rng.integers(suffix_lo, suffix_hi + 1, size=n_requests)]

    def run(prefix_caching):
        eng = InferenceEngineV2(
            model, params, _dc.replace(icfg, prefix_caching=prefix_caching))
        # throwaway pass: warm the shape-bin ladder so neither measured
        # pass carries JIT wall-time (same trace -> same shapes)
        ContinuousBatchingScheduler(eng).serve(prompts,
                                               max_new_tokens=max_new)
        cap = ContinuousBatchingScheduler(eng)
        cap.serve(prompts, max_new_tokens=max_new)
        return eng, cap.stats()

    eng_off, cold = run(False)
    # offered load calibrated on the NO-cache capacity, reused for both
    # traces so the comparison is at identical arrivals
    span = n_requests * max_new / cold["sustained_tokens_per_sec"] / load
    arrivals = poisson_arrivals(rng, n_requests, span)

    def trace(eng):
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=max_new,
                          arrivals=list(arrivals))
        return out, sched.stats()

    # the calibration engine IS the warmed no-cache engine — reuse it for
    # the measured pass instead of warming a fresh twin from scratch
    out_off, st_off = trace(eng_off)
    out_on, st_on = trace(run(True)[0])
    # cached vs uncached runs chunk prefill at different boundaries, so
    # under bf16 KV the tokens must match exactly; reported (not
    # asserted) because quantized kv_cache_dtype modes read chunk
    # boundaries back dequantized and greedy near-ties may flip
    mismatches = sum(out_on[u] != out_off[u] for u in out_on)
    hit = st_on["prefix_cache"]
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cold["sustained_tokens_per_sec"]),
        "n_requests": n_requests,
        "sys_prompt_tokens": sys_prompt_len,
        "suffix_tokens": [suffix_lo, suffix_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "kv_cache_dtype": icfg.kv_cache_dtype,
        # engine-cumulative over the warm + capacity + measured passes
        "prefix_hit_rate": round(hit["hit_rate"], 3),
        "prefix_hit_tokens": hit["hit_tokens"],
        "cow_copies": hit["cow_copies"],
        "token_mismatches_vs_no_cache": mismatches,
        "ttft_p50_s_no_cache": round(st_off["ttft_p50_s"], 4),
        "ttft_p50_s_cached": round(st_on["ttft_p50_s"], 4),
        "ttft_p50_delta_pct": round(
            100 * (1 - st_on["ttft_p50_s"] / st_off["ttft_p50_s"]), 1),
        "sustained_tokens_per_sec_no_cache": round(
            st_off["sustained_tokens_per_sec"], 1),
        "sustained_tokens_per_sec_cached": round(
            st_on["sustained_tokens_per_sec"], 1),
    }


def serving_fleet_row(model, params, icfg, vocab, *, n_requests=24,
                      prompt_lo=64, prompt_hi=512, max_new=32,
                      load=2.0, seed=0):
    """Config-5 serving-fleet row (ISSUE 7): the SAME Poisson trace served
    by a 1-replica and a 2-replica ``ReplicaRouter`` fleet, at arrivals
    calibrated on the single-replica capacity. The 2-replica fleet splits
    the queue across engines (placement by queue depth + KV pressure), so
    goodput should rise and the TTFT tails — queueing time, mostly — should
    fall; the row publishes both plus the speedup. Token parity with the
    1-replica serve is reported (greedy routing is token-identical under
    the scheduler contract). Reused at toy size by
    tests/test_bench_smoke.py so the published row cannot rot on CPU."""
    from shuffle_exchange_tpu.inference import InferenceEngineV2
    from shuffle_exchange_tpu.serving import ReplicaRouter

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]
    eng_a = InferenceEngineV2(model, params, icfg)
    eng_b = InferenceEngineV2(model, params, icfg)
    # throwaway pass per engine: warm each replica's shape-bin ladder so
    # no measured fleet carries JIT wall-time (same trace -> same shapes)
    ReplicaRouter([eng_a]).serve(prompts, max_new_tokens=max_new)
    ReplicaRouter([eng_b]).serve(prompts, max_new_tokens=max_new)
    # capacity: everything up front on ONE replica, arrivals calibrated on
    # it and reused for both fleets so the comparison is at identical load
    cap_router = ReplicaRouter([eng_a])
    cap_router.serve(prompts, max_new_tokens=max_new)
    cap = cap_router.stats()["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = np.cumsum(rng.exponential(span / n_requests,
                                         size=n_requests)).tolist()

    def fleet(engines):
        router = ReplicaRouter(engines)
        out = router.serve(prompts, max_new_tokens=max_new,
                           arrivals=list(arrivals))
        return out, router.stats()

    out1, st1 = fleet([eng_a])
    out2, st2 = fleet([eng_a, eng_b])
    mismatches = sum(out2[u] != out1[u] for u in out2)
    return {
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "capacity_tokens_per_sec": round(cap, 1),
        "replicas_used": [st1["replicas"], st2["replicas"]],
        "sustained_tokens_per_sec_1r": round(
            st1["sustained_tokens_per_sec"], 1),
        "sustained_tokens_per_sec_2r": round(
            st2["sustained_tokens_per_sec"], 1),
        "fleet_speedup_x": round(st2["sustained_tokens_per_sec"]
                                 / st1["sustained_tokens_per_sec"], 2),
        "ttft_p50_s_1r": round(st1["ttft_p50_s"], 4),
        "ttft_p95_s_1r": round(st1["ttft_p95_s"], 4),
        "ttft_p99_s_1r": round(st1["ttft_p99_s"], 4),
        "ttft_p50_s_2r": round(st2["ttft_p50_s"], 4),
        "ttft_p95_s_2r": round(st2["ttft_p95_s"], 4),
        "ttft_p99_s_2r": round(st2["ttft_p99_s"], 4),
        "tpot_p50_s_1r": round(st1["tpot_p50_s"], 4),
        "tpot_p50_s_2r": round(st2["tpot_p50_s"], 4),
        "token_mismatches_vs_1r": mismatches,
    }


def serving_speculative_row(model, params, icfg, vocab, *, n_requests=12,
                            period=5, prompt_lo=48, prompt_hi=96, max_new=48,
                            k=4, load=2.0, seed=0):
    """Config-5 speculative-serving row (ISSUE 8): the SAME Poisson trace
    served at k=0 (speculation off) and k=4 with BOTH drafters — the
    n-gram self-speculation drafter (zero extra weights) and a draft model
    (here the target model itself, the acceptance-rate ceiling a
    well-distilled draft approaches). The workload is repetitive-suffix
    (period-``period`` cycling prompts — the code/structured-output/
    multi-turn regime where suffixes repeat and decode steps are most
    wasteful), because that is the regime the steps-per-token lever pays
    in; acceptance on incompressible random text is near zero by
    construction and would measure the drafter, not the machinery.

    Headline figures: tokens/s/sequence (the per-sequence latency axis
    batching cannot touch), steps-per-emitted-token (decode ticks per
    token per sequence — the ISSUE bar is < 0.67 at k=4), acceptance
    rate, and TTFT/TPOT p50/p95. Greedy acceptance keeps every variant
    token-identical to k=0 (asserted); the row is seed-reproducible with
    its ``trace`` returned (ISSUE 14). Reused at toy size by
    tests/test_bench_smoke.py so the published row cannot rot on CPU."""
    import dataclasses as _dc

    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                DraftModelDrafter,
                                                InferenceEngineV2)

    rng = np.random.default_rng(seed)
    prompts = []
    for n in rng.integers(prompt_lo, prompt_hi + 1, size=n_requests):
        cyc = rng.integers(1, vocab, size=period).tolist()
        prompts.append((cyc * (int(n) // period + 1))[:int(n)])

    def spec_cfg(enabled):
        sv = _dc.replace(
            icfg.serving,
            token_budget=max(icfg.serving.token_budget,
                             icfg.serving.max_running * (k + 1)),
            speculative=_dc.replace(icfg.serving.speculative,
                                    enabled=enabled, k=k))
        return _dc.replace(icfg, serving=sv)

    def run(enabled, drafter=None, arrivals=None):
        eng = InferenceEngineV2(model, params, spec_cfg(enabled))
        sched = ContinuousBatchingScheduler(eng, drafter=drafter)
        out = sched.serve(prompts, max_new_tokens=max_new,
                          arrivals=arrivals)
        return out, sched.stats()

    # throwaway + capacity passes at k=0 calibrate the arrivals every
    # variant then replays, so all runs face identical offered load
    run(False)
    _, cold = run(False)
    span = n_requests * max_new / cold["sustained_tokens_per_sec"] / load
    arrivals = poisson_arrivals(rng, n_requests, span)

    def variant(enabled, drafter=None):
        out, st = run(enabled, drafter=drafter, arrivals=list(arrivals))
        sp = st["speculative"]
        return out, {
            # tpot_p50 can legitimately be 0.0 (multi-token ticks emit at
            # one timestamp — the speculative win itself), so guard on
            # None, not truthiness; ttft keeps the denominator positive
            "tokens_per_sec_per_seq": round(
                max_new / (st["ttft_p50_s"]
                           + st["tpot_p50_s"] * (max_new - 1)), 2)
            if st["tpot_p50_s"] is not None else None,
            "sustained_tokens_per_sec": round(
                st["sustained_tokens_per_sec"], 1),
            "steps_per_emitted_token": (
                round(sp["steps_per_emitted_token"], 3)
                if sp["steps_per_emitted_token"] is not None else None),
            "acceptance_rate": (round(sp["acceptance_rate"], 3)
                                if sp["acceptance_rate"] is not None
                                else None),
            "proposed": sp["proposed"], "rollbacks": sp["rollbacks"],
            "ttft_p50_s": round(st["ttft_p50_s"], 4),
            "ttft_p95_s": round(st["ttft_p95_s"], 4),
            "tpot_p50_s": round(st["tpot_p50_s"], 4),
            "tpot_p95_s": round(st["tpot_p95_s"], 4),
            "ticks": st["ticks"],
        }

    out0, base = variant(False)
    out_ng, ngram_row = variant(True)
    out_dm, draft_row = variant(
        True, drafter=DraftModelDrafter.for_target(model, params,
                                                   spec_cfg(True)))
    tok0 = [out0[u] for u in out0]
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cold["sustained_tokens_per_sec"]),
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "prompt_period": period,
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "k": k,
        "baseline_k0": base,
        "ngram_k4": ngram_row,
        "draft_model_k4": draft_row,
        "speedup_steps_ngram_x": round(
            base["steps_per_emitted_token"]
            / ngram_row["steps_per_emitted_token"], 2),
        "speedup_steps_draft_x": round(
            base["steps_per_emitted_token"]
            / draft_row["steps_per_emitted_token"], 2),
        "token_mismatches_ngram_vs_k0": sum(a != b for a, b in zip(
            [out_ng[u] for u in out_ng], tok0)),
        "token_mismatches_draft_vs_k0": sum(a != b for a, b in zip(
            [out_dm[u] for u in out_dm], tok0)),
    }


def serving_sampling_row(model, params, icfg, vocab, *, n_requests=16,
                         prompt_lo=48, prompt_hi=128, max_new=32,
                         temperature=0.8, top_p=0.9, spec_k=4,
                         spec_top_k=2, load=2.0, seed=0):
    """Config-5 one-dispatch-sampling row (ISSUE 16): the SAME Poisson
    trace served greedy, sampled stop-DISABLED, and sampled with EOS
    early-stop, all at identical arrivals on one warmed engine.

    Sampling happens inside the fused serving dispatch (the logits never
    leave the device), so the greedy-vs-sampled goodput delta measures
    the fused sampler's marginal cost, and the stop-disabled-vs-EOS delta
    measures what early termination RETURNS to the fleet — dead tokens
    never decoded, KV blocks freed at the stop tick. The EOS id is the
    MODAL token of the stop-disabled sampled run, so the stop condition
    provably fires on this workload instead of being vacuously absent.
    The row also re-serves the sampled trace on a fresh scheduler and
    asserts bit-exact tokens (``seeded_replay_verified`` — the per-row
    Gumbel chain is a pure function of seed and position), and runs a
    side trace with the draft-model drafter (the target as its own
    draft, the acceptance ceiling) at ``temperature`` with
    ``top_k=spec_top_k`` to pin speculative acceptance > 0 at
    temperature > 0 AND spec-on/off token parity under sampling (the
    generalized accept rule emits the seeded chain either way; top_k
    keeps the chain near the draft's greedy proposals so acceptance is
    measurable on a toy model too). Seed-reproducible; ``trace``
    returned (ISSUE 14). Reused at toy size by
    tests/test_bench_smoke.py."""
    import dataclasses as _dc
    import time as _time

    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                DraftModelDrafter,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.inference.config import SamplingParams

    rng = np.random.default_rng(seed)
    eng = InferenceEngineV2(model, params, icfg)
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]

    def sps(eos=-1):
        return [SamplingParams(temperature=temperature, top_p=top_p,
                               seed=seed * 1000 + i, eos_token_id=eos)
                for i in range(n_requests)]

    def run(sampling=None, arrivals=None):
        sched = ContinuousBatchingScheduler(eng)
        t0 = _time.perf_counter()
        out = sched.serve(prompts, max_new_tokens=max_new,
                          arrivals=arrivals, sampling=sampling)
        return out, sched.stats(), _time.perf_counter() - t0

    # throwaway greedy + sampled passes compile both program families.
    # Seeded chains are arrival-invariant, so the throwaway stop-disabled
    # run already yields the measured run's tokens — pick EOS from it
    # (the modal token, guaranteed to recur under THIS model/temperature
    # so early stop actually fires). The greedy capacity pass then
    # calibrates the shared arrivals.
    run()
    out_w, _, _ = run(sampling=sps())
    all_toks = [t for u in out_w for t in out_w[u]]
    eos = int(np.bincount(all_toks).argmax())
    _, cold, _ = run()
    cap = cold["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = poisson_arrivals(rng, n_requests, span)

    # each measured variant runs TWICE at identical arrivals and times
    # the second: arrivals (and, for EOS, mid-stream stops) create batch
    # compositions the no-arrivals warmups never compiled, and a single
    # pass would bill those compiles to the variant that hit them first
    def measured(sampling=None):
        warm, _, _ = run(sampling=sampling, arrivals=list(arrivals))
        out, st, wall = run(sampling=sampling, arrivals=list(arrivals))
        return warm, out, st, wall

    _, out_g, st_g, wall_g = measured()
    _, out_ns, st_ns, wall_ns = measured(sps())
    warm_es, _, _ = run(sampling=sps(eos=eos), arrivals=list(arrivals))
    freed0 = eng.early_stop_freed_blocks  # cumulative; warm pass freed some
    out_es, st_es, wall_es = run(sampling=sps(eos=eos),
                                 arrivals=list(arrivals))
    freed_measured = eng.early_stop_freed_blocks - freed0
    # the warm pass ran on a fresh scheduler — its bit-identity with the
    # measured pass IS the seeded-replay check
    replay_ok = [warm_es[u] for u in warm_es] == [out_es[u] for u in out_es]

    # speculative acceptance at temperature > 0 (the generalized accept
    # rule): target-as-draft side trace — proposals are the greedy chain,
    # so acceptance measures how often the seeded chain agrees with
    # argmax; spec on vs off must emit identical seeded chains
    spec_prompts = [rng.integers(1, vocab, size=int(n)).tolist()
                    for n in rng.integers(prompt_lo, prompt_hi + 1,
                                          size=max(4, n_requests // 2))]
    spec_sps = [SamplingParams(temperature=temperature, top_k=spec_top_k,
                               seed=7000 + i)
                for i in range(len(spec_prompts))]
    sv = _dc.replace(
        icfg.serving,
        token_budget=max(icfg.serving.token_budget,
                         icfg.serving.max_running * (spec_k + 1)),
        speculative=_dc.replace(icfg.serving.speculative, enabled=True,
                                k=spec_k))
    spec_icfg = _dc.replace(icfg, serving=sv)
    spec_eng = InferenceEngineV2(model, params, spec_icfg)
    spec_sched = ContinuousBatchingScheduler(
        spec_eng, drafter=DraftModelDrafter.for_target(model, params,
                                                       spec_icfg))
    out_sp = spec_sched.serve(spec_prompts, max_new_tokens=max_new,
                              sampling=spec_sps)
    spec_st = spec_sched.stats()
    base_sched = ContinuousBatchingScheduler(eng)
    out_sq = base_sched.serve(spec_prompts, max_new_tokens=max_new,
                              sampling=spec_sps)
    spec_parity = [out_sp[u] for u in out_sp] == [out_sq[u] for u in out_sq]

    def _summ(st, wall, out):
        return {
            "sustained_tokens_per_sec": round(
                st["sustained_tokens_per_sec"], 1),
            "requests_per_sec": round(n_requests / wall, 2),
            "emitted_tokens": sum(len(out[u]) for u in out),
            "ttft_p50_s": round(st["ttft_p50_s"], 4),
            "tpot_p50_s": round(st["tpot_p50_s"], 4),
            "ticks": st["ticks"],
        }

    samp = st_es["sampling"]
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cap),
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "temperature": temperature, "top_p": top_p,
        "eos_token_id": eos,
        "greedy": _summ(st_g, wall_g, out_g),
        "sampled_no_stop": _summ(st_ns, wall_ns, out_ns),
        "sampled_eos": _summ(st_es, wall_es, out_es),
        # the fused sampler's marginal cost on identical arrivals
        "sampling_overhead_x": round(wall_ns / wall_g, 3),
        # what early stop returns to the fleet vs the stop-disabled run
        "goodput_eos_vs_no_stop_x": round(
            (n_requests / wall_es) / (n_requests / wall_ns), 3),
        "early_stop_fraction": round(samp["early_stops"] / n_requests, 3),
        "dead_tokens_saved": samp["dead_tokens_saved"],
        "early_stop_freed_blocks": freed_measured,
        "seeded_replay_verified": bool(replay_ok),
        "spec_acceptance_at_temp": (
            round(spec_st["speculative"]["acceptance_rate"], 3)
            if spec_st["speculative"]["acceptance_rate"] is not None
            else None),
        "spec_resamples": spec_st["sampling"]["resamples"],
        "spec_token_parity_at_temp": bool(spec_parity),
    }


def serving_failover_row(model, params, icfg, vocab, *, n_requests=16,
                         prompt_lo=48, prompt_hi=192, max_new=24,
                         kill_after_ticks=4, load=2.0, seed=0):
    """Config-5 serving-failover row (ISSUE 12): the SAME Poisson trace
    served by a 2-replica fleet clean, then with replica 0 CRASHED
    uncleanly mid-trace (``replica_crash`` fault at its
    ``kill_after_ticks``-th tick, no drain, engine lost). Failover
    re-places the dead replica's queue and in-flight requests on the
    survivor with token-identical drain-replay, so the row's headline
    figures are the COST of an unclean death under load: goodput
    retention (chaos/clean sustained tokens/s), recovered-request count,
    and the TTFT p95 delta (queueing on the halved fleet plus the retry
    backoff). Token parity is asserted per request. Reused at toy size by
    tests/test_bench_smoke.py so the published row cannot rot on CPU."""
    from shuffle_exchange_tpu.inference import InferenceEngineV2
    from shuffle_exchange_tpu.serving import ReplicaRouter
    from shuffle_exchange_tpu.testing import faults

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]

    def fleet():
        return ReplicaRouter([InferenceEngineV2(model, params, icfg)
                              for _ in range(2)])

    # throwaway pass warms the shape-bin ladder; capacity calibrates the
    # arrivals both measured runs then replay at identical offsets
    fleet().serve(prompts, max_new_tokens=max_new)
    cap_router = fleet()
    cap_router.serve(prompts, max_new_tokens=max_new)
    cap = cap_router.stats()["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = np.cumsum(rng.exponential(span / n_requests,
                                         size=n_requests)).tolist()

    clean_router = fleet()
    out_clean = clean_router.serve(prompts, max_new_tokens=max_new,
                                   arrivals=list(arrivals))
    st_clean = clean_router.stats()

    chaos_router = fleet()
    faults.clear()
    faults.arm("replica_crash", index=0, fire_nth=kill_after_ticks)
    try:
        out_chaos = chaos_router.serve(prompts, max_new_tokens=max_new,
                                       arrivals=list(arrivals))
    finally:
        faults.clear()
    st_chaos = chaos_router.stats()
    fo = st_chaos["failover"]
    mismatches = sum(out_chaos[u] != out_clean[u] for u in out_chaos)
    return {
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "kill_after_ticks": kill_after_ticks,
        "deaths": fo["deaths"],
        "recovered_requests": fo["recovered_requests"],
        "reprefill_tokens": fo["reprefill_tokens"],
        "quarantined": len(fo["quarantined"]),
        "token_mismatches_vs_clean": mismatches,
        "sustained_tokens_per_sec_clean": round(
            st_clean["sustained_tokens_per_sec"], 1),
        "sustained_tokens_per_sec_failover": round(
            st_chaos["sustained_tokens_per_sec"], 1),
        "goodput_retention": round(
            st_chaos["sustained_tokens_per_sec"]
            / st_clean["sustained_tokens_per_sec"], 3),
        "ttft_p50_s_clean": round(st_clean["ttft_p50_s"], 4),
        "ttft_p95_s_clean": round(st_clean["ttft_p95_s"], 4),
        "ttft_p50_s_failover": round(st_chaos["ttft_p50_s"], 4),
        "ttft_p95_s_failover": round(st_chaos["ttft_p95_s"], 4),
        "ttft_p95_delta_s": round(st_chaos["ttft_p95_s"]
                                  - st_clean["ttft_p95_s"], 4),
    }


def serving_async_publish_row(model, params, icfg, vocab, *, n_requests=16,
                              prompt_lo=48, prompt_hi=192, max_new=24,
                              publish_every_ticks=3, n_publishes=4,
                              staleness_window=4, load=2.0, seed=0):
    """Config-5 async-weight-sync row (ISSUE 20): the SAME Poisson trace
    served by a 2-replica fleet while ``n_publishes`` weight publishes
    land mid-trace, two ways:

      - *barrier* (``router.sync`` off): each publish is the two-phase
        stage-on-every-replica commit under the router lock — the
        publish call's wall time IS the stall it imposes on the fleet
        (no tick can run while it holds the lock), O(fleet);
      - *async* (``router.sync`` on, Gossip): each publish retains one
        host copy and kicks only the trainer peer's current edge
        partners — O(edge-degree) — with cooperative ``sync_step()``
        rounds playing the background gossip thread between ticks.

    Publishes carry the SAME bytes as the boot weights, so every
    version decodes identically and token parity between the two
    variants (and versions) is assertable exactly. Headline figures:
    the per-publish stall (p50/max) barrier vs async, goodput
    retention, the honest ``weight_version`` census over finished
    requests (how stale the fleet actually served), the bounded
    staleness window holding over every stamp, and a final
    ``converge()`` landing the whole surviving fleet on one version.
    Reused at toy size by tests/test_bench_smoke.py so the published
    row cannot rot on CPU."""
    import dataclasses as _dc
    from collections import Counter, deque as _deque

    from shuffle_exchange_tpu.inference import InferenceEngineV2
    from shuffle_exchange_tpu.serving import ReplicaRouter

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]

    def fleet(sync_on):
        rcfg = ({"sync": {"enabled": True, "method": "Gossip",
                          "gossip_prob": 1.0,
                          "staleness_window": staleness_window}}
                if sync_on else None)
        cfg2 = _dc.replace(icfg, router=rcfg)
        return ReplicaRouter([InferenceEngineV2(model, params, cfg2)
                              for _ in range(2)])

    def drive(router, arrivals, publish):
        """serve() with mid-trace publish hooks: submit on the arrival
        clock, tick cooperatively, publish every ``publish_every_ticks``
        ticks (wall-timing each call), and run one gossip round per tick
        when async sync is on."""
        pending = _deque(enumerate(prompts))
        t0 = router.clock()
        uids, stalls, ticks, version = [], [], 0, 0
        while pending or any(r.scheduler.active or r.scheduler.queue
                             for r in router.replicas if r.active):
            while pending and (arrivals is None or
                               router.clock() - t0
                               >= arrivals[pending[0][0]]):
                i, prompt = pending.popleft()
                uids.append(router.submit(prompt, max_new_tokens=max_new))
            alive = router.tick()
            ticks += 1
            if (publish and version < n_publishes
                    and ticks % publish_every_ticks == 0):
                version += 1
                tp = time.perf_counter()
                router.publish_weights(params, version=version)
                stalls.append(time.perf_counter() - tp)
            if router._async_sync is not None:
                router.sync_step()
            if not alive and pending and arrivals is not None:
                wait = arrivals[pending[0][0]] - (router.clock() - t0)
                if wait > 0:
                    time.sleep(wait)
        # a short trace can drain before the tick schedule spends the
        # publish budget: flush the remainder so both variants always
        # time n_publishes calls (idle-fleet stalls still measure the
        # stage/commit cost the call imposes)
        while publish and version < n_publishes:
            version += 1
            tp = time.perf_counter()
            router.publish_weights(params, version=version)
            stalls.append(time.perf_counter() - tp)
            if router._async_sync is not None:
                router.sync_step()
        out = {u: router.requests[u].generated for u in uids}
        return out, stalls, uids

    # throwaway pass warms the shape-bin ladder; capacity calibrates the
    # arrivals both measured runs then replay at identical offsets
    drive(fleet(False), None, publish=False)
    cap_router = fleet(False)
    drive(cap_router, None, publish=False)
    cap = cap_router.stats()["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = np.cumsum(rng.exponential(span / n_requests,
                                         size=n_requests)).tolist()

    barrier_router = fleet(False)
    out_b, stalls_b, _ = drive(barrier_router, list(arrivals), publish=True)
    st_b = barrier_router.stats()

    async_router = fleet(True)
    out_a, stalls_a, uids_a = drive(async_router, list(arrivals),
                                    publish=True)
    st_a = async_router.stats()
    sync = async_router._async_sync
    newest = sync.newest_version
    census = Counter(async_router.requests[u].weight_version
                     for u in uids_a)
    window_ok = all(0 <= newest - wv <= staleness_window for wv in census)
    converged_v = async_router.converge()
    converged = all(r.engine.weight_version == converged_v
                    for r in async_router.replicas if r.active)
    mismatches = sum(out_a[u] != out_b[u] for u in out_a)
    return {
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "publishes": n_publishes,
        "staleness_window": staleness_window,
        "publish_stall_p50_s_barrier": round(
            float(np.median(stalls_b)), 5),
        "publish_stall_max_s_barrier": round(max(stalls_b), 5),
        "publish_stall_p50_s_async": round(float(np.median(stalls_a)), 5),
        "publish_stall_max_s_async": round(max(stalls_a), 5),
        "publish_stall_ratio": round(
            float(np.median(stalls_b)) / max(float(np.median(stalls_a)),
                                             1e-9), 1),
        "sustained_tokens_per_sec_barrier": round(
            st_b["sustained_tokens_per_sec"], 1),
        "sustained_tokens_per_sec_async": round(
            st_a["sustained_tokens_per_sec"], 1),
        "goodput_retention": round(st_a["sustained_tokens_per_sec"]
                                   / st_b["sustained_tokens_per_sec"], 3),
        "token_mismatches_vs_barrier": mismatches,
        "version_census": {int(k): int(v)
                          for k, v in sorted(census.items())},
        "staleness_window_held": bool(window_ok),
        "forced_catchups": st_a["sync"]["forced_catchups"],
        "edge_exchanges": st_a["sync"]["edge_exchanges"],
        "failed_exchanges": st_a["sync"]["failed_exchanges"],
        "publish_bytes": st_a["publish"]["bytes"],
        "converged_version": converged_v,
        "fleet_converged": bool(converged),
    }


def serving_longctx_row(model, params, icfg, vocab, *, n_requests=12,
                        prompt_blocks=16, grow_blocks=2, load=4.0, seed=0):
    """Config-5 long-context tier row (ISSUE 15): the SAME Poisson trace —
    contexts whose AGGREGATE KV exceeds the resident pool — served three
    ways on identically-constrained pools:

      - *refuse-admission baseline* (``kv_tier`` off): overflow waits in
        the queue and decode growth past the pool PREEMPTS the youngest
        sequence — flush + full re-prefill replay;
      - *spill-on* (``kv_tier`` on): the same overflow PARKS host-ward —
        cold blocks spill byte-exactly over the AIO pinned-buffer path
        and fetch back when pressure subsides, zero re-prefill compute;
      - *unconstrained reference*: a pool big enough to hold everything,
        the token-parity oracle.

    The trace is shaped to force the overflow deterministically: every
    prompt fills ``prompt_blocks`` KV blocks to one token short of the
    boundary and generates ``grow_blocks`` blocks of new tokens, while
    the constrained pool holds exactly ``max_running`` prompts' worth —
    admission fills the pool, decode growth overflows it. Token parity
    is ASSERTED for bf16 KV (int8/fp8 are deterministic-not-bit-equal
    per the PR 6 chunk-boundary contract and only reported). Headline:
    goodput + TTFT/TPOT p95 for both, the tier's prefetch hit-rate, and
    spill-on's preemption count (must be 0 — parks replace preempts).
    Reused at toy size by tests/test_bench_smoke.py."""
    import dataclasses as _dc

    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.inference.paged import blocks_needed

    rng = np.random.default_rng(seed)
    bs = icfg.kv_block_size
    sv = icfg.serving
    prompt_len = prompt_blocks * bs - 1
    max_new = grow_blocks * bs
    prompts = [rng.integers(1, vocab, size=prompt_len).tolist()
               for _ in range(n_requests)]
    per_req = blocks_needed(prompt_len + max_new, bs)
    # constrained pool: admission fits max_running prompts, growth does
    # not (+1 scratch, +1 slack so the first boundary crossing parks
    # rather than stalls); reference pool holds the whole trace resident
    small = sv.max_running * prompt_blocks + 2
    big = n_requests * per_req + 2

    def run(num_blocks, spill, arrivals=None):
        eng = InferenceEngineV2(model, params, _dc.replace(
            icfg, num_kv_blocks=num_blocks,
            kv_tier=_dc.replace(icfg.kv_tier, enabled=spill)))
        # throwaway pass warms the shape-bin ladder with the SAME
        # arrivals — staggered admission reaches decode-batch / park
        # widths an all-at-once warm never compiles, and those compiles
        # would land mid-measurement otherwise
        ContinuousBatchingScheduler(eng).serve(prompts,
                                               max_new_tokens=max_new,
                                               arrivals=arrivals)
        if eng.tier is not None:
            # the warm pass parked/fetched through the SAME tier — zero
            # the traffic counters so the published spills/fetches/
            # hit-rate describe only the measured pass
            eng.tier.reset_counters()
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=max_new,
                          arrivals=arrivals)
        return out, sched.stats()

    out_ref, st_ref = run(big, False)
    # arrivals calibrated on the BASELINE capacity and replayed at the
    # same offsets for all three, so the comparison is variance-paired
    _, st_cap = run(small, False)
    cap = st_cap["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = poisson_arrivals(rng, n_requests, span)
    out_off, st_off = run(small, False, arrivals=list(arrivals))
    out_on, st_on = run(small, True, arrivals=list(arrivals))
    mism_off = sum(out_off[u] != out_ref[u] for u in out_ref)
    mism_on = sum(out_on[u] != out_ref[u] for u in out_ref)
    if icfg.kv_cache_dtype == "bf16":
        assert mism_on == 0 and mism_off == 0, (
            f"long-context token parity broken: spill-on {mism_on} / "
            f"baseline {mism_off} requests diverge from the "
            f"unconstrained-pool reference under bf16 KV")
    tier = st_on["kv_tier"]
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cap),
        "n_requests": n_requests,
        "prompt_tokens": prompt_len,
        "max_new_tokens": max_new,
        "kv_block_size": bs,
        "pool_blocks_constrained": small,
        "pool_blocks_reference": big,
        "aggregate_kv_blocks": n_requests * per_req,
        "offered_load_x": load,
        "kv_cache_dtype": icfg.kv_cache_dtype,
        "hot_block_fraction": icfg.kv_tier.hot_block_fraction,
        "prefetch_depth": icfg.kv_tier.prefetch_depth,
        "token_mismatches_spill_on": mism_on,
        "token_mismatches_baseline": mism_off,
        "preemptions_baseline": st_off["preemptions"],
        "preemptions_spill_on": st_on["preemptions"],
        "parks": tier["parks"],
        "unparks": tier["unparks"],
        "spills": tier["spills"],
        "fetches": tier["fetches"],
        "tier_hit_rate": (round(tier["hit_rate"], 3)
                          if tier["hit_rate"] is not None else None),
        "sustained_tokens_per_sec_baseline": round(
            st_off["sustained_tokens_per_sec"], 1),
        "sustained_tokens_per_sec_spill_on": round(
            st_on["sustained_tokens_per_sec"], 1),
        "sustained_tokens_per_sec_unconstrained": round(
            st_ref["sustained_tokens_per_sec"], 1),
        "goodput_vs_baseline": round(
            st_on["sustained_tokens_per_sec"]
            / st_off["sustained_tokens_per_sec"], 3),
        "ttft_p95_s_baseline": round(st_off["ttft_p95_s"], 4),
        "ttft_p95_s_spill_on": round(st_on["ttft_p95_s"], 4),
        "tpot_p95_s_baseline": round(st_off["tpot_p95_s"], 4),
        "tpot_p95_s_spill_on": round(st_on["tpot_p95_s"], 4),
    }


def serving_multi_tenant_row(model, params, icfg, vocab, *, n_requests=24,
                             adapter_counts=(1, 8, 64), pool_slots=4,
                             rank=8, prompt_lo=64, prompt_hi=512,
                             max_new=32, load=2.0, seed=0,
                             parity_samples=3):
    """Config-5 multi-tenant LoRA row (ISSUE 18): the SAME Poisson trace
    served with requests striped round-robin across 1, 8, and 64 distinct
    adapters on a fixed ``pool_slots``-slot pool — the pool holds the
    1-adapter set resident and is oversubscribed 2x/16x by the others, so
    the sweep measures what adapter paging COSTS: goodput retention vs
    the single-tenant run, pool hit-rate, eviction and park counts (parks
    replace preemptions — adapter pressure must preempt NOTHING), and the
    zero-recompile contract (the adapter-count sweep reuses one engine's
    programs; adapter identity is data). Mixed-vs-solo token parity is
    ASSERTED under greedy for ``parity_samples`` requests of the widest
    entry. Reused at toy size by tests/test_bench_smoke.py."""
    import dataclasses as _dc

    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.inference.adapters import target_dims

    rng = np.random.default_rng(seed)
    targets = ("wq", "wv")
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]

    def factors(i):
        frng = np.random.default_rng(1000 + i)
        out = {}
        for t in targets:
            din, dout = target_dims(model.config, t)
            out[t] = (
                0.02 * frng.standard_normal(
                    (model.config.n_layers, din, rank)).astype(np.float32),
                0.02 * frng.standard_normal(
                    (model.config.n_layers, rank, dout)).astype(np.float32))
        return out

    # ONE engine for the whole sweep: 64 registered adapters over
    # pool_slots resident slots. Re-registration across entries is a
    # content-key no-op, and reusing the engine is itself the contract —
    # programs compiled for the 1-adapter entry must serve the 64-adapter
    # entry untouched.
    eng = InferenceEngineV2(model, params, _dc.replace(
        icfg, adapters={"enabled": True, "slots": pool_slots,
                        "max_rank": rank, "targets": targets}))
    for i in range(max(adapter_counts)):
        eng.adapters.register(f"tenant-{i:03d}", factors(i))

    def run(n_adapters, arrivals=None):
        aids = [f"tenant-{i % n_adapters:03d}" for i in range(n_requests)]
        # warm pass: same arrivals, so park/unpark widths compile here
        ContinuousBatchingScheduler(eng).serve(
            prompts, max_new_tokens=max_new, arrivals=arrivals,
            adapter_ids=aids)
        before = eng.adapters.stats()
        programs = set(eng.program_shapes)
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=max_new,
                          arrivals=arrivals, adapter_ids=aids)
        st = sched.stats()
        pool = {k: st["adapters"][k] - before[k]
                for k in ("hits", "misses", "evictions")}
        return out, st, pool, len(set(eng.program_shapes) - programs)

    _, st_cap, _, _ = run(1)
    cap = st_cap["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = list(poisson_arrivals(rng, n_requests, span))
    entries = []
    outs = {}
    for n_adapters in adapter_counts:
        out, st, pool, new_programs = run(n_adapters, arrivals=arrivals)
        outs[n_adapters] = out
        lookups = pool["hits"] + pool["misses"]
        entries.append({
            "n_adapters": n_adapters,
            "sustained_tokens_per_sec": round(
                st["sustained_tokens_per_sec"], 1),
            "ttft_p95_s": round(st["ttft_p95_s"], 4),
            "tpot_p95_s": round(st["tpot_p95_s"], 4),
            "pool_hit_rate": (round(pool["hits"] / lookups, 3)
                              if lookups else None),
            "evictions": pool["evictions"],
            "parks": st["adapters"]["parks"],
            "unparks": st["adapters"]["unparks"],
            "preemptions": st["preemptions"],
            # programs compiled DURING the measured pass — reported, not
            # asserted: Poisson replay is wall-clock-paced, so warm and
            # measured passes can straddle a shape-bin boundary on a
            # slow tick (the deterministic zero-recompile assert is the
            # fresh-adapter probe below)
            "measured_pass_new_programs": new_programs,
        })
    # adapter pressure parks, never preempts
    assert all(e["preemptions"] == 0 for e in entries), entries
    base_tps = entries[0]["sustained_tokens_per_sec"]
    for e in entries:
        e["goodput_retention"] = round(
            e["sustained_tokens_per_sec"] / base_tps, 3)
    # mixed-vs-solo parity: replay sample requests of the widest entry
    # alone (same engine, fresh scheduler, same adapter) — greedy tokens
    # must match the mixed run exactly
    widest = adapter_counts[-1]
    mism = 0
    for i in range(min(parity_samples, n_requests)):
        solo = ContinuousBatchingScheduler(eng).serve(
            [prompts[i]], max_new_tokens=max_new,
            adapter_ids=[f"tenant-{i % widest:03d}"])
        mism += solo[0] != outs[widest][i]
    assert mism == 0, (f"multi-tenant token parity broken: {mism}/"
                       f"{parity_samples} sampled requests diverge "
                       f"mixed-vs-solo at {widest} adapters")
    # zero-recompile probe (deterministic — no arrival pacing, and the
    # parity replays above warmed the solo-request widths): a brand-new
    # adapter id on the engine the whole sweep warmed must serve without
    # compiling anything; adapter identity is data, not shape
    eng.adapters.register("tenant-fresh", factors(max(adapter_counts)))
    programs = set(eng.program_shapes)
    ContinuousBatchingScheduler(eng).serve(
        [prompts[0]], max_new_tokens=max_new, adapter_ids=["tenant-fresh"])
    fresh_adapter_new_programs = len(set(eng.program_shapes) - programs)
    assert fresh_adapter_new_programs == 0, (
        f"fresh adapter id compiled {fresh_adapter_new_programs} new "
        f"programs on a warmed engine — adapter identity leaked into a "
        f"program shape")
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cap),
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "pool_slots": pool_slots,
        "adapter_rank": rank,
        "adapter_targets": list(targets),
        "entries": entries,
        "token_mismatches_mixed_vs_solo": mism,
        "parity_samples": parity_samples,
        "fresh_adapter_new_programs": fresh_adapter_new_programs,
    }


def serving_moe_row(model, params, icfg, vocab, *, n_requests=16,
                    n_experts=4, prompt_lo=64, prompt_hi=256, max_new=32,
                    load=2.0, seed=0, parity_samples=3):
    """Config-5 expert-parallel MoE serving row (ISSUE 19): the SAME
    Poisson trace served by the dense baseline and by an MoE twin at
    MATCHED total parameters (each of the ``n_experts`` experts gets
    ``ff_dim // n_experts``, so the expert pool together weighs what the
    dense FFN weighs, while each token only computes ``top_k/n_experts``
    of it). The MoE engine pins ``serving.moe.moe_impl="ragged"`` — the
    dropless sorted-route through ``ops/grouped_gemm.grouped_matmul``,
    whose output is batch-composition independent, which is what makes
    the batched-vs-sequential token-parity assert below exact. The row
    reports goodput + TTFT/TPOT tails for both twins, the MoE routing
    counters (dispatched/dropped/parks and the expert-load balance of the
    final tick), and ASSERTS expert pressure never preempted. Reused at
    toy size by tests/test_bench_smoke.py."""
    import dataclasses as _dc

    import jax as _jax

    from shuffle_exchange_tpu.autotuning import poisson_arrivals
    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.models import Transformer

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(prompt_lo, prompt_hi + 1,
                                     size=n_requests)]

    dense_cfg = model.config
    moe_cfg = _dc.replace(
        dense_cfg, n_experts=n_experts, moe_top_k=2,
        d_ff=max(128, dense_cfg.ff_dim // n_experts))
    moe_model = Transformer(moe_cfg)
    moe_params = moe_model.init(_jax.random.PRNGKey(seed))
    moe_icfg = icfg.with_overlay(
        {"serving": {"moe": {"moe_impl": "ragged"}}})

    def pcount(p):
        import jax.tree_util as _jtu
        return sum(int(np.prod(l.shape)) for l in _jtu.tree_leaves(p))

    def run(m, p, ic, arrivals=None):
        eng = InferenceEngineV2(m, p, ic)
        # throwaway pass warms the shape-bin ladder (same trace -> same
        # shapes), so the measured pass carries no JIT wall-time
        ContinuousBatchingScheduler(eng).serve(prompts,
                                               max_new_tokens=max_new)
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=max_new,
                          arrivals=arrivals)
        return eng, out, sched.stats()

    # capacity pass on the dense twin sets the paired arrival trace both
    # twins replay — same prompts, same offsets, same offered load
    _, _, st_cap = run(model, params, icfg)
    cap = st_cap["sustained_tokens_per_sec"]
    span = n_requests * max_new / cap / load
    arrivals = list(poisson_arrivals(rng, n_requests, span))

    entries = {}
    _, _, st_dense = run(model, params, icfg, arrivals=arrivals)
    moe_eng, moe_out, st_moe = run(moe_model, moe_params, moe_icfg,
                                   arrivals=arrivals)
    for name, st in (("dense", st_dense), ("moe", st_moe)):
        entries[name] = {
            "sustained_tokens_per_sec": round(
                st["sustained_tokens_per_sec"], 1),
            "ttft_p95_s": round(st["ttft_p95_s"], 4),
            "ttft_p99_s": round(st["ttft_p99_s"], 4),
            "tpot_p95_s": round(st["tpot_p95_s"], 4),
            "tpot_p99_s": round(st["tpot_p99_s"], 4),
            "ticks": st["ticks"],
            "preemptions": st["preemptions"],
        }
    entries["dense"]["params"] = pcount(params)
    entries["moe"]["params"] = pcount(moe_params)
    entries["moe"].update({
        "n_experts": n_experts, "top_k": moe_cfg.moe_top_k,
        "d_ff_per_expert": moe_cfg.d_ff,
        **{k: st_moe["moe"][k] for k in
           ("dispatched", "dropped", "expert_load_max", "capacity_parks")},
    })
    # expert pressure parks at the queue's FIFO seat — it never preempts
    assert st_moe["preemptions"] == 0, st_moe
    assert st_moe["moe"]["dropped"] == 0, st_moe   # ragged is dropless
    # expert-load balance of the final tick: mean/max over the per-expert
    # routed-token counts (1.0 = perfectly balanced routing)
    counts = moe_eng._moe_last_counts
    balance = (round(float(counts.mean() / counts.max()), 3)
               if counts is not None and counts.max() else None)
    entries["moe"]["expert_load_balance"] = balance
    # token parity vs the SEQUENTIAL oracle: each sampled request alone
    # through put() + decode_loop() on a fresh engine — the dense-gather
    # route a one-request batch takes. Ragged routing is batch-composition
    # independent, so the Poisson-mixed run must emit identical tokens.
    oracle_eng = InferenceEngineV2(moe_model, moe_params, moe_icfg)
    mism = 0
    for i in range(min(parity_samples, n_requests)):
        lg = oracle_eng.put([i], [prompts[i]])
        first = int(np.asarray(lg)[0].argmax())
        toks = [first] + np.asarray(oracle_eng.decode_loop(
            [i], [first], max_new - 1))[0].tolist()
        mism += toks != moe_out[i]
    assert mism == 0, (f"moe token parity broken: {mism}/{parity_samples} "
                       f"sampled requests diverge batched-vs-sequential")
    return {
        "trace": _trace_record(seed, prompts, max_new, load, arrivals,
                               capacity=cap),
        "n_requests": n_requests,
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "moe_impl": "ragged",
        "entries": entries,
        "goodput_vs_dense": round(
            entries["moe"]["sustained_tokens_per_sec"]
            / entries["dense"]["sustained_tokens_per_sec"], 3),
        "token_mismatches_vs_oracle": mism,
        "parity_samples": parity_samples,
    }


def _jaxpr_peak_var_bytes(jaxpr) -> int:
    """Largest single intermediate array (bytes) in the jaxpr's MANUAL
    region (the shard_map body — vars there have per-chip local shapes),
    subjaxprs included; falls back to the whole jaxpr when no manual
    region exists. The honest per-chip working-set proxy the ring scaling
    row reports: the outer jaxpr's operands keep their GLOBAL [B, T, ...]
    shapes at every CP degree, so only the in-region vars show the
    O(seq/CP) attention-memory scaling."""
    import jax

    j = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr

    def find_manual(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "shard_map":
                inner = eqn.params["jaxpr"]
                return inner.jaxpr if hasattr(inner, "jaxpr") else inner
        for sub in jax.core.subjaxprs(jx):
            got = find_manual(sub)
            if got is not None:
                return got
        return None

    j = find_manual(j) or j
    best = 0

    def visit(jx):
        nonlocal best
        for eqn in jx.eqns:
            for var in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(var, "aval", None)
                shape = getattr(aval, "shape", None)
                dtype = getattr(aval, "dtype", None)
                if shape is not None and dtype is not None:
                    n = int(np.prod(shape)) if len(shape) else 1
                    best = max(best, n * dtype.itemsize)
        for sub in jax.core.subjaxprs(jx):
            visit(sub)

    visit(j)
    return best


def ring_scaling_row(*, cp_degrees=(1, 2, 4), d=256, heads=4, layers=2,
                     seq=512, vocab=512, batch=8, steps=3, seed=0):
    """Config-2 ring-attention context-parallel scaling entry (ISSUE 15):
    tokens/s and per-chip attention peak-memory vs CP degree on the
    virtual mesh (SURVEY §2.6's missing parallelism; Ring Attention +
    FPDT §5.7). Per degree: a full ``sxt.initialize`` training engine
    with ``context_parallel.degree`` set (ring KV rotation via ppermute,
    online-softmax accumulation), measuring steady-state train-step
    tokens/s, the first-step loss (parity across degrees — exact
    softmax), and the largest single intermediate in the local attention
    region's jaxpr (O(seq/CP): the per-chip score tile shrinks with the
    ring). CPU-mesh numbers are SHAPE evidence, not speed — the on-chip
    row is not measured (ROADMAP R5 f). Reused at toy size by
    tests/test_bench_smoke.py."""
    import time as _time

    import jax
    from jax.sharding import PartitionSpec as P

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer, tiny
    from shuffle_exchange_tpu.parallel import reset_topology
    from shuffle_exchange_tpu.parallel.mesh import (MeshTopology,
                                                    shard_map)
    from shuffle_exchange_tpu.parallel.sequence import ring_attention

    n_dev = len(jax.devices())
    degrees = [c for c in cp_degrees if c <= n_dev and seq % c == 0
               and n_dev % c == 0]
    if not degrees:
        return {"pending": f"needs a multi-device mesh (have {n_dev}); "
                           f"publish on the next TPU window"}
    rng = np.random.default_rng(seed)
    # ONE batch shared by every degree — the loss-parity claim is exact
    # softmax over IDENTICAL data, so the same tokens must divide each
    # degree's data world; any multiple of n_dev does (data world =
    # n_dev / cp for every surviving degree)
    b = ((max(batch, n_dev) + n_dev - 1) // n_dev) * n_dev
    batch_ids = rng.integers(0, vocab, size=(b, seq)).astype(np.int32)
    entries = []
    for cp in degrees:
        reset_topology()
        model = Transformer(tiny(vocab=vocab, d=d, layers=layers,
                                 heads=heads, seq=seq,
                                 activation="swiglu", norm="rmsnorm",
                                 position="rope"))
        cfg = {"train_batch_size": b,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
               "steps_per_print": 10**9}
        if cp > 1:
            cfg["context_parallel"] = {"degree": cp}
        eng, *_ = sxt.initialize(model=model, config=cfg)
        loss0 = float(eng.train_batch({"input_ids": batch_ids}))
        t0 = _time.perf_counter()
        for _ in range(steps):
            eng.train_batch({"input_ids": batch_ids})
        dt = (_time.perf_counter() - t0) / steps
        # per-chip attention working set: the local ring region's largest
        # intermediate at this degree's shard length (seq/cp)
        from shuffle_exchange_tpu.config.config import MeshConfig

        reset_topology()
        topo = MeshTopology.build(
            MeshConfig(data=1, seq=max(1, cp)), n_devices=max(1, cp))
        B, H, D = 1, heads, d // heads
        q = np.zeros((B, seq, H, D), np.float32)
        spec = P(None, "seq", None, None)
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                           causal=True, use_kernel=False),
            mesh=topo.mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        attn_bytes = _jaxpr_peak_var_bytes(
            jax.make_jaxpr(fn)(q, q, q))
        entries.append({
            "cp": cp,
            "batch_run": b,
            "tokens_per_sec": round(b * seq / dt, 1),
            "step_s": round(dt, 4),
            "loss": round(loss0, 6),
            "attention_peak_bytes_per_chip": attn_bytes,
        })
    base = entries[0]
    for e in entries:
        e["attention_mem_vs_cp1"] = round(
            e["attention_peak_bytes_per_chip"]
            / base["attention_peak_bytes_per_chip"], 3)
    reset_topology()
    return {
        "seq": seq, "batch": batch, "d_model": d, "layers": layers,
        "degrees": degrees,
        "entries": entries,
        "loss_parity": max(abs(e["loss"] - base["loss"])
                           for e in entries),
        "note": ("CPU virtual-mesh shape evidence: attention memory "
                 "O(seq/CP); tokens/s on chip not measured"),
    }


def serving_autotune_row(model, params, icfg, vocab, *, n_requests=16,
                         prompt_lo=48, prompt_hi=192, max_new=16,
                         load=2.0, seed=0, rounds=2, max_programs=512,
                         axes=None, journal_dir=None):
    """Config-5 serving-autotune row (ISSUE 14): a bounded successive-
    halving search of the serving knob families (scheduler packing shape,
    chunk/k ladders, KV/kernel modes) against the SAME seeded Poisson
    goodput trace, headline = the tuned-vs-default goodput delta.

    Search discipline (autotuning/search.py): capacity is calibrated once
    on the default config and every candidate then faces identical
    arrival offsets (paired trace, variance-controlled ranking);
    candidates whose declared ladders blow the warmed-server compile
    budget are pruned STATICALLY and never measured
    (``pruned_never_measured`` asserts it); every measured trial warms
    its shape-bin ladder and then must compile nothing during the
    measured pass (``zero_recompile_all_trials``). The winner is emitted
    as a loadable ServingConfig overlay — the same artifact
    ``scripts/autotune_serving.py`` writes to disk. Reused at toy size by
    tests/test_bench_smoke.py so the published row cannot rot on CPU."""
    from shuffle_exchange_tpu.autotuning import PoissonTrace
    from shuffle_exchange_tpu.autotuning.search import run_serving_search

    trace = PoissonTrace.generate(seed, vocab=vocab, n_requests=n_requests,
                                  prompt_lo=prompt_lo, prompt_hi=prompt_hi,
                                  max_new=max_new)
    out = run_serving_search(model, params, icfg, trace=trace, axes=axes,
                             rounds=rounds, load=load,
                             max_programs=max_programs,
                             journal_dir=journal_dir)
    row = out.summary()
    row.update({
        "prompt_tokens": [prompt_lo, prompt_hi],
        "max_new_tokens": max_new,
        "offered_load_x": load,
        "rounds": rounds,
        "engines_built": out.objective.engines_built,
        # finals only: screening metrics come off a trace PREFIX and are
        # not comparable with full-trace goodput in one ranking
        "ranked_final": [
            {"candidate": t.candidate_name, "round": t.round,
             "goodput_tokens_per_sec": (round(t.metric, 2)
                                        if t.metric is not None else None),
             "feasible": bool(t.detail.get("feasible", True))}
            for t in out.result.ranked(final_only=True)[:8]],
    })
    return row


def rlhf_rollout_row(model_cfg, *, n_rollouts=8, shared_len=64,
                     suffix_lo=8, suffix_hi=32, max_new=32, flips=3,
                     kv_block=64, seed=0, toy=False):
    """Config-5 RLHF-rollout row (ISSUE 11): the hybrid engine's two
    headline numbers — rollout goodput through the serving fleet (shared-
    prompt batches, so the prefix cache absorbs the common system-prompt
    span) and the train->serve FLIP latency (jitted ZeRO gather + two-
    phase fleet publish), measured across ``flips`` train->publish->
    generate cycles on a warmed fleet with the zero-recompile and replay
    contracts asserted. Reused at toy size by tests/test_bench_smoke.py
    so the published row cannot rot on CPU; the on-chip figures are
    not measured (ROADMAP R6)."""
    import dataclasses as _dc

    import jax as _jax

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.rlhf import HybridEngineV2, RLHFLoop, pg_loss_fn

    cfg = _dc.replace(model_cfg, remat=False)
    model = Transformer(cfg)
    vocab = cfg.vocab_size
    rng = np.random.default_rng(seed)
    S = cfg.max_seq_len
    bs = min(kv_block, S)
    while bs > 1 and S % bs:
        bs //= 2
    n_dev = len(_jax.devices())
    tbs = max(n_rollouts, n_dev)
    engine, *_ = sxt.initialize(model=model, loss_fn=pg_loss_fn(model),
                                config={
        "train_batch_size": tbs,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": not toy},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9,
    })
    hy = HybridEngineV2(engine, model, inference_config={
        "dtype": "float32" if toy else "bfloat16",
        "max_seq_len": S, "kv_block_size": bs,
        "num_kv_blocks": 8 * max(1, S // bs) + 8,
        "prefix_caching": True,
        "serving": {"token_budget": max(64, 2 * shared_len),
                    "max_running": 8,
                    "chunk_min": min(16, bs)},
    })
    shared = rng.integers(1, vocab, size=shared_len).tolist()
    prompts = [shared + rng.integers(1, vocab, size=int(n)).tolist()
               for n in rng.integers(suffix_lo, suffix_hi + 1, size=tbs)]
    loop = RLHFLoop(hy, reward_fn=lambda p, t: float(len(set(t))),
                    seq_len=min(S, shared_len + suffix_hi + max_new))
    # warm: build the fleet, compile the ladder + the train step
    loop.pg_step(loop.rollout(prompts, max_new_tokens=max_new))
    progs0 = [r.engine.program_shapes for r in hy.router.replicas]
    flip_s, gen_s, gen_tokens = [], [], 0
    for _ in range(flips):
        t0 = time.perf_counter()
        hy.eval()
        hy.publish_weights()
        flip_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        records = hy.rollout(prompts, max_new_tokens=max_new)
        gen_s.append(time.perf_counter() - t0)
        gen_tokens += sum(len(r.tokens) for r in records)
        loop.pg_step(records)
    # the zero-recompile flag covers exactly the flip loop — snapshot
    # before the replay drill below adds its own (legitimate, cold)
    # single-request shapes
    no_recompiles = ([r.engine.program_shapes
                      for r in hy.router.replicas] == progs0)
    st = hy.fleet_stats()
    sched_stats = hy.router.replicas[0].scheduler.stats()
    verified, _ = hy.replay_log.verify(
        hy, hy.replay_log.at_version(hy.weight_version)[:2])
    return {
        "n_rollouts": tbs,
        "shared_prefix_tokens": shared_len,
        "suffix_tokens": [suffix_lo, suffix_hi],
        "max_new_tokens": max_new,
        "flips": flips,
        "flip_s_median": round(float(np.median(flip_s)), 4),
        "gather_s_total": round(hy.gather_latency_s, 4),
        "rollout_tokens_per_sec": round(
            gen_tokens / max(1e-9, sum(gen_s)), 1),
        "prefix_cache_hit_rate": (
            round(sched_stats["prefix_cache"]["hit_rate"], 3)
            if sched_stats["prefix_cache"]["hit_rate"] is not None else None),
        "weight_version": hy.weight_version,
        "train_steps": engine.global_steps,
        "publishes": hy.publisher.publishes,
        "replays_bit_exact": verified,
        "zero_recompile_across_flips": no_recompiles,
        "kv_pools_intact": all(
            r.engine.free_blocks == r.engine.allocator.num_blocks - 1
            for r in hy.router.replicas),
        "weight_versions_converged": (
            len(set(st["weight_versions"].values())) == 1),
    }


def bench_serving(label, model_cfg, peak_flops, hbm_bw=None):
    """Config #5: engine_v2 paged prefill + decode tokens/s.

    Round 5 (VERDICT r4 #6): decode latency is published at ENGINE level —
    ``decode_loop`` runs N greedy steps as one device program, so the
    number excludes the per-``put`` host round trip — with a batch sweep,
    serving MFU, and HBM bandwidth utilization (decode is weight-bandwidth
    bound: bytes/token ≈ param bytes + KV-read bytes)."""
    import jax

    from shuffle_exchange_tpu.inference import InferenceConfig, InferenceEngineV2
    from shuffle_exchange_tpu.models import Transformer

    cfg = dataclasses.replace(model_cfg, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = _param_count(cfg)

    bsz, prompt_len, decode_steps = 4, 512, 48
    icfg = InferenceConfig(dtype="bfloat16", max_seq_len=2048,
                           kv_block_size=64, num_kv_blocks=4 * (2048 // 64) + 8)
    eng = InferenceEngineV2(model, params, icfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(bsz)]
    uids = list(range(bsz))

    # warm both programs (prefill bucket + batched decode)
    logits = eng.put(uids, prompts)              # put() returns host np: syncs
    nxt = [[int(np.argmax(logits[i]))] for i in range(bsz)]
    logits = eng.put(uids, nxt)

    t0 = time.perf_counter()
    eng.flush(uids)
    logits = eng.put(uids, prompts)
    prefill_s = time.perf_counter() - t0

    # Device-side prefill figure (VERDICT r5 missing #3, finished round 9):
    # the decode_loop discipline applied to prefill — ONE jitted program
    # scans the compiled batched-prefill body ``reps`` times (idempotent
    # rewrites of the sequences' own blocks), so the host round trip
    # and the logits readback are amortized reps-fold and the figure
    # measures the COMPILED program, not the RTT. This replaces the round-7
    # "put() wall minus noop-dispatch RTT" estimate, which drifted ~25% run
    # to run; the per-put number stays published as the API-latency figure.
    import jax as _jax

    reps = 4
    descs = [eng._seqs[u] for u in uids]
    P_, tpad_, pf_ids, pf_len, pf_bt = eng._pack_prefill(
        list(zip(descs, prompts)))
    prefill_impl = eng._paged_prefill_impl

    @_jax.jit
    def _prefill_loop(params, cache, ids, plen, btables):
        def body(c, _):
            c, lg = prefill_impl(params, c, ids, plen, btables)
            return c, lg
        return _jax.lax.scan(body, cache, None, length=reps)

    def _run_prefill_loop():
        _, lgs = _prefill_loop(eng.params, eng.cache, pf_ids, pf_len, pf_bt)
        return host_sync(lgs[-1, 0, :1])

    _run_prefill_loop()                          # compile + warm
    prefill_device_s = sorted(_timed(_run_prefill_loop)
                              for _ in range(3))[1] / reps
    prefill_tokens = bsz * prompt_len
    prefill_device_mfu = 2.0 * n_params * prefill_tokens / prefill_device_s / peak_flops

    # Large-batch prefill through the same public put(): 8 x 1024-token
    # prompts = 8192 tokens in ONE dispatch, so the host round trip is
    # amortized 4x vs the bs4x512 figure — the number a batch-serving
    # deployment sees (the bs4x512 row doubles as the small-batch API
    # latency figure).
    try:
        big_prompts = [rng.integers(0, cfg.vocab_size, size=1024).tolist()
                       for _ in range(8)]
        big_uids = list(range(100, 108))
        eng2 = InferenceEngineV2(model, params, icfg)
        eng2.put(big_uids, big_prompts)          # warm the 8x1024 bucket
        eng2.flush(big_uids)
        t0 = time.perf_counter()
        eng2.put(big_uids, big_prompts)
        prefill_big_s = time.perf_counter() - t0
        del eng2                                 # free its KV pool before
        # the quantized / decode-sweep benches below run
    except Exception as e:
        print(f"SXT_WARN big prefill bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        prefill_big_s = None

    nxt = [[int(np.argmax(logits[i]))] for i in range(bsz)]
    t0 = time.perf_counter()
    for _ in range(decode_steps):
        logits = eng.put(uids, nxt)
        nxt = [[int(np.argmax(logits[i]))] for i in range(bsz)]
    decode_s = time.perf_counter() - t0

    decode_tps = bsz * decode_steps / decode_s

    # v1 fused generate: the whole decode loop is ONE on-device program
    # (lax.scan), so the host round trip is paid once, not per token —
    # this is the serving number the engine can actually sustain; the
    # put()-loop number above is an API-latency measurement (each put is a
    # host round trip).
    from shuffle_exchange_tpu.inference.engine import InferenceEngine

    v1 = InferenceEngine(model, params, icfg)
    gen_new = 64
    ids = np.stack([np.asarray(p, np.int32) for p in prompts])

    def fused_median_tps(engine):
        """Median of 3 timed generates: a single timed iteration moved the
        published number by ~30% between runs (one scheduling hiccup or a
        cold cache line is a third of the figure) — same p50 discipline as
        the training benches."""
        engine.generate(ids, max_new_tokens=gen_new)  # compile + warm
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            engine.generate(ids, max_new_tokens=gen_new)   # host np: syncs
            times.append(time.perf_counter() - t0)
        return bsz * gen_new / sorted(times)[1]

    fused_tps = fused_median_tps(v1)

    # Quantized weight-storage tiers (kernel-injection quantization analog,
    # reference GroupQuantizer + FP-quantizer): decode is weight-bandwidth
    # bound, so tokens/s should rank by weight bytes — and does, on the
    # dequant-into-dot path (round 5): int4 964 > int8 927 > fp8 904 >
    # bf16 871 on this config. A failure below is a real quantized-serving
    # regression and must be visible in the record.
    fused_q_tps = {}
    for bits, key in ((8, "int8"), ("fp8", "fp8"), (4, "int4")):
        try:
            icfg_q = dataclasses.replace(icfg, quantize_weights=True,
                                         quant_bits=bits)
            fused_q_tps[key] = fused_median_tps(
                InferenceEngine(model, params, icfg_q))
        except Exception as e:
            print(f"SXT_WARN {key} serving bench failed: {_short_err(e)}",
                  file=sys.stderr, flush=True)
            fused_q_tps[key] = None

    # ---- engine-level decode: paged decode_loop, one dispatch for N
    # tokens, batch sweep (the per-put numbers above include one host RTT
    # per token — an API-latency figure, not the engine's)
    engine_rows = []
    loop_steps = 64
    for b in (1, 4, 8):
        try:
            e2 = InferenceEngineV2(model, params, icfg)
            pr = [rng.integers(0, cfg.vocab_size, size=prompt_len).tolist()
                  for _ in range(b)]
            lg = e2.put(list(range(b)), pr)
            first = [int(np.argmax(lg[i])) for i in range(b)]
            e2.decode_loop(list(range(b)), first, loop_steps)  # compile+warm
            lg = e2.put(list(range(b)), [[1]] * b)
            first = [int(np.argmax(lg[i])) for i in range(b)]
            # mean KV length DURING the timed loop (warm loop + puts have
            # already advanced these sequences)
            kv_len = e2._seqs[0].seen_tokens + loop_steps // 2
            t0 = time.perf_counter()
            toks = e2.decode_loop(list(range(b)), first, loop_steps)
            dt = time.perf_counter() - t0        # one dispatch: RTT paid once
            tps = b * loop_steps / dt
            # per decode step: all weights read once (bf16 bytes) + each
            # sequence's KV read; the step yields b tokens. The kernels
            # stream the block TABLE, not the live KV: every table entry's
            # block goes through VMEM, padding included, so the bytes the
            # chip actually moves are table_tokens = table_width * block
            # per sequence (>= kv_len). Publishing util from live-KV bytes
            # while the kernel streamed a max_seq_len-wide table is the
            # round-5 "hbm_util falls with batch" artifact (ISSUE 5
            # satellite) — decode_loop now bins
            # the table width to the covering power of two, and the sweep
            # publishes BOTH accountings so padding overhead stays visible.
            per_tok_kv = 2 * cfg.n_layers * cfg.kv_heads * cfg.head_dim * 2
            table_tokens = e2._last_decode_table_width * icfg.kv_block_size
            bytes_step = 2.0 * n_params + b * per_tok_kv * kv_len
            bytes_streamed = 2.0 * n_params + b * per_tok_kv * table_tokens
            engine_rows.append({
                "batch": b,
                "engine_ms_per_token": round(1000 * dt / loop_steps, 3),
                "tokens_per_sec": round(tps, 1),
                "mfu": round(2.0 * n_params * tps / peak_flops, 4),
                "kv_len": int(kv_len),
                "table_tokens": int(table_tokens),
                "hbm_util": (round(bytes_step * (tps / b) / hbm_bw, 3)
                             if hbm_bw else None),
                "hbm_util_streamed": (
                    round(bytes_streamed * (tps / b) / hbm_bw, 3)
                    if hbm_bw else None),
            })
        except Exception as e:
            print(f"SXT_WARN decode_loop bench b={b} failed: {_short_err(e)}",
                  file=sys.stderr, flush=True)

    # ---- serving goodput: the continuous-batching scheduler under a
    # Poisson arrival trace (ISSUE 5 — the aggregate-throughput figure the
    # "millions of users" north star actually needs; per-request latency
    # rides along as TTFT/TPOT p50)
    try:
        goodput = serving_goodput_row(model, params, icfg, cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving goodput bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        goodput = None

    # ---- prefix cache: the shared-system-prompt regime (ISSUE 6) — the
    # same Poisson trace with and without prefix_caching; hit-rate and
    # the TTFT delta are the row's headline
    try:
        prefix_row = prefix_cache_row(model, params, icfg, cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN prefix cache bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        prefix_row = None

    # ---- serving fleet: 1 vs 2 router replicas on the same Poisson
    # trace (ISSUE 7) — goodput + TTFT tails; the multi-replica answer to
    # arrivals that outpace one engine's capacity
    try:
        fleet_row = serving_fleet_row(model, params, icfg, cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving fleet bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        fleet_row = None

    # ---- speculative decoding: k=0 vs k=4 on the same repetitive-suffix
    # Poisson trace (ISSUE 8) — the steps-per-token lever on per-sequence
    # latency, with acceptance rate and the token-parity check
    try:
        spec_row = serving_speculative_row(model, params, icfg,
                                           cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving speculative bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        spec_row = None

    # ---- one-dispatch sampling: greedy vs fused in-dispatch sampled vs
    # EOS-early-stop on the same Poisson trace (ISSUE 16) — sampler
    # overhead, early-stop goodput return, seeded-replay verification,
    # and speculative acceptance at temperature > 0
    try:
        sampling_row = serving_sampling_row(model, params, icfg,
                                            cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving sampling bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        sampling_row = None

    # ---- serving failover: the same Poisson trace clean vs with one
    # mid-trace unclean replica kill (ISSUE 12) — goodput retention,
    # recovered-request count, and the TTFT p95 delta an unclean death
    # costs under load, with per-request token parity asserted
    try:
        failover_row = serving_failover_row(model, params, icfg,
                                            cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving failover bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        failover_row = None

    # ---- async weight sync: the same Poisson trace with mid-trace
    # publishes, barrier two-phase vs async shuffle-exchange gossip
    # (ISSUE 20) — per-publish stall, goodput retention, the honest
    # weight_version census, and the bounded-staleness + converge()
    # contracts, with token parity asserted (same-bytes publishes)
    try:
        async_publish_row = serving_async_publish_row(model, params, icfg,
                                                      cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving async-publish bench failed: "
              f"{_short_err(e)}", file=sys.stderr, flush=True)
        async_publish_row = None

    # ---- long-context tiered KV: the same Poisson trace on constrained
    # pools, spill-on vs the refuse-admission baseline vs an
    # unconstrained-pool reference (ISSUE 15) — goodput, TTFT/TPOT p95,
    # tier hit-rate, with token parity asserted under bf16 KV
    try:
        longctx_row = serving_longctx_row(model, params, icfg,
                                          cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving longctx bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        longctx_row = None

    # ---- multi-tenant LoRA: the same Poisson trace striped across 1 vs
    # 8 vs 64 adapters on a fixed 4-slot pool (ISSUE 18) — goodput
    # retention under adapter paging, pool hit-rate, park counts (zero
    # preemptions), with mixed-vs-solo token parity asserted
    try:
        multi_tenant_row = serving_multi_tenant_row(model, params, icfg,
                                                    cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving multi-tenant bench failed: "
              f"{_short_err(e)}", file=sys.stderr, flush=True)
        multi_tenant_row = None

    # ---- expert-parallel MoE serving: the same Poisson trace on the
    # dense baseline vs an MoE twin at matched total params (ISSUE 19) —
    # goodput, TTFT/TPOT tails, routing counters and expert-load balance,
    # with batched-vs-sequential token parity asserted under the ragged
    # (dropless) route
    try:
        moe_row = serving_moe_row(model, params, icfg, cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving moe bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        moe_row = None

    # ---- serving autotune: bounded successive-halving search of the
    # serving knobs against the paired Poisson goodput trace (ISSUE 14) —
    # tuned-vs-default delta, static-prune and zero-recompile contracts,
    # and the winner overlay a deployment can load directly
    try:
        autotune_row = serving_autotune_row(model, params, icfg,
                                            cfg.vocab_size)
    except Exception as e:
        print(f"SXT_WARN serving autotune bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        autotune_row = None

    # ---- RLHF rollout: the hybrid engine's flip latency + rollout
    # goodput (ISSUE 11) — train -> publish -> generate cycles on a warmed
    # fleet, shared-prompt rollout batches (the prefix cache's regime),
    # with the zero-recompile / replay / version-convergence contracts
    # reported alongside the timings
    try:
        rlhf_row = rlhf_rollout_row(model_cfg)
    except Exception as e:
        print(f"SXT_WARN rlhf rollout bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        rlhf_row = None

    # decode FLOPs ≈ 2*N per token (fwd only) -> model-bandwidth utilization
    best_tps = max([decode_tps, fused_tps]
                   + [r["tokens_per_sec"] for r in engine_rows])
    decode_mfu = 2.0 * n_params * best_tps / peak_flops
    # headline latency = the bs-1 row (pure inter-token latency; the sweep
    # rows report ms between consecutive tokens of one sequence at each
    # batch width, which is throughput-facing for b > 1)
    eng_best = next((r for r in engine_rows if r["batch"] == 1),
                    engine_rows[0] if engine_rows else None)
    # headline bandwidth-utilization figure (round 6, VERDICT r5 #2): the
    # bs-1 hbm_util from streamed bytes/step (weights once + live KV once)
    # against the chip's HBM bandwidth. Tracked goal (ROADMAP S1):
    # >= 0.5 on chip (the XLA layer body measured 0.183 on 2026-07-31,
    # BASELINE.json engine_decode_sweep, before PR 1; the fused
    # decode_kernel path exists to close that gap).
    return {
        "config": label,
        "params_m": round(n_params / 1e6, 1),
        "batch_size": bsz,
        "prompt_len": prompt_len,
        "prefill_tokens_per_sec": round(bsz * prompt_len / prefill_s, 1),
        "prefill_device_tokens_per_sec": round(prefill_tokens / prefill_device_s, 1),
        "prefill_device_mfu": round(prefill_device_mfu, 4),
        "prefill_note": ("prefill_device_* = DEVICE-measured: one jitted "
                         f"program scans the compiled batched-prefill body "
                         f"{reps}x (median of 3), so host RTT and logits "
                         "readback amortize away — the decode_loop "
                         "discipline applied to prefill (replaces the "
                         "round-7 RTT-subtraction estimate). "
                         "prefill_tokens_per_sec is the per-put() API "
                         "latency figure and includes one host RTT"),
        "prefill_bs8x1024_tokens_per_sec": (
            round(8 * 1024 / prefill_big_s, 1) if prefill_big_s else None),
        "decode_tokens_per_sec": round(decode_tps, 1),
        "decode_ms_per_token": round(1000 * decode_s / decode_steps, 2),
        "put_api_note": "per-put numbers include one host RTT per token",
        "engine_decode_sweep": engine_rows,
        "serving_goodput": goodput,
        "serving_prefix_cache": prefix_row,
        "serving_fleet": fleet_row,
        "serving_speculative": spec_row,
        "serving_sampling": sampling_row,
        "serving_failover": failover_row,
        "serving_async_publish": async_publish_row,
        "serving_longctx": longctx_row,
        "serving_multi_tenant": multi_tenant_row,
        "serving_moe": moe_row,
        "serving_autotune": autotune_row,
        "rlhf_rollout": rlhf_row,
        "engine_ms_per_token": (eng_best["engine_ms_per_token"]
                                if eng_best else None),
        "decode_hbm_util": (eng_best or {}).get("hbm_util"),
        "decode_kernel": getattr(eng, "_decode_kernel", "xla"),
        "serving_mfu": round(decode_mfu, 4),
        "fused_generate_tokens_per_sec": round(fused_tps, 1),
        **{f"fused_generate_{key}_tokens_per_sec":
           (round(tps, 1) if tps else None)
           for key, tps in fused_q_tps.items()},
        "valid": bool(decode_mfu <= 1.0),
        "unit": "tokens/s",
    }


# ---------------------------------------------------------------------------


def publish(rows, calib_record):
    path = os.path.join(REPO, "BASELINE.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    # merge, don't replace: a run that lost a config keeps the rows the
    # last complete run published
    published = dict(doc.get("published", {}))
    published["calibration"] = calib_record
    published.update(rows)
    doc["published"] = published
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def _config1(peak, hbm, n_chips, hbm_bw=None):
    from shuffle_exchange_tpu.models import Transformer, gpt2_small

    # bs16: on-chip sweep of 2026-07-31 (before PR 1) — 24.5% MFU / 64.7k
    # tok/s vs 20.4% / 53.8k at bs8; tuning mbs is the reference
    # autotuner's own methodology (autotuning/README.md's GPT-2 example)
    cfg1 = {
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10**9,
    }
    return "config1_gpt2_125m_zero1", bench_train(
        "gpt2-125M zero1 bf16 bs16", Transformer(gpt2_small()), cfg1,
        batch_size=16, seq_len=1024, steps=15, warmup=3,
        peak_flops=peak, n_chips=n_chips)


def _config2(peak, hbm, n_chips, hbm_bw=None):
    from shuffle_exchange_tpu.models import Transformer

    name2, mcfg2 = pick_config2(hbm)
    # full per-layer remat: dots_saveable keeps every matmul output
    # (~1.2GB/layer at bs 8 x 4096) and OOMs a 16GB chip; saving only
    # the residual stream costs ~33% recompute FLOPs and fits.
    # Geometry (round-5 on-chip sweep, scripts/tune_config2.py): the 6N·tok
    # MFU formula bills neither the quadratic attention matmuls nor remat
    # recompute, so billed MFU rises as seq shrinks at fixed tokens/step
    # (35.4% @ bs8x4096 -> 39.6% @ bs16x2048 -> 41.4% @ bs32x1024; real
    # silicon utilization is ~64% counting executed FLOPs). The primary row
    # uses the throughput-optimal bs32x1024 (the reference's own autotuning
    # README headlines GPT-2 at seq 1024 with a tuned micro-batch); the
    # seq-4096 row stays published for r3/r4 comparability.
    cfg2 = {
        "train_batch_size": 8,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9,
    }
    mtuned = dataclasses.replace(mcfg2, remat=True,
                                 remat_policy="nothing_saveable",
                                 max_seq_len=1024)
    row = bench_train(
        f"{name2} zero3 + pallas fused adam, autotuned bs32x1024 "
        "(8B does not fit 1 chip; scaled)",
        Transformer(mtuned), dict(cfg2, train_batch_size=32),
        batch_size=32, seq_len=1024,
        steps=10, warmup=3, peak_flops=peak, n_chips=n_chips)
    m4096 = dataclasses.replace(mcfg2, remat=True,
                                remat_policy="nothing_saveable",
                                max_seq_len=4096)
    row4096 = bench_train(
        f"{name2} zero3 + pallas fused adam, bs8x4096 (r3-comparable)",
        Transformer(m4096), cfg2, batch_size=8, seq_len=4096,
        steps=10, warmup=3, peak_flops=peak, n_chips=n_chips)
    row["seq4096_row"] = row4096
    # Host-offload ladder entry (the two untried config-2 levers, round 7):
    # ~1.7B fits via the cpu tier + overlapped optimizer pipeline, with the
    # save_flash_lse remat policy cutting the flash-forward recompute. The
    # per-step time budget rides in offload_budget.
    name_h, mcfg_h, ds_h, bs_h, seq_h = host_offload_ladder_entry()
    try:
        row["host_offload_row"] = bench_train(
            f"{name_h} cpu-offload overlapped optimizer + save_flash_lse "
            "(fits one chip only via the host tier: 2 B/param device vs 14)",
            Transformer(mcfg_h), ds_h, batch_size=bs_h, seq_len=seq_h,
            steps=8, warmup=2, peak_flops=peak, n_chips=n_chips,
            offload_budget=True)
    except Exception as e:
        print(f"SXT_WARN host-offload ladder bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        row["host_offload_row"] = {"error": _short_err(e)}
    # Ring-attention CP scaling entry (ISSUE 15): tokens/s + per-chip
    # attention peak-memory vs CP degree. On one chip this reports
    # pending (the ring needs a live multi-device mesh); a CPU virtual
    # mesh measures the shape claims.
    try:
        row["ring_attention_row"] = ring_scaling_row()
    except Exception as e:
        print(f"SXT_WARN ring scaling bench failed: {_short_err(e)}",
              file=sys.stderr, flush=True)
        row["ring_attention_row"] = {"error": _short_err(e)}
    return "config2_llama3_zero3_fused_adam", row


def _config3(peak, hbm, n_chips, hbm_bw=None):
    from shuffle_exchange_tpu.models import Transformer, TransformerConfig

    # capacity with INDEX dispatch (round 5): the GShard one-hot
    # dispatch/combine einsums are real matmuls costing ~4x the expert
    # compute at these shapes; the index form (scalar slot scatter + row
    # gathers, identical capacity/drop semantics) measured 1.84x faster
    # end-to-end on-chip (23.1% vs 12.5% active-param MFU at bs8x2048).
    # megablox ragged under the layer scan measured 5.3% then, with the
    # kernel's default 128^3 tile (PR 28 found the tile, not the scan, to be
    # the cause: PERF.md section 6). Geometry bs32x1024 per the same
    # unbilled-attention analysis as config 2. Head geometry matches
    # Mixtral's Dh=128 / G=4 (same reasoning as the config-2 ladder).
    mcfg3 = TransformerConfig(
        vocab_size=32768, d_model=1024, n_layers=8, n_heads=8,
        n_kv_heads=2, max_seq_len=2048, activation="swiglu",
        norm="rmsnorm", position="rope", tie_embeddings=True,
        n_experts=8, moe_top_k=2, moe_impl="capacity", remat=True,
        remat_policy="nothing_saveable")
    cfg3 = {
        "train_batch_size": 32,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10**9,
    }
    row = bench_train(
        "mixtral-style 8-expert top-2, index dispatch, bs32x1024 "
        "(scaled; 8x7B does not fit 1 chip)",
        Transformer(mcfg3), cfg3, batch_size=32, seq_len=1024,
        steps=10, warmup=3, peak_flops=peak, n_chips=n_chips)
    row["note"] = "mfu bills activated (top-k/E) expert params"
    return "config3_moe_8x", row


def _config5(peak, hbm, n_chips, hbm_bw=None):
    name5, mcfg5 = pick_config2(hbm)
    return "config5_paged_serving", bench_serving(
        f"{name5} engine_v2 paged serving", mcfg5, peak, hbm_bw=hbm_bw)


_CONFIGS = {"1": _config1, "2": _config2, "3": _config3, "5": _config5}
# per-config wall budgets: a stuck compile must cost one config, not the
# whole bench
_BUDGET_S = {"1": 480, "2": 1800, "3": 900, "5": 1800}   # 2: + the host-
# offload ladder row's extra compile; 5: four quant
# tiers x3 medians + big prefill + decode sweep + the bounded autotune
# search (compile cache makes the steady-state ~5 min; the budget covers
# a cold cache)
_CALIBRATE_BUDGET_S = 300


def _hw():
    """The chip this CHILD process holds. Raises unless it is a TPU whose
    peaks are published and whose memory limit is known."""
    import jax

    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py needs a TPU; JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}). A CPU run gives no device number")
    return (dev, len(jax.devices()), chip_peak_flops(dev), hbm_bytes(dev),
            chip_hbm_bandwidth(dev))


def _run_calibration() -> None:
    """Child entry: probe the chip and calibrate the clock; print ONE
    ``SXT_CALIB`` line. Any failure is this child's non-zero exit."""
    dev, n_chips, peak, hbm, _ = _hw()
    achieved, rtt, cal_ok = calibrate(peak)
    print("SXT_CALIB " + json.dumps({
        "chip": dev.device_kind,
        "n_chips": n_chips,
        "peak_tflops_assumed": round(peak / 1e12, 1),
        "matmul_chain_tflops": round(achieved / 1e12, 1),
        "host_sync_rtt_ms": round(rtt * 1000, 2),
        "hbm_gb": round(hbm / 2**30, 1),
        "ok": bool(cal_ok),
    }), flush=True)


def _run_one_config(which: str) -> None:
    """Child entry: run one config, print ONE {"row_key", "row"} line."""
    dev, n_chips, peak, hbm, hbm_bw = _hw()
    key, row = _CONFIGS[which](peak, hbm, n_chips, hbm_bw)
    print("SXT_ROW " + json.dumps({"row_key": key, "row": row}), flush=True)


def _child(args, tag: str, budget_s: int):
    """Run ``bench.py <args>`` to its end and return (payload, error): the
    JSON after its last ``tag`` line, or one bounded line saying why there
    is none. Sequential by construction - the chip has one owner."""
    import subprocess

    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *args],
            capture_output=True, text=True, timeout=budget_s)
    except subprocess.TimeoutExpired:
        return None, f"timeout after {budget_s}s (budgeted)"
    line = next((l for l in reversed(proc.stdout.splitlines())
                 if l.startswith(tag + " ")), None)
    if proc.returncode == 0 and line:
        return json.loads(line[len(tag) + 1:]), None
    tail = " ".join((proc.stderr or proc.stdout).split())[-300:]
    return None, f"rc={proc.returncode}: {tail}"


def main() -> int:
    if len(sys.argv) >= 2 and sys.argv[1] == "--calibrate":
        _run_calibration()
        return 0
    if len(sys.argv) >= 3 and sys.argv[1] == "--config":
        _run_one_config(sys.argv[2])
        return 0

    # This parent stays off JAX until its last child has finished.
    calib_record, err = _child(["--calibrate"], "SXT_CALIB", _CALIBRATE_BUDGET_S)
    if err is not None:
        print(json.dumps({"metric": "device probe / calibration failed",
                          "value": 0, "unit": "tokens/s/chip", "valid": False,
                          "errors": {"calibration": err}}))
        return 1
    rows, errors = {}, {}

    # -- configs, each in its OWN subprocess with a wall budget ---------
    # (a hung compile or an OOM kills one config, not the bench; rows
    # publish incrementally so a driver-level timeout keeps them)
    for w in ("1", "2", "3", "5"):
        parsed, err = _child(["--config", w], "SXT_ROW", _BUDGET_S[w])
        if err is not None:
            errors[f"config{w}"] = err
        else:
            rows[parsed["row_key"]] = parsed["row"]
        try:
            publish(rows, calib_record)   # incremental
        except OSError as e:
            errors["publish"] = _short_err(e)

    # -- headline line --------------------------------------------------
    head = rows.get("config2_llama3_zero3_fused_adam") or next(iter(rows.values()), None)
    if head is None:
        print(json.dumps({"metric": "bench failed", "value": 0, "unit": "tokens/s/chip",
                          "valid": False, "errors": errors}))
        return 1
    valid = bool(calib_record["ok"] and head.get("valid"))
    calib_note = (f"calib {calib_record['matmul_chain_tflops']}/"
                  f"{calib_record['peak_tflops_assumed']} TFLOP/s")
    if "mfu_pct" in head:   # training row
        metric = (f"train tokens/sec/chip ({head['config']}, "
                  f"step p50 {head['step_p50_ms']:.0f}ms, "
                  f"MFU {head['mfu_pct']:.1f}%, {calib_note})")
        value = head["tokens_per_sec_chip"]
    else:                   # serving row
        metric = (f"serving decode tokens/sec ({head['config']}, "
                  f"{head['decode_ms_per_token']:.0f}ms/token, {calib_note})")
        value = head["decode_tokens_per_sec"]
    result = {
        "metric": metric,
        "value": value,
        "unit": head.get("unit", "tokens/s/chip"),
        "valid": valid,
    }
    if valid and "mfu_pct" in head:
        result["vs_baseline"] = round(head["mfu_pct"] / 100.0 / 0.45, 4)
    if errors:
        result["errors"] = errors
    print(json.dumps(result))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
