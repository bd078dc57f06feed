#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            one TPU chip: kernels, trainer, server
    python chip_smoke.py --chips 4  four chips: ZeRO-3 sharded training only

One process, the normal entry points (``sxt.initialize(...).train_batch``;
``ContinuousBatchingScheduler(InferenceEngineV2(...)).serve``), published
widths, seeded random weights. Every phase prints one JSON line (model, what
was cut under ``"reduced"``, compile vs run seconds, programs compiled, peak
device bytes, the kernel route each dispatch site resolved to, and the
phase's own numbers); any exception or failed check exits non-zero. The last
line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Without a TPU it fails as its first act: there is no size or platform option
that could make a CPU run look like a pass. ``tests/test_chip_smoke.py``
imports the phase functions and rehearses them at ``tiny()`` size on the CPU.
Nothing here is a measurement of speed: seconds are printed so that the next
builder can budget chip time, not to be compared.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(**record) -> None:
    print(json.dumps(record, default=float), flush=True)


def conclude(record: dict, checks) -> dict:
    """Print a phase's line with its verdict, then raise the first check
    that did not hold. ``checks``: (condition, message) pairs."""
    emit(ok=all(cond for cond, _ in checks), **record)
    for cond, msg in checks:
        require(cond, msg)
    return record


class CompileMeter:
    """Counts the programs JAX compiles (or reads back from the persistent
    cache) and the seconds that takes, through ``jax.monitoring`` - the
    backend-compile event wraps the cache lookup, so a hit is counted as a
    program with a small duration and also as a ``cache_hit``."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self._COMPILE:
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def mark(self):
        return (self.programs, self.seconds, self.cache_hits, time.perf_counter())

    def since(self, mark) -> Dict[str, float]:
        p, s, h, t = mark
        wall = time.perf_counter() - t
        compile_s = self.seconds - s
        return {"programs_compiled": self.programs - p,
                "compile_cache_hits": self.cache_hits - h,
                "compile_s": round(compile_s, 2),
                "run_s": round(max(0.0, wall - compile_s), 2)}


def device_memory(devices) -> List[Optional[Dict[str, int]]]:
    """``memory_stats()`` of each device, cut to the two numbers the smoke
    prints (None where the backend keeps none: the CPU rehearsal)."""
    out = []
    for d in devices:
        s = d.memory_stats()
        out.append(None if s is None else
                   {"bytes_in_use": int(s["bytes_in_use"]),
                    "peak_bytes_in_use": int(s["peak_bytes_in_use"])})
    return out


def _release() -> None:
    """Drop what the finished phase left on the device before the next one
    sizes itself against 16 GB."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# Phase 1: kernels on silicon
# ---------------------------------------------------------------------------


def phase_kernels(meter: CompileMeter) -> dict:
    from shuffle_exchange_tpu.testing import kernel_parity

    mark = meter.mark()
    records = list(kernel_parity.run())
    failed = [r for r in records if not r["ok"]]
    out = {"phase": "kernels", "checks": len(records), "failed": failed,
           "worst": max(records, key=lambda r: r["err"] / r["tol"]
                        if r["tol"] else float(r["err"] > 0))["name"],
           **meter.since(mark)}
    return conclude(out, [(not failed, f"{len(failed)} kernel parity checks "
                           f"failed: {[r['name'] for r in failed]}")])


# ---------------------------------------------------------------------------
# Phase 2: trainer
# ---------------------------------------------------------------------------


def train_config(batch: int, zero_stage: int = 3, mesh: Optional[dict] = None):
    """The README quickstart's training config (FusedAdam, warmup-cosine,
    bf16, ZeRO-3) at the smoke's batch. ZeRO++'s int8 wire flags are left
    out: one chip has no wire, and there they only round the weights the
    float32 reference is compared with."""
    cfg = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        # the quickstart warms up over 100 steps; the smoke takes 8, so its
        # warm-up is 8: the loss has to fall within them, and a ramp that is
        # still rising does it without the spikes 3e-4 from step 2 gave
        "scheduler": {"type": "WarmupCosineLR",
                      "params": {"warmup_num_steps": 8,
                                 "total_num_steps": 10000}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    if mesh:
        cfg["mesh"] = mesh
    return cfg


def seeded_batch(vocab: int, batch: int, seq: int, seed: int):
    """``seq`` + 1 ids per row: the model sees ``seq`` positions and is
    scored on the shifted ``seq`` labels."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(batch, seq + 1),
                                      dtype=np.int32)}


def reference_loss(model_cfg, params, batch, device=None, dtype=None):
    """The same loss by the plainest route the repo has: jnp attention (no
    Pallas kernel anywhere in it), one jitted forward, one row of the batch
    at a time (whole [H, T, T] scores are kept, so a row is what fits) - in
    float32 at full matmul precision unless ``dtype`` says otherwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shuffle_exchange_tpu.models import Transformer

    plain = Transformer(dataclasses.replace(model_cfg,
                                            attention_impl="reference"))
    dtype = dtype or jnp.float32
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    if device is not None:
        params = jax.device_put(params, device)
    loss = jax.jit(plain.loss)
    with jax.default_matmul_precision("float32"):
        rows = [float(loss(params, {"input_ids": jax.device_put(
            batch["input_ids"][i:i + 1], device)}))
            for i in range(len(batch["input_ids"]))]
    return float(np.mean(rows))     # rows are equally long: mean of means


def attention_route(model_cfg, seq: int) -> str:
    """Which attention the trainer's forward resolves to at this shape on
    one chip, as the program itself names it."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.flash_attention import attention_route as route

    q, kv = (jax.ShapeDtypeStruct((1, seq, heads, model_cfg.head_dim), jnp.bfloat16)
             for heads in (model_cfg.n_heads, model_cfg.kv_heads))
    return route(q, kv, kv, impl=model_cfg.attention_impl)


def phase_trainer(meter: CompileMeter, model_cfg, *, model_name: str,
                  seq: int, batch: int, steps: int, reduced: dict,
                  loss_tol: float = 5e-2, seed: int = 0) -> dict:
    import jax

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.runtime.resilience import uninstall_preemption_hook

    mark = meter.mark()
    config = train_config(batch)
    data = seeded_batch(model_cfg.vocab_size, batch, seq, seed)

    def fresh_engine():
        return sxt.initialize(model=Transformer(model_cfg), config=config,
                              seed=seed)[0]

    engine = fresh_engine()
    # before the first step: the step donates the master weights
    ref = reference_loss(model_cfg, engine.state.master, data)
    losses = [float(engine.train_batch(data)) for _ in range(steps)]
    try:
        with tempfile.TemporaryDirectory(prefix="sxt_smoke_ckpt_") as ckpt:
            engine.save_checkpoint(ckpt)
            resumed = fresh_engine()
            resumed.load_checkpoint(ckpt)
            next_old = float(engine.train_batch(data))
            next_new = float(resumed.train_batch(data))
    finally:
        # save/load pointed the process-wide SIGTERM hook at a final save
        # into that directory: it goes with the directory
        uninstall_preemption_hook()
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    out = {
        "phase": "trainer", "model": model_name, "reduced": reduced,
        "params": n_params, "seq": seq, "batch": batch, "dtype": "bf16",
        "zero_stage": engine.zero_stage, "optimizer": "FusedAdam",
        "routes": {"attention": attention_route(model_cfg, seq)},
        "losses": losses, "reference_loss_f32": ref,
        "first_loss_abs_err": abs(losses[0] - ref),
        "resume": {"old_engine": next_old, "fresh_engine": next_new},
        **meter.since(mark),
        "memory": device_memory(jax.devices()[:1]),
    }
    conclude(out, [
        (all(math.isfinite(x) for x in losses + [next_old, next_new]),
         f"non-finite loss: {losses} {next_old} {next_new}"),
        (abs(losses[0] - ref) <= loss_tol,
         f"first loss {losses[0]} vs float32 reference {ref}: off by more "
         f"than {loss_tol}"),
        (losses[-1] < losses[0], f"loss did not fall: {losses}"),
        (next_old == next_new,
         f"checkpoint round trip: next loss {next_new} after load, "
         f"{next_old} without"),
    ])
    del engine, resumed
    _release()
    return out


# ---------------------------------------------------------------------------
# Phase 3: server
# ---------------------------------------------------------------------------


class TickClock:
    """The scheduler's clock, counting ticks instead of seconds, so that
    staggered arrivals land on the same ticks in every run: the second
    ``serve`` then packs exactly the batches of the first and must compile
    nothing. (Latencies the scheduler derives from it are in ticks.)"""

    def __init__(self):
        self.sched = None

    def __call__(self) -> float:
        return float(self.sched.ticks)


def seeded_requests(vocab: int, lengths: Sequence[int], seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lengths]


def bf16_params(model, seed: int):
    """Seeded random weights born in bf16 on the device: the float32 tree
    ``model.init`` describes would not fit beside the pool at these widths,
    so the cast is fused into the initialiser."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), model.init(k)))(jax.random.PRNGKey(seed))


def reference_logit_check(model_cfg, params, prompts, served, pad_to: int):
    """Teacher-forced check of served tokens against a plain forward of the
    same weights (``Transformer.apply`` with jnp attention: no paged cache,
    no scheduler, no fused kernel). For every generated position: the gap
    between the reference's best logit and the logit of the token that was
    served, in units of that position's logit standard deviation - 0 where
    the served token IS the reference's argmax. Greedy decoding is exact in
    float32; in bf16 two correct computations can differ at a near-tie, so
    the caller bounds the gap instead of demanding equality."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shuffle_exchange_tpu.models import Transformer

    plain = Transformer(dataclasses.replace(model_cfg,
                                            attention_impl="reference"))

    @jax.jit
    def gaps(params, ids, pos, toks):
        logits = plain.apply(params, ids)[0].astype(jnp.float32)   # [T, V]
        rows = logits[pos]                                         # [n, V]
        took = jnp.take_along_axis(rows, toks[:, None], axis=1)[:, 0]
        return ((rows.max(axis=1) - took) / rows.std(axis=1),
                rows.argmax(axis=1))

    worst, exact, total = 0.0, 0, 0
    for prompt, toks in zip(prompts, served):
        n = len(toks)
        ids = np.zeros((1, pad_to), np.int32)
        seq = list(prompt) + list(toks[:-1])
        ids[0, :len(seq)] = seq
        # token j of the answer is predicted at position len(prompt)-1+j
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + n, dtype=np.int32)
        gap, arg = gaps(params, ids, pos, np.asarray(toks, np.int32))
        worst = max(worst, float(gap.max()))
        exact += int((np.asarray(arg) == np.asarray(toks)).sum())
        total += n
    return {"tokens": total, "exact_argmax": exact,
            "worst_gap_sigma": round(worst, 5)}


def phase_server(meter: CompileMeter, model_cfg, *, model_name: str,
                 prompt_lengths: Sequence[int], arrivals: Sequence[int],
                 max_new: int, inference: dict, reduced: dict,
                 gap_tol_sigma: float, seed: int = 0) -> dict:
    import jax

    from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                                InferenceConfig,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops import fused_decode as fd

    # with the tick clock an idle scheduler would never see the next arrival
    require(arrivals[0] == 0 and all(
        b - a <= max_new for a, b in zip(arrivals, arrivals[1:])),
        f"arrivals {arrivals} leave the scheduler idle")
    mark = meter.mark()
    model = Transformer(model_cfg)
    params = bf16_params(model, seed)
    icfg = InferenceConfig(**inference)
    engine = InferenceEngineV2(model, params, icfg)
    clock = TickClock()
    sched = ContinuousBatchingScheduler(engine, clock=clock)
    clock.sched = sched
    prompts = seeded_requests(model_cfg.vocab_size, prompt_lengths, seed + 1)
    chunk = icfg.serving.token_budget
    require(any(n > chunk for n in prompt_lengths),
            "no prompt is longer than one prefill chunk")

    d0, t0 = engine.dispatch_count, sched.ticks
    first = sched.serve(prompts, max_new_tokens=max_new, arrivals=list(arrivals))
    ticks, dispatches = sched.ticks - t0, engine.dispatch_count - d0
    served = [first[uid] for uid in sorted(first)]
    shapes = engine.program_shapes
    first_pass = meter.since(mark)

    again = meter.mark()
    second = sched.serve(prompts, max_new_tokens=max_new, arrivals=list(arrivals))
    repeat = meter.since(again)
    repeated = [second[uid] for uid in sorted(second)]

    pool = engine.cache.k
    routes = {
        "decode_kernel": engine._decode_kernel,
        "fused_qkv": bool(engine._fuse_qkv), "fused_mlp": bool(engine._fuse_mlp),
        "kv_append": (fd.qkv_append_route(pool.shape, pool.dtype)
                      if engine._fuse_qkv else "xla-scatter"),
    }
    check = reference_logit_check(model_cfg, params, prompts, served,
                                  pad_to=icfg.max_seq_len)
    out = {
        "phase": "server", "model": model_name, "reduced": reduced,
        "dtype": icfg.dtype, "kv_pool_tokens":
            (icfg.num_kv_blocks - 1) * icfg.kv_block_size,
        "kv_pool_bytes": engine.cache.pool_nbytes(),
        "requests": len(prompts), "prompt_lengths": list(prompt_lengths),
        "arrival_ticks": list(arrivals), "max_new_tokens": max_new,
        "serving": {"token_budget": chunk,
                    "max_running": icfg.serving.max_running},
        "routes": routes, "ticks": ticks, "dispatches": dispatches,
        "preemptions": sched.preemptions,
        "programs": len(shapes), "reference": check,
        **first_pass,
        "repeat_serve": {"programs_compiled": repeat["programs_compiled"],
                         "new_program_shapes":
                             len(engine.program_shapes - shapes),
                         "same_tokens": repeated == served,
                         "run_s": repeat["run_s"]},
        "memory": device_memory(jax.devices()[:1]),
    }
    conclude(out, [
        (all(len(t) == max_new for t in served),
         f"not every request completed: {[len(t) for t in served]}"),
        (dispatches == ticks,
         f"{dispatches} dispatches over {ticks} ticks: not one per tick"),
        (check["worst_gap_sigma"] <= gap_tol_sigma,
         f"a served token sits {check['worst_gap_sigma']} logit-sigmas "
         f"under the reference's best (bound {gap_tol_sigma})"),
        (repeat["programs_compiled"] == 0 and engine.program_shapes == shapes,
         f"the repeated serve compiled {repeat['programs_compiled']} "
         f"programs ({len(engine.program_shapes - shapes)} new shapes)"),
        (repeated == served, "the repeated serve gave other tokens"),
    ])
    del engine, sched, params
    _release()
    return out


# ---------------------------------------------------------------------------
# --chips 4: ZeRO-3 sharded training
# ---------------------------------------------------------------------------


def state_bytes_per_device(devices, tree) -> List[int]:
    """Bytes of ``tree``'s shards on each device: what the CPU rehearsal
    balances, where the backend keeps no ``memory_stats()``."""
    import jax

    held = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[d.id] for d in devices]


def count_collectives(hlo_text: str) -> Dict[str, int]:
    """Collective instructions in a compiled program's text. The TPU
    compiler writes ZeRO-3's gradient reduce-scatter as ``all-reduce-scatter``
    fusions and turns part of the parameter all-gathers into
    collective-permute rings overlapped with the matmuls that consume them;
    the CPU backend leaves an all-reduce and a slice."""
    def ops(name):
        return hlo_text.count(f" {name}(") + hlo_text.count(f" {name}-start(")

    return {"all-gather": ops("all-gather"),
            "reduce-scatter": hlo_text.count("reduce-scatter"),
            "all-reduce": ops("all-reduce"),
            "collective-permute": ops("collective-permute")}


def phase_sharded(meter: CompileMeter, model_cfg, *, model_name: str,
                  seq: int, batch: int, steps: int, reduced: dict,
                  need: Sequence[str] = ("all-gather", "reduce-scatter"),
                  loss_tol: float = 5e-2, seed: int = 0) -> dict:
    """ZeRO stage 3 over ``mesh: {"fsdp": <all devices>}`` against a plain
    bf16 forward of the same parameters on one of those devices. ``need``:
    the collectives the compiled step must hold (the CPU rehearsal names
    others: XLA's CPU backend never forms a reduce-scatter)."""
    import jax
    import jax.numpy as jnp

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer

    mark = meter.mark()
    devices = jax.devices()
    n = len(devices)
    config = train_config(batch, mesh={"fsdp": n})
    data = seeded_batch(model_cfg.vocab_size, batch, seq, seed)
    engine = sxt.initialize(model=Transformer(model_cfg), config=config,
                            seed=seed)[0]
    memory = device_memory(devices)
    if all(m is not None for m in memory):
        held, source = [m["bytes_in_use"] for m in memory], "memory_stats"
    else:
        held, source = state_bytes_per_device(devices, engine.state), "state_shards"
    mean = sum(held) / n
    spread = max(abs(h - mean) for h in held) / mean

    ref = reference_loss(model_cfg, engine.state.master, data,
                         device=devices[-1], dtype=jnp.bfloat16)
    collectives = count_collectives(engine.compile(data).as_text())
    losses = [float(engine.train_batch(data)) for _ in range(steps)]
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    out = {
        "phase": "sharded_trainer", "model": model_name,
        "reduced": reduced, "params": n_params, "seq": seq, "batch": batch,
        "dtype": "bf16", "zero_stage": engine.zero_stage,
        "mesh": {k: v for k, v in engine.topology.axis_sizes.items() if v > 1},
        "devices": [str(d) for d in engine.topology.mesh.devices.flat],
        "losses": losses, "reference_loss_bf16_one_device": ref,
        "first_loss_abs_err": abs(losses[0] - ref),
        "bytes_after_initialize": held, "bytes_source": source,
        "max_dev_from_mean": round(spread, 4), "collectives": collectives,
        **meter.since(mark), "memory": device_memory(devices),
    }
    conclude(out, [
        (all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}"),
        (abs(losses[0] - ref) <= loss_tol,
         f"first loss {losses[0]} vs one-device reference {ref}: off by "
         f"more than {loss_tol}"),
        (losses[-1] < losses[0], f"loss did not fall: {losses}"),
        (spread <= 0.25, f"device memory after initialize is unbalanced: "
         f"{held} ({source})"),
        (all(collectives[op] > 0 for op in need),
         f"the compiled step lacks ZeRO-3's collectives {list(need)}: "
         f"{collectives}"),
    ])
    del engine
    _release()
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def _one_chip(meter: CompileMeter) -> None:
    from shuffle_exchange_tpu.models import gpt2_small, llama3_8b

    phase_kernels(meter)
    phase_trainer(meter, gpt2_small(), model_name="gpt2_small (GPT-2 125M)",
                  seq=1024, batch=8, steps=8,
                  reduced={"widths": "none", "depth": "none (12 layers)",
                           "weights": "random, seed 0",
                           "zero++ int8 wire flags": "off (one chip, no wire)"})
    layers = 8
    phase_server(
        meter, dataclasses.replace(llama3_8b(), n_layers=layers),
        model_name="llama3_8b (Llama-3-8B)",
        # 3 prompts longer than the 256-token chunk; arrivals in ticks
        prompt_lengths=[40, 300, 50, 520, 64, 700, 330, 33],
        arrivals=[0, 0, 2, 5, 9, 14, 20, 27], max_new=32,
        inference={"dtype": "bfloat16", "max_seq_len": 1024,
                   "kv_block_size": 64, "num_kv_blocks": 1025,
                   "serving": {"token_budget": 256, "max_running": 8}},
        reduced={"widths": "none",
                 "depth": f"{layers} of 32 layers (16 GB beside a 64k-token "
                          f"KV pool)",
                 "weights": "random bf16, seed 0",
                 "max_seq_len": "1024 of 8192 (bounds the reference forward)"},
        gap_tol_sigma=0.1)


def _four_chips(meter: CompileMeter) -> None:
    from shuffle_exchange_tpu.models import llama3_8b

    layers = 2
    phase_sharded(meter, dataclasses.replace(llama3_8b(), n_layers=layers),
                  model_name="llama3_8b (Llama-3-8B)",
                  # the step gathers every bf16 weight and holds whole f32
                  # gradients: ~9.6 GB a device whatever the batch, beside
                  # 4.2 GB of state (compiled for a described v5e:2x2)
                  seq=1024, batch=4, steps=6,
                  reduced={"widths": "none",
                           "depth": f"{layers} of 32 layers (1.5 B parameters, "
                                    f"21 GB of training state)",
                           "weights": "random, seed 0",
                           "seq": "1024 of 8192"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the ZeRO-3 sharded-training phase and its "
                         "reference, and no other phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no accelerator: JAX's first device is "
              f"{d0.platform!r} ({d0.device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips or d0.memory_stats() is None:
        print(f"chip_smoke: --chips {args.chips} needs that many TPU devices "
              f"with memory_stats(); JAX reports {len(devices)}",
              file=sys.stderr)
        return 1

    from shuffle_exchange_tpu.ops.native.builder import load_native
    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    # cache every program, not only the slow ones: phase 1 alone compiles
    # ~650 small ones (its checks run op by op), 50 s that a warm cache saves
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    emit(phase="start", chips=args.chips, jax=jax.__version__,
         device={"platform": d0.platform, "kind": d0.device_kind,
                 "count": len(devices)},
         compile_cache_dir=enable_compile_cache(),
         native_library_loaded=load_native() is not None)
    meter = CompileMeter()
    try:
        (_four_chips if args.chips == 4 else _one_chip)(meter)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
