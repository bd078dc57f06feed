"""Training cells: ``sxt.initialize(...).train_batch`` on a new seeded batch
every step, steps chained on the donated state, the window closed by
``block_until_ready``. Starts from ``chip_smoke.phase_trainer`` /
``phase_sharded`` (PR 23), without the checkpoint round trip.

Traffic parameters (``chipbench/traffic/<name>.json``):
  seq             tokens per sequence the model is scored on
  batch_per_chip  sequences per chip per step
  warmup_steps    steps before the window (the first compiles)
  trace_steps     steps inside the profiler's trace in a traced run
  loss_tol        how far the first loss may sit from the plain reference
"""

from __future__ import annotations

import math
import time

from chipbench import arith, harness, reference


def batches(vocab: int, batch: int, seq: int, seed: int):
    """A host generator: ``seq`` + 1 ids per row (the model sees ``seq``
    positions and is scored on the shifted labels), new every step. Ids
    follow a Zipf-like law (p(i) ~ 1/(i + 10)), as token frequencies do: a
    model that trains at all learns the unigram law within tens of steps, so
    "the loss fell" can be checked on fresh batches."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cdf = np.cumsum(1.0 / (np.arange(vocab) + 10.0))
    cdf /= cdf[-1]
    while True:
        ids = np.searchsorted(cdf, rng.random(size=(batch, seq + 1)))
        yield {"input_ids": np.minimum(ids, vocab - 1).astype(np.int32)}


def compiled_step_bytes(engine, batch) -> int:
    """Bytes per device the compiler sizes the train step at: arguments +
    outputs - aliased + temporaries, from ``engine.compile(batch)
    .memory_analysis()``. ``memory_stats()["peak_bytes_in_use"]`` misses the
    step's temporaries on this chip (``PERF.md`` 7), so the last line's
    ``memory_peak_bytes`` takes the larger of the two. The step is compiled
    here and found in the cache by ``train_batch``."""
    compiled = engine.compile(batch)
    m = compiled.memory_analysis() if compiled is not None else None
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled

    cell, meter, spans = ctx["cell"], ctx["meter"], ctx["spans"]
    rehearsal = ctx.get("rehearsal") or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    seq, per_chip = int(traffic["seq"]), int(traffic["batch_per_chip"])
    batch = per_chip * chips
    mcfg = harness.model_config(cell, rehearsal)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9)
    if settings.get("mesh"):
        config["mesh"] = {k: (chips if v == "chips" else v)
                          for k, v in settings["mesh"].items()}

    mark = meter.mark()
    engine = sxt.initialize(model=Transformer(mcfg), config=config,
                            seed=harness.seed32(ctx["seed"]))[0]
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # before the first step: the step donates the master weights. One chip:
    # float32 at full precision; a sharded state: gathered to one of the
    # chips in bf16, as chip_smoke.phase_sharded does (float32 would not fit)
    sharded = chips > 1
    ref = reference.reference_loss(
        mcfg, engine.state.master, first,
        device=devices[-1] if sharded else None,
        dtype=jnp.bfloat16 if sharded else None)
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)

    losses = [engine.train_batch(first)]
    for _ in range(max(1, int(traffic["warmup_steps"])) - 1):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 mesh={k: v for k, v in engine.topology.axis_sizes.items()
                       if v > 1},
                 routes={"fused_adamw": "pallas" if pallas_enabled() else "xla"},
                 reference_loss=ref, first_loss=float(losses[0]),
                 compiled_step_bytes=step_bytes, **warm)

    # -- the window -------------------------------------------------------
    traced = bool(ctx["trace"])
    trace_steps = int(traffic.get("trace_steps", 5))
    in_window = meter.mark()
    window_losses = []
    tracing, trace_at = False, None
    t0 = time.perf_counter()
    ctx["window_start"](t0)
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx["seconds"]:
            break
        if traced and not tracing and trace_at is None \
                and now - t0 >= ctx["seconds"] / 3:
            jax.block_until_ready(window_losses[-1:] or losses[-1:])
            ctx["start_trace"]()
            tracing, trace_at = True, len(window_losses)
        if traced:
            # a traced run times each step alone; the untraced run below
            # keeps two steps in flight and times the window as a whole
            with spans.span("train_step"):
                loss = engine.train_batch(next(data))
                jax.block_until_ready(loss)
            window_losses.append(loss)
            if tracing and len(window_losses) - trace_at >= trace_steps:
                ctx["stop_trace"]()
                tracing = False
        else:
            window_losses.append(engine.train_batch(next(data)))
            if len(window_losses) >= 2:
                jax.block_until_ready(window_losses[-2])
    jax.block_until_ready(window_losses[-1])
    t1 = time.perf_counter()
    if tracing:
        ctx["stop_trace"]()
    window_s = t1 - t0
    in_win = meter.since(in_window)
    steps = len(window_losses)

    # -- correct, outside the window --------------------------------------
    vals = [float(x) for x in losses + window_losses]
    tol = float(traffic.get("loss_tol", 5e-2))
    tail = vals[-max(1, min(20, steps)):]
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - ref) <= tol,
         f"first loss {vals[0]} vs plain reference {ref}: off by more than {tol}"),
        (sum(tail) / len(tail) < vals[0],
         f"loss did not fall: first {vals[0]}, mean of the last {len(tail)} "
         f"{sum(tail) / len(tail)}"),
    ]
    correct = all(c for c, _ in checks)
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_abs_err=abs(vals[0] - ref),
                 failed_checks=[m for c, m in checks if not c], **in_win)
    return {
        "correct": correct, "attempted": steps,
        "failed": sum(1 for v in vals[len(losses):] if not math.isfinite(v)),
        "end_to_end": {"train_tokens_per_s_chip": per_chip_rate},
        "window_s": window_s, "program_bytes": step_bytes,
        "counters": {"compiles_in_window": in_win["programs_compiled"],
                     "steps": steps},
        "facts": {"model_cfg": mcfg, "seq": seq, "batch": batch,
                  "chips": chips, "tokens_per_step": batch * seq,
                  "step_s": [b - a for a, b in spans.named("train_step")],
                  "flops_per_token": arith.train_flops_per_token(mcfg, seq)},
    }
