"""Where ``olmohybrid-zero3-x4``'s ``loss_tol``, ``grad_tol`` and ``state_tol``
come from: on the cell's own first batch and weights, the PROGRAM's first step
(``sxt.initialize(...).train_batch`` under the cell's own mesh and sizes) and
the reference (``chipbench/reference_olmohybrid.py``) computed in a lower
precision or with one piece of the mathematics changed, each held against the
reference in float32 BY THE DRIVER'S OWN CHECKS
(``train_steps_gdn.failed_checks``, the variant's answers in the program's
place): every line carries ``failed_checks`` and ``correct``. Run on the
cell's chips by hand when a tolerance is in question; no cell and no check
runs it:

    python chipbench/olmohybrid_band.py --seeds 11 12 ... [--variants bf16 ...] [--few 1]
    python chipbench/olmohybrid_band.py --time-rule          (one chip)

Variants, one line per seed (``loss_gap``, ``grad_gap`` and the leaf it is on,
``state_gap``). ``program`` and ``bf16`` must come out ``correct``: the band a
sound program lies in, and what the configuration states (weights and
activations in bf16; norms' statistics, softmax, g, beta, the state S and the
loss in float32). Every other variant is ``bf16`` with one change, and must
come out NOT correct:
  program_rule      not a variant of the reference: the PROGRAM's rule
                    (``ops/gated_delta.gated_delta_chunked``) on the driver's
                    long-memory inputs (``state_gap`` only)
  bf16_state        S rounded to bf16 after every token, in the whole model
                    and on the rule alone (``state_gap``); ``bf16_state_rule``
                    is its reading on the rule alone and nothing else (one
                    chip is enough)
  bf16_qk_stat      the q/k norm's statistic (mean of squares, rsqrt) in bf16:
                    no whole-model reading shows it, the norm alone does
                    (``stat_gap``); ``program_stat`` / ``bf16_qk_stat_alone``
                    are the two readings of the norm alone and nothing else
  beta_1x           beta = sigmoid, without the 2
  norm_on_input     h + f(norm(h)) in place of h + norm(f(h))
  per_head_qk_norm  q and k normed per head over 128, the gains as they are
  rope_on           RoPE (theta 500,000) on q and k of the attention layers
  q_scale_dv        q scaled by 1 / sqrt(192) in place of 1 / sqrt(96)
  no_decay          g = 0: a delta rule that never forgets
  no_l2norm         q and k of the DeltaNet layers not normalised
  norm_after_gate   the DeltaNet output gated first, then normed

``--time-rule``: on ONE chip, at one chip's share of the cell's batch, the
milliseconds of the rule's forward + backward on its XLA route and on the
padded kernels (several heads a grid step), and of the prologue's XLA body.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_gdn as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_hybrid import (RULE_PARTS, first_moment,  # noqa: E402
                                                 reference_rule, rule_answers)

CELL = "olmohybrid-zero3-x4"


def variants(base=None) -> dict:
    """{name: (the reference's functions to replace while tracing, keys of
    the configuration to replace, the reference's dtype)}. ``base``: the
    precision the configuration states (bf16; a rehearsal in float32 hands
    float32, and every wrong model is then that one change and nothing else)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_olmohybrid as ref

    low = jnp.bfloat16

    def layer_norm_on_input(w, i, x, cfg, remat=False):
        eps = cfg["rms_norm_eps"]
        name = f"model.layers.{i}."
        y = ref.rms_norm(x, w[name + "post_attention_layernorm.weight"], eps)
        if ref.is_full_attention(i, cfg):
            h = x + ref.attention(w, name + "self_attn.", y, cfg, remat)
        else:
            h = x + ref.delta_net(w, name + "linear_attn.", y, cfg, remat)
        y = ref.rms_norm(h, w[name + "post_feedforward_layernorm.weight"], eps)
        return h + ref.mlp(w, name + "mlp.", y)

    def qk_norm_per_head(q, k, wq, wk, cfg):
        H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        eps = cfg["rms_norm_eps"]
        heads = lambda x, w, n: ref.rms_norm(
            x.reshape(x.shape[:-1] + (n, -1)), w.reshape(n, -1), eps).reshape(x.shape)
        return heads(q, wq, H), heads(k, wk, KV)

    def qk_norm_bf16_stat(q, k, wq, wk, cfg):
        eps = cfg["rms_norm_eps"]

        def norm(x, gain):
            x16 = x.astype(low)
            y = x16 * jax.lax.rsqrt(jnp.mean(x16 * x16, axis=-1, keepdims=True) + eps)
            return (y.astype(jnp.float32) * gain.astype(jnp.float32)).astype(x.dtype)
        return norm(q, wq), norm(k, wk)

    def rope_on(q, k, cfg, theta=500000.0):
        def rotated(x):
            T, Dh = x.shape[1], x.shape[-1]
            inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
            ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
            ang = jnp.concatenate([ang, ang], axis=-1)
            cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
            sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
            half = Dh // 2
            turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
            return x * cos + turned * sin
        return rotated(q), rotated(k)

    def gate_then_norm(o, z, gain, eps):
        o = o * jax.nn.silu(z.astype(jnp.float32))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        return gain.astype(jnp.float32) * o

    changed = {
        "bf16_state": {"delta_rule": functools.partial(ref.delta_rule, state_bits=(8, 7))},
        "bf16_qk_stat": {"qk_norm": qk_norm_bf16_stat},
        "norm_on_input": {"layer": layer_norm_on_input},
        "per_head_qk_norm": {"qk_norm": qk_norm_per_head},
        "rope_on": {"position": rope_on},
        "q_scale_dv": {"query_scale": lambda dk, dv: dv ** -0.5},
        "no_decay": {"log_decay": lambda a, A_log, dt: jnp.zeros(a.shape, jnp.float32)},
        "no_l2norm": {"l2norm": lambda x, eps=1e-6: x},
        "norm_after_gate": {"gated_out_norm": gate_then_norm},
    }
    base = base or low
    out = {"float32": ({}, {}, jnp.float32), "bf16": ({}, {}, low),
           "beta_1x": ({}, {"linear_allow_neg_eigval": False}, base)}
    out.update({name: (fns, {}, base) for name, fns in changed.items()})
    return out


WRONG = ("beta_1x", "norm_on_input", "per_head_qk_norm", "rope_on", "q_scale_dv",
         "no_decay", "no_l2norm", "norm_after_gate", "bf16_state", "bf16_qk_stat")


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the counter is the program's own business)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact_loss,
         "grad_gaps": line["grad_gaps"], "scan_chunks": 1,
         "stat_gap": line.get("stat_gap", 0.0),
         "state_gaps": line.get("state_gaps") or {"o": 0.0}}, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def judge_log(path: str, traffic: dict) -> list:
    """The ``band`` lines of an earlier run's output, judged again by the
    limits the traffic file has NOW (a limit changed; the chip's readings did
    not)."""
    import json

    lines = [json.loads(x) for x in open(path) if x.startswith('{"phase": "band"')]
    exact = {x["seed"]: x["loss"] for x in lines if x["variant"] == "float32"}
    out = []
    for x in lines:
        if "loss" in x:
            out.append(judged(x, exact[x["seed"]], traffic))
        else:                                   # the rule, or the norm, alone
            out.append({**x, "correct": x["state_gap"] <= float(traffic["state_tol"])
                        and x.get("stat_gap", 0.0) <= float(traffic["stat_tol"])})
        harness.emit(**{k: v for k, v in out[-1].items() if k != "grad_gaps"})
    return out


def program_first_step(cell, model, mcfg, seed, first, chips, rehearsal) -> dict:
    """The PROGRAM's first loss and first gradient (out of Adam's first
    moment), through ``sxt.initialize(...).train_batch`` as the driver runs
    it."""
    import shuffle_exchange_tpu as sxt

    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    batch = int(traffic["batch_per_chip"]) * chips
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))
    if settings.get("mesh"):
        config["mesh"] = {k: (chips if v == "chips" else v)
                          for k, v in settings["mesh"].items()}
    engine = sxt.initialize(model=model, params=driver.initial_params(model, seed),
                            config=config, seed=seed)[0]
    loss = float(engine.train_batch(first))
    beta1 = settings["train_config"]["optimizer"]["params"].get("betas", (0.9,))[0]
    return {"loss": loss, "moment": first_moment(engine.state.opt_state),
            "scale": 1.0 / (1.0 - beta1)}


def measure(cell: dict, seeds, names, rehearsal=None, few=None, rule_seeds=None) -> list:
    """One record per (variant, seed), seed by seed (a seed's float32
    gradient, 4 bytes a parameter on the host, is dropped before the next):
    the variant against float32, through the driver's own checks. ``few``:
    every variant but ``bf16`` and ``program`` runs on the first ``few`` seeds
    only (a wrong model is far off on any seed; the band's width wants many).
    ``rule_seeds``: ``program_rule``'s (default ``seeds``)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_olmohybrid as ref
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.gated_delta import gated_delta_chunked

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    train_config = {**cell["config"]["chipbench"]["train_config"],
                    **rehearsal.get("train_config", {})}
    dtype = jnp.bfloat16 if train_config.get("bf16", {}).get("enabled") else jnp.float32
    devices = jax.devices()[:cell["chips"]]
    chips = len(devices)
    per_chip, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    batch = per_chip * chips
    mcfg = harness.model_config(cell, rehearsal)
    src = dict(rehearsal.get("source_config") or cell["config"])
    model = Transformer(mcfg)
    every = variants(dtype)
    compiled, out = {}, []
    inputs = lambda seed: driver.rule_inputs(harness.seed32(seed), per_chip, seq, mcfg, dtype)

    def stat_of(norm, seed):
        return driver.stat_gap(norm, driver.stat_inputs(
            harness.seed32(seed), seq, mcfg, dtype), mcfg.norm_eps)

    def record(name, seed, state, exact_loss=None, stat=0.0, **whole):
        line = {"variant": name, "seed": seed, **whole, "stat_gap": stat,
                "state_gap": max(state.values()), "state_gaps": state}
        out.append(judged(line, exact_loss, traffic) if whole else line)
        harness.emit(phase="band", **{k: v for k, v in out[-1].items()})

    def reference(name, weights, ids):
        """Variant ``name`` of the reference on (weights, ids): traced and
        compiled ONCE, while its functions stand in the reference's place."""
        fns, keys, precision = every[name]
        if name not in compiled:
            plain = {k: getattr(ref, k) for k in fns}
            for k, fn in fns.items():
                setattr(ref, k, fn)
            try:
                program, mesh = driver.reference_program({**src, **keys}, devices,
                                                         precision)
                placed = jax.device_put(ids, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec("rows")))
                compiled[name] = (program.lower(weights, placed).compile(), mesh)
            finally:
                for k, fn in plain.items():
                    setattr(ref, k, fn)
        return driver.reference_first_step(*compiled[name], weights, ids, src)

    mesh = driver.reference_mesh(devices)
    whole = [n for n in names if n not in ("program_rule", "bf16_state_rule",
                                           "program_stat", "bf16_qk_stat_alone")]
    for seed in seeds:
        s32 = harness.seed32(seed)
        first = next(batches(mcfg.vocab_size, batch, seq, seed))
        ids = jnp.asarray(first["input_ids"])
        exact_rule = jax.device_get(rule_answers(reference_rule, *inputs(seed)))
        state_of = lambda rule: driver.state_gaps(rule, inputs(seed), exact_rule)
        if "program_rule" in names and seed in (rule_seeds or seeds):
            record("program_rule", seed, state_of(gated_delta_chunked))
        if "program_stat" in names and seed in (rule_seeds or seeds):
            record("program_stat", seed, dict.fromkeys(RULE_PARTS, 0.0),
                   stat=stat_of(driver.program_qk_norm(mcfg), seed))
        if "bf16_qk_stat_alone" in names and seed in (rule_seeds or seeds):
            wrong = every["bf16_qk_stat"][0]["qk_norm"]
            record("bf16_qk_stat_alone", seed, dict.fromkeys(RULE_PARTS, 0.0),
                   stat=stat_of(lambda x, gain: wrong(x, x, gain, gain, src)[0], seed))
        if "bf16_state_rule" in names and seed in (rule_seeds or seeds):
            record("bf16_state_rule", seed, state_of(functools.partial(
                reference_rule_with, every["bf16_state"][0]["delta_rule"])))
        if not whole:
            continue
        weights = driver.reference_weights(model, s32, src, mesh)
        exact = reference("float32", weights, ids)
        record("float32", seed, dict.fromkeys(RULE_PARTS, 0.0), exact["loss"],
               loss=exact["loss"], loss_gap=0.0, grad_gap=0.0, grad_gap_leaf="",
               grad_gaps=dict.fromkeys(exact["grads"], 0.0))
        for name in whole:
            if name in ("float32", "program"):
                continue
            if name != "bf16" and few is not None and seeds.index(seed) >= few:
                continue
            got = reference(name, weights, ids)
            gaps = driver.host_gaps(got.pop("grads"), exact["grads"])
            worst = max(gaps, key=lambda k: gaps[k] if gaps[k] == gaps[k] else float("inf"))
            fns = every[name][0]
            state = (state_of(functools.partial(reference_rule_with, fns["delta_rule"]))
                     if "delta_rule" in fns else dict.fromkeys(RULE_PARTS, 0.0))
            stat = (stat_of(lambda x, gain: fns["qk_norm"](x, x, gain, gain, src)[0], seed)
                    if name == "bf16_qk_stat" else 0.0)
            record(name, seed, state, exact["loss"], stat, loss=got["loss"],
                   loss_gap=abs(got["loss"] - exact["loss"]),
                   grad_gap=gaps[worst], grad_gap_leaf=worst, grad_gaps=gaps)
        del weights
        if "program" in whole:
            got = program_first_step(cell, model, mcfg, s32, first, chips, rehearsal)
            gaps = driver.host_gaps(got.pop("moment"), exact["grads"], got["scale"])
            worst = max(gaps, key=lambda k: gaps[k] if gaps[k] == gaps[k] else float("inf"))
            record("program", seed, state_of(gated_delta_chunked), exact["loss"],
                   stat_of(driver.program_qk_norm(mcfg), seed), loss=got["loss"], loss_gap=abs(got["loss"] - exact["loss"]),
                   grad_gap=gaps[worst], grad_gap_leaf=worst, grad_gaps=gaps)
    return out


def reference_rule_with(rule, *args):
    """``reference_rule`` with another recurrence in ``delta_rule``'s place."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = args
    with jax.default_matmul_precision("highest"):
        return rule(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), g, beta, remat=True)


def time_rule(cell: dict, repeats: int = 5, groups=(2, 5, 6, 10)) -> None:
    """On one chip, at one chip's rows: milliseconds of forward + backward of
    the rule on its XLA route and on the padded kernels at several heads a
    grid step, and of the prologue's XLA body."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops import gated_delta as gd

    traffic = cell["traffic"]
    mcfg = harness.model_config(cell, None)
    per_chip, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    args, ct = driver.rule_inputs(1, per_chip, seq, mcfg, jnp.bfloat16)

    def timed(fn, *xs):
        run = jax.jit(fn)
        t0 = time.perf_counter()
        jax.block_until_ready(run(*xs))
        first = time.perf_counter() - t0
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*xs))
            times.append(time.perf_counter() - t0)
        return {"ms": 1e3 * sorted(times)[len(times) // 2], "first_call_s": first}

    def both(rule):
        def fn(args, ct):
            o, back = jax.vjp(rule, *args)
            return (o,) + back(ct)
        return fn

    harness.emit(phase="time_rule", route=gd.kernel_route(*args[:3]), shape=[
        per_chip, seq, mcfg.gdn_value_heads, mcfg.gdn_key_dim, mcfg.gdn_value_dim])
    harness.emit(phase="time_rule", form="xla",
                 **timed(both(functools.partial(_xla_rule, gd)), args, ct))
    held = gd._HEAD_GROUPS
    for G in groups:
        if mcfg.gdn_value_heads % G:
            continue
        gd._HEAD_GROUPS = (G,)
        gd._delta_core.cache_clear()
        try:
            harness.emit(phase="time_rule", form="pallas_padded", heads_a_step=G,
                         **timed(both(gd._gated_delta_pallas), args, ct))
        except Exception as e:                       # a G the compiler refuses
            harness.emit(phase="time_rule", form="pallas_padded", heads_a_step=G,
                         error=f"{type(e).__name__}: {str(e)[:300]}")
    gd._HEAD_GROUPS = held
    gd._delta_core.cache_clear()
    # the prologue's XLA body, forward + backward, on a projection's output
    Hk, dk, dv = mcfg.gdn_key_heads, mcfg.gdn_key_dim, mcfg.gdn_value_dim
    H = mcfg.gdn_value_heads
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    qkvz = jax.random.normal(keys[0], (per_chip, seq, 2 * Hk * dk + 2 * H * dv), jnp.bfloat16)
    conv_w = jax.random.normal(keys[1], (mcfg.gdn_conv_kernel, 2 * Hk * dk + H * dv),
                               jnp.float32)

    def prologue(qkvz, conv_w):
        def fn(x, w):
            return gd.gdn_prologue(x, w, key_heads=Hk, dk=dk, dv=dv)
        out, back = jax.vjp(fn, qkvz, conv_w)
        return back(jax.tree.map(jnp.ones_like, out))

    harness.emit(phase="time_rule", form="prologue",
                 route=gd.prologue_route(qkvz, conv_w, dk, dv),
                 **timed(prologue, qkvz, conv_w))


def sweep(cell: dict, rows_a_chip, steps: int = 8) -> None:
    """On the cell's chips, for each ``batch_per_chip``: the compiled train
    step's bytes a device (XLA's ``memory_analysis``) and, over ``steps``
    blocked steps after three warm-ups, tokens/s/chip. No reference, no
    check: the cell's own runs are the measurement at the value it states."""
    import jax

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer

    settings = cell["config"]["chipbench"]
    chips = cell["chips"]
    seq = int(cell["traffic"]["seq"])
    for per_chip in rows_a_chip:
        batch = per_chip * chips
        config = dict(settings["train_config"], train_batch_size=batch,
                      gradient_accumulation_steps=1, steps_per_print=10 ** 9)
        if settings.get("mesh"):
            config["mesh"] = {k: (chips if v == "chips" else v)
                              for k, v in settings["mesh"].items()}
        model = Transformer(harness.model_config(cell, None))
        engine = sxt.initialize(model=model, config=config, seed=1)[0]
        data = batches(model.config.vocab_size, batch, seq, 1)
        first = next(data)
        m = engine.compile(first).memory_analysis()
        for _ in range(3):
            loss = engine.train_batch(next(data))
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(next(data))
        jax.block_until_ready(loss)
        step_s = (time.perf_counter() - t0) / steps
        harness.emit(phase="sweep", batch_per_chip=per_chip, tokens_per_step=batch * seq,
                     step_ms=1e3 * step_s, tokens_per_s_chip=batch * seq / step_s / chips,
                     peak_memory_in_bytes=int(getattr(m, "peak_memory_in_bytes", 0) or 0),
                     argument=int(m.argument_size_in_bytes), temp=int(m.temp_size_in_bytes),
                     generated_code=int(m.generated_code_size_in_bytes))
        del engine, loss


def _xla_rule(gd, *args):
    """``gated_delta_chunked``'s XLA body whatever the route says."""
    route = gd.kernel_route
    gd.kernel_route = lambda *a, **k: "xla"
    try:
        return gd.gated_delta_chunked(*args)
    finally:
        gd.kernel_route = route


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["program_rule", "bf16", "program"])
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16, program and "
                    "program_rule (default: all)")
    ap.add_argument("--rule-seeds", type=int, nargs="+", default=None,
                    help="seeds of program_rule (default: --seeds)")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    ap.add_argument("--time-rule", action="store_true",
                    help="time the rule's routes and the prologue on one chip")
    ap.add_argument("--sweep", type=int, nargs="+", default=None, metavar="ROWS",
                    help="batch_per_chip values: compiled bytes a device and "
                    "tokens/s/chip of each, on the cell's chips, no reference")
    ap.add_argument("--rows-a-chip", type=int, default=None,
                    help="batch_per_chip in the traffic file's place (a wrong "
                    "model on ONE chip and one row is still a wrong model)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(CELL)
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    if args.time_rule:
        time_rule(cell)
        return 0
    if args.sweep:
        sweep(cell, args.sweep)
        return 0
    rehearsal = ({"traffic": {"batch_per_chip": args.rows_a_chip}}
                 if args.rows_a_chip else None)
    measure(cell, args.seeds, args.variants, rehearsal=rehearsal, few=args.few,
            rule_seeds=args.rule_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
