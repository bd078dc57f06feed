"""Where ``qwen3next-train``'s ``loss_tol``, ``route_tol``, ``grad_tol``,
``grad_tol_routed`` and ``state_tol`` come from: the reference
(``chipbench/reference_qwen3next.py``) on the cell's own first batch and
weights, computed in a lower precision or with one piece of the mathematics
changed, and held against itself in float32 BY THE DRIVER'S OWN CHECKS
(``train_steps_hybrid.failed_checks``, the variant's answers in the program's
place): every line carries ``failed_checks`` and ``correct``. Run on the chip
by hand when a tolerance is in question; no cell and no check runs it:

    python chipbench/qwen3next_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``grad_gap``
and the leaf it is on, ``state_gap``). ``bf16`` is what the configuration
states: weights and activations in bf16; the router, norms, softmaxes, g,
beta, the state S and the loss in float32. It is the band a sound program
lies in: the tolerances sit above it and it comes out ``correct``. Every other
variant is ``bf16`` with one change, and must come out NOT correct:
  program_rule       not a variant of the reference: the PROGRAM's rule
                     (``ops/gated_delta.gated_delta_chunked``) on the
                     driver's long-memory inputs, the reading ``state_tol``
                     has to pass (``state_gap`` only)
  bf16_state         S rounded to bf16 after every token: the nearest
                     precision below the stated one for the recurrence; the
                     whole model at the init's decays does not show it, the
                     rule alone at a long memory does (``state_gap``)
  bf16_router        router logits, softmax and weights in bf16
  bf16_norms         the RMS and l2 norms, beta and g with bf16 results
  no_decay           g = 0: a delta rule that never forgets
  no_beta            beta = 1: every token writes at full strength
  no_l2norm          q and k of the DeltaNet layers not normalised
  plain_gain         x / rms(x) * w where the source has (1 + w)
  rope_all_dims      RoPE on all 256 dims of a head instead of the first 64
  no_attn_gate       attention output not gated by sigmoid(gate)
  no_shared_gate     the shared expert added without its sigmoid gate
  weights_over_held  routing weights normalised over the choices that fall on
                     the held experts instead of over all 10
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_hybrid as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402


def variants(src: dict) -> dict:
    """{name: (the reference's functions to replace while tracing, keys of
    the configuration to replace)}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_qwen3next as ref

    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=jnp.bfloat16)}
    plain_route = ref.route
    first, end = ref.held_range(src)

    def plain_gain(x, gain, eps):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (y * gain.astype(jnp.float32)).astype(x.dtype)

    def route_over_held(w, prefix, y, cfg):
        p, chosen, weight = plain_route(w, prefix, y, dict(cfg, norm_topk_prob=False))
        held = jnp.where((chosen >= first) & (chosen < end), weight, 0.0)
        return p, chosen, held / jnp.maximum(held.sum(axis=-1, keepdims=True), 1e-9)

    def in_bf16(fn):
        """``fn`` on bf16 inputs with a bf16 result, handed back as float32."""
        def rounded(*args, **kw):
            low = [a.astype(jnp.bfloat16) if hasattr(a, "astype") else a for a in args]
            return fn(*low, **kw).astype(jnp.bfloat16).astype(jnp.float32)
        return rounded

    def route_bf16(w, prefix, y, cfg):
        low = jnp.bfloat16
        logits = y.astype(low) @ w[prefix + "gate.weight"].astype(low).T
        p = jax.nn.softmax(logits, axis=-1)                      # bf16
        weight, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
        if cfg.get("norm_topk_prob", False):
            weight = weight / weight.sum(axis=-1, keepdims=True)
        f32 = jnp.float32
        return p.astype(f32), chosen.astype(jnp.int32), weight.astype(f32)

    def rms_norm_bf16(x, gain, eps):
        low = x.astype(jnp.bfloat16)
        y = low * jax.lax.rsqrt(jnp.mean(low * low, axis=-1, keepdims=True) + eps)
        return (y * (1.0 + gain.astype(jnp.bfloat16))).astype(x.dtype)

    def l2norm_bf16(x, eps=1e-6):
        low = x.astype(jnp.bfloat16)
        return (low * jax.lax.rsqrt(jnp.sum(low * low, axis=-1, keepdims=True) + eps)
                ).astype(x.dtype)

    changed = {
        "bf16_state": {"delta_rule": functools.partial(ref.delta_rule, state_bits=(8, 7))},
        "bf16_router": {"route": route_bf16},
        "bf16_norms": {"rms_norm": rms_norm_bf16, "l2norm": l2norm_bf16,
                       "write_strength": in_bf16(ref.write_strength),
                       "log_decay": in_bf16(ref.log_decay)},
        "no_decay": {"log_decay": lambda a, A_log, dt: jnp.zeros(a.shape, jnp.float32)},
        "no_beta": {"write_strength": lambda b: jnp.ones(b.shape, jnp.float32)},
        "no_l2norm": {"l2norm": lambda x, eps=1e-6: x},
        "plain_gain": {"rms_norm": plain_gain},
        "no_attn_gate": {"output_gate": lambda o, gate: o},
        "no_shared_gate": {"shared_gate": lambda w, prefix, y: 1.0},
        "weights_over_held": {"route": route_over_held},
    }
    out = {"float32": ({}, {}), "bf16": (bf16, {}),
           "rope_all_dims": (bf16, {"partial_rotary_factor": 1.0})}
    out.update({name: ({**bf16, **fns}, {}) for name, fns in changed.items()})
    return out


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up and drop nothing by construction)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact_loss,
         "route_gap": line["route_gap"], "held_gap": line["held_gap"],
         "counters_add_up": True, "overflow": [0, 0], "grad_gaps": line["grad_gaps"],
         "state_gaps": line.get("state_gaps") or {"o": 0.0}}, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def judge_log(path: str, traffic: dict) -> list:
    """The ``band`` lines of an earlier run's output, judged again by the
    limits the traffic file has NOW (a limit changed; the chip's readings did
    not). Lines from before the rule's own reading existed carry no
    ``state_gaps`` and are judged on the other checks."""
    import json

    lines = [json.loads(x) for x in open(path) if x.startswith('{"phase": "band"')]
    exact = {x["seed"]: x["loss"] for x in lines if x["variant"] == "float32"}
    out = [judged(x, exact[x["seed"]], traffic) for x in lines if "loss" in x]
    for line in out:
        harness.emit(**{k: v for k, v in line.items() if k != "grad_gaps"})
    return out


def measure(cell: dict, seeds, names, rehearsal=None, few=None, rule_seeds=None) -> list:
    """One record per (variant, seed): the variant against float32, through
    the driver's own checks. ``few``: every variant but ``bf16`` runs on the
    first ``few`` seeds only (a wrong model is far off on any seed; the
    band's width wants many). ``rule_seeds``: ``program_rule``'s (the rule
    alone takes seconds a seed; default ``seeds``)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_qwen3next as ref
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.gated_delta import gated_delta_chunked

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    train_config = {**cell["config"]["chipbench"]["train_config"],
                    **rehearsal.get("train_config", {})}
    dtype = jnp.bfloat16 if train_config.get("bf16", {}).get("enabled") else jnp.float32
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants(src)
    exact, out = {}, []

    inputs = lambda seed: driver.rule_inputs(harness.seed32(seed), batch, seq, mcfg, dtype)

    def rule_gaps(rule, seed):
        """``rule`` on the driver's long-memory inputs against the reference's
        own recurrence, computed before any variant replaced a function."""
        return driver.state_gaps(rule, inputs(seed), exact[seed]["rule"])

    def record(name, seed, state, **whole):
        line = {"variant": name, "seed": seed, **whole,
                "state_gap": max(state.values()), "state_gaps": state}
        out.append(judged(line, exact[seed]["loss"], traffic) if whole else line)
        harness.emit(phase="band", **out[-1])

    def exact_rule(seed):
        return jax.device_get(driver.rule_answers(driver.reference_rule, *inputs(seed)))

    for name in ["float32"] + [n for n in names if n != "float32"]:
        many = name == "bf16" or (name == "float32" and "bf16" in names)
        if name == "program_rule":
            for seed in rule_seeds or seeds:
                if seed not in exact:
                    exact[seed] = {"rule": exact_rule(seed)}
                record(name, seed, rule_gaps(gated_delta_chunked, seed))
                if seed not in seeds:
                    del exact[seed]
            continue
        fns, keys = every[name]
        if name == "float32":
            exact.update({seed: {"rule": exact_rule(seed)} for seed in seeds})
        plain = {k: getattr(ref, k) for k in fns}
        for k, fn in fns.items():
            setattr(ref, k, fn)
        try:
            program = driver.reference_program({**src, **keys})
            for seed in (seeds if many else seeds[:few]):
                ids = next(batches(mcfg.vocab_size, batch, seq, seed))["input_ids"]
                weights = driver.to_source_names(
                    driver.initial_params(model, harness.seed32(seed)), src)
                got = driver.reference_first_step(program, weights, jnp.asarray(ids))
                del weights
                if name == "float32":
                    exact[seed].update(got)
                base = exact[seed]
                gaps = grad_gaps(got["grads"] if name == "float32"
                                 else got.pop("grads"), base["grads"])
                worst = max(gaps, key=gaps.get)
                # a variant with its own recurrence is read on the rule alone
                # too; the others have the reference's: distance 0
                state = (rule_gaps(driver.reference_rule, seed) if "delta_rule" in fns
                         else dict.fromkeys(driver.RULE_PARTS, 0.0))
                record(name, seed, state, loss=got["loss"],
                       loss_gap=abs(got["loss"] - base["loss"]),
                       route_gap=route_gap(got["expert_tokens"], base["expert_tokens"]),
                       held_gap=route_gap(got["held_rows"], base["held_rows"]),
                       grad_gap=gaps[worst], grad_gap_leaf=worst, grad_gaps=gaps)
        finally:
            for k, fn in plain.items():
                setattr(ref, k, fn)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["program_rule", "bf16", "bf16_state"])
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 and program_rule "
                    "(default: all)")
    ap.add_argument("--rule-seeds", type=int, nargs="+", default=None,
                    help="seeds of program_rule (default: --seeds)")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("qwen3next-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, rule_seeds=args.rule_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
