"""Where ``nemotron3-train``'s ``loss_tol``, ``route_tol``, ``grad_tol``,
``grad_tol_routed``, ``router_tol``, ``weight_tol``, ``mixer_tol``,
``state_tol`` and ``decay_tol`` come from: the reference (``chipbench/reference_nemotron3.py``)
on the cell's own first batch and weights, computed in a lower precision or
with one piece of the mathematics changed, and held against itself in float32
BY THE DRIVER'S OWN CHECKS (``train_steps_ssm.failed_checks``, the variant's
answers in the program's place): every line carries ``failed_checks`` and
``correct``. Run on the chip by hand when a tolerance is in question; no cell
and no check runs it:

    python chipbench/nemotron3_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``held_gap``,
``grad_gap`` and the leaf it is on, ``router_gap``, ``mixer_gap``,
``scan_gap``, ``scan32_gap``). ``bf16`` is what the configuration states: weights and
activations in bf16; the router, norms, softmaxes, the taps' sum, the scan's
state and decay and the loss in float32. It is the band a sound program lies
in: the tolerances sit above it and it comes out ``correct``. Every other
variant is ``bf16`` with one change, and must come out NOT correct. A variant
that changes only a piece of the state-space mixer is read on the mixer and the
scan ALONE (cheap; the whole-model readings it is judged with are ``bf16``'s of
that seed) unless ``--whole`` asks for its whole-model pass too:
  program_router   not a variant of the reference: the PROGRAM's router
                   (``moe.gating.topk_select`` with the forms its own
                   configuration gives) on the driver's logits and bias, the
                   reading ``router_tol`` has to pass (``router_gap`` only)
  program_mixer    likewise the PROGRAM's state-space mixer
                   (``Transformer._ssm``) and scan (``ops.ssd.ssd_chunked``)
                   on the driver's inputs, the readings ``mixer_tol``,
                   ``state_tol`` and ``decay_tol`` have to pass
  gate_after_norm  the grouped norm first, then the gate
  one_norm         one RMSNorm over all 4096 channels for the 8 groups'
  wrong_group      head h reads B and C of the group after its own
  dt_clamped       the step clamped to [time_step_min, time_step_max]
  no_conv_bias     the convolution without its bias
  no_skip          the scan without ``D x``
  gated_expert     an expert gated by SiLU of its own up projection
  relu_not_squared relu for relu squared, experts and shared expert
  bias_weighed     the selection bias in the weights too
  rotation         attention's q and k rotated (RoPE at ``rope_theta``)
  bf16_state       the scan's state kept in bf16 between tokens
  bf16_decay       the decay's exponent (step x A) formed in bf16
  bf16_router      router logits, sigmoid and weights in bf16
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_ssm as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_mla import (mixer_answers, router_gaps,  # noqa: E402
                                               router_inputs)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402

WRONG = ["gate_after_norm", "one_norm", "wrong_group", "dt_clamped", "no_conv_bias",
         "no_skip", "gated_expert", "relu_not_squared", "bias_weighed", "rotation"]
LOWER = ["bf16_state", "bf16_decay", "bf16_router"]
# the reference's functions that are pieces of the state-space mixer
MIXER = {"gated_norm", "group_of", "step_and_decay", "decay_of", "state_dtype",
         "taps_sum", "scan"}


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_nemotron3 as ref

    f32, low = jnp.float32, jnp.bfloat16
    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=low)}
    plain = {k: getattr(ref, k) for k in ("gated_norm", "group_of", "step_and_decay",
                                          "taps_sum", "scan", "mlp", "linear")}
    k = int(src["num_experts_per_tok"])
    scale = float(src.get("routed_scaling_factor", 1.0))

    def choose_with(weigh_bias=False, dtype=f32):
        def choose(logits, bias, cfg):
            s = jax.nn.sigmoid(logits.astype(dtype))
            biased = s + jax.lax.stop_gradient(bias.astype(dtype))
            _, chosen = jax.lax.top_k(biased, k)
            weight = jnp.take_along_axis(biased if weigh_bias else s, chosen, axis=-1)
            weight = weight / (weight.sum(axis=-1, keepdims=True) + jnp.asarray(1e-20, dtype))
            weight = weight * jnp.asarray(scale, dtype)
            return s.astype(f32), chosen.astype(jnp.int32), weight.astype(f32)
        return choose

    def logits_bf16(w, prefix, y):
        return y.astype(low) @ w[prefix + "gate.weight"].astype(low).T

    def gate_after_norm(o, z, gain, groups, eps):
        B, T, inner = o.shape
        g = o.astype(f32).reshape(B, T, groups, inner // groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return (g.reshape(B, T, inner) * gain.astype(f32)
                * jax.nn.silu(z.astype(f32))).astype(o.dtype)

    def clamped(dt, dt_bias, A_log):
        step, A = plain["step_and_decay"](dt, dt_bias, A_log)
        return jnp.clip(step, float(src.get("time_step_min", 1e-3)),
                        float(src.get("time_step_max", 1e-1))), A

    def decay_bf16(dt, A):
        return jnp.exp((dt.astype(low) * A.astype(low)).astype(f32))

    # (an expert's variants replace ``mlp`` itself, not a name it reads: the
    # reference wraps ``mlp`` in jax.checkpoint, which keeps a trace by the
    # function's identity, so a new ``relu2`` under the old ``mlp`` would
    # run the trace of the variant before it)
    def gated(w, name, y):
        up = plain["linear"](y, w[name + "up_proj.weight"])
        return plain["linear"](jax.nn.silu(up) * up, w[name + "down_proj.weight"])

    def unsquared(w, name, y):
        up = plain["linear"](y, w[name + "up_proj.weight"])
        return plain["linear"](jax.nn.relu(up), w[name + "down_proj.weight"])

    def rotated(q, k_, cfg):
        def rope(x):
            Dh = x.shape[-1]
            inv = 1.0 / (float(cfg.get("rope_theta", 10000)) ** (
                jnp.arange(0, Dh, 2, dtype=f32) / Dh))
            angles = jnp.arange(x.shape[1], dtype=f32)[:, None] * inv[None, :]
            angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
            half = Dh // 2
            turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
            return x * jnp.cos(angles).astype(x.dtype) + turned * jnp.sin(angles).astype(x.dtype)
        return rope(q), rope(k_)

    changed = {
        "gate_after_norm": {"gated_norm": gate_after_norm},
        "one_norm": {"gated_norm": lambda o, z, gain, groups, eps: plain["gated_norm"](
            o, z, gain, 1, eps)},
        "wrong_group": {"group_of": lambda h, H, G: (plain["group_of"](h, H, G) + 1) % G},
        "dt_clamped": {"step_and_decay": clamped},
        "no_conv_bias": {"taps_sum": lambda u, taps, bias: plain["taps_sum"](
            u, taps, jnp.zeros_like(bias))},
        "no_skip": {"scan": lambda x, dt, A, B, C, D: plain["scan"](
            x, dt, A, B, C, jnp.zeros_like(D))},
        "gated_expert": {"mlp": gated},
        "relu_not_squared": {"mlp": unsquared},
        "bias_weighed": {"choose": choose_with(weigh_bias=True)},
        "rotation": {"positioned": rotated},
        "bf16_state": {"state_dtype": lambda: low},
        "bf16_decay": {"decay_of": decay_bf16},
        "bf16_router": {"router_logits": logits_bf16, "choose": choose_with(dtype=low)},
    }
    return {"float32": {}, "bf16": bf16,
            **{name: {**bf16, **fns} for name, fns in changed.items()}}


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up, drop nothing, move no bias and
    take one route by construction)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact_loss,
         "route_gap": line["route_gap"], "held_gap": line["held_gap"],
         "counters_add_up": True, "overflow": [0, 0], "bias_grad": 0.0,
         "bias_update_gap": 0.0, "grad_gaps": line["grad_gaps"],
         "weight_gap": line["weight_gap"],
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "mixer_gaps": line.get("mixer_gaps") or {"ssm/y": 0.0},
         "scan_gaps": line.get("scan_gaps") or {"scan/y": 0.0, "scan32/y": 0.0},
         "routes": {"ssd": "xla", "ssd_alone": "xla", "ssd_step_kernels": False}}, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def judge_log(path: str, traffic: dict) -> list:
    """The ``band`` lines of an earlier run's output, judged again by the
    limits the traffic file has NOW."""
    import json

    lines = [json.loads(x) for x in open(path) if x.startswith('{"phase": "band"')]
    exact = {x["seed"]: x["loss"] for x in lines if x["variant"] == "float32"}
    out = [judged(x, exact[x["seed"]], traffic) for x in lines if "grad_gaps" in x]
    for line in out:
        harness.emit(**{k: v for k, v in line.items() if k != "grad_gaps"})
    return out


def measure(cell: dict, seeds, names, rehearsal=None, few=None, whole=False) -> list:
    """One record per (variant, seed): the variant against float32, through
    the driver's own checks. ``few``: every variant but ``bf16`` and the
    program's own readings runs on the first ``few`` seeds only. ``whole``: a
    variant of the mixer alone runs its whole-model pass too."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_nemotron3 as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    bias_std = float(traffic["select_bias_std"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants(src)
    low = jnp.bfloat16
    exact, whole_of, out = {}, {}, []
    plain_router = driver.reference_router(src)
    plain_choose = ref.choose          # bound now: a variant swaps the module's
    exact_router = lambda logits, bias: plain_choose(logits, bias, src)[1:]
    inputs = lambda seed: router_inputs(
        harness.seed32(seed), batch * seq, mcfg.n_experts, bias_std)

    def mixer_of(seed):
        drawn = driver.initial_params(model, harness.seed32(seed), bias_std)
        return driver.mixer_inputs(drawn, src, harness.seed32(seed), batch, seq, low)

    def scan_of(seed, given):
        return driver.scan_inputs(given[0], given[1], src, harness.seed32(seed), low)

    exact_mixer, exact_scan, band_alone = {}, {}, {}

    def alone(seed, mixer, scan):
        """(mixer_gaps, scan_gaps) of ``mixer`` and ``scan`` on the seed's
        inputs against the float32 reference's answers."""
        given = mixer_of(seed)
        mixed = {"ssm/" + k: v for k, v in grad_gaps(
            mixer_answers(mixer, *given), exact_mixer[seed]).items()}
        scanned = driver.scan_gaps_of(scan, *scan_of(seed, given), exact_scan[seed])
        return mixed, scanned

    def record(name, seed, router, mixer, scanned, **whole_model):
        line = {"variant": name, "seed": seed, **whole_model,
                "router_gap": max(router.values()), "router_gaps": router,
                "mixer_gap": max(mixer.values()), "mixer_gaps": mixer,
                "scan_gap": max(v for k, v in scanned.items() if k.startswith("scan/")),
                "scan32_gap": max(v for k, v in scanned.items() if k.startswith("scan32/")),
                "scan_gaps": scanned}
        out.append(judged(line, exact[seed]["loss"], traffic) if "grad_gaps" in line else line)
        harness.emit(phase="band", **out[-1])

    nothing = {"choice": 0.0, "weight": 0.0}
    for name in ["float32"] + [n for n in names if n != "float32"]:
        many = name in ("bf16", "program_router", "program_mixer") or (
            name == "float32" and "bf16" in names)
        if name == "program_router":
            for seed in seeds:
                record(name, seed, router_gaps(
                    driver.program_router(mcfg), inputs(seed), exact_router),
                    {"ssm/y": 0.0}, {"scan/y": 0.0, "scan32/y": 0.0})
            continue
        if name == "program_mixer":
            for seed in seeds:
                record(name, seed, nothing, *alone(
                    seed, driver.program_mixer(model), driver.program_scan()))
            continue
        fns = every[name]
        # a trace kept by a function's identity (jit, jax.checkpoint) must not
        # outlive the pieces it read
        jax.clear_caches()
        plain = {k: getattr(ref, k) for k in fns}
        for k, fn in fns.items():
            setattr(ref, k, fn)
        try:
            of_mixer = bool(MIXER & set(fns))
            program = driver.reference_program(src)
            for seed in (seeds if many else seeds[:few]):
                # the whole model first, on a device that holds nothing else
                if of_mixer and not whole and seed in whole_of:
                    readings = {**whole_of[seed], "whole_model_of": "bf16"}
                else:
                    ids = next(batches(mcfg.vocab_size, batch, seq, seed))["input_ids"]
                    weights = driver.to_source_names(
                        driver.initial_params(model, harness.seed32(seed), bias_std), src)
                    got = driver.reference_first_step(program, weights, jnp.asarray(ids))
                    del weights
                    if name == "float32":
                        exact[seed] = got
                    base = exact[seed]
                    gaps = grad_gaps(got["grads"] if name == "float32"
                                     else got.pop("grads"), base["grads"])
                    worst = max(gaps, key=lambda leaf: gaps[leaf]
                                if gaps[leaf] == gaps[leaf] else float("inf"))
                    readings = dict(
                        loss=got["loss"], loss_gap=abs(got["loss"] - base["loss"]),
                        route_gap=route_gap(got["expert_tokens"], base["expert_tokens"]),
                        held_gap=route_gap(got["held_rows"], base["held_rows"]),
                        weight_gap=driver.weight_gap(
                            got["expert_weight"], got["expert_tokens"],
                            base["expert_weight"], base["expert_tokens"],
                            int(traffic.get("weight_min_tokens", 0)))[0],
                        weight_gap_all_experts=driver.weight_gap(
                            got["expert_weight"], got["expert_tokens"],
                            base["expert_weight"], base["expert_tokens"])[0],
                        least_expert_tokens=int(got["expert_tokens"].min()),
                        grad_gap=gaps[worst], grad_gap_leaf=worst,
                        grad_gap_routed=max(g for leaf, g in gaps.items()
                                            if driver.is_routed(leaf)),
                        grad_gap_others=max(g for leaf, g in gaps.items()
                                            if not driver.is_routed(leaf)),
                        grad_gaps=gaps)
                    if name == "bf16":
                        whole_of[seed] = readings
                # a variant that changes a piece of the mixer is read on the
                # mixer and the scan alone; the others read there as ``bf16``
                if name == "float32":
                    given = mixer_of(seed)
                    exact_mixer[seed] = jax.device_get(
                        mixer_answers(driver.reference_mixer(src), *given))
                    exact_scan[seed] = jax.device_get(
                        driver.exact_scan_answers(*scan_of(seed, given)))
                    del given
                    mixer, scanned = {"ssm/y": 0.0}, {"scan/y": 0.0, "scan32/y": 0.0}
                elif name == "bf16" or of_mixer or seed not in band_alone:
                    mixer, scanned = alone(seed, driver.reference_mixer(src, low),
                                           driver.reference_scan())
                    if not of_mixer:
                        band_alone[seed] = (mixer, scanned)
                else:
                    mixer, scanned = band_alone[seed]
                # a variant with its own router is read on the router alone
                router = (router_gaps(plain_router, inputs(seed), exact_router)
                          if "choose" in fns else nothing)
                record(name, seed, router, mixer, scanned, **readings)
        finally:
            for k, fn in plain.items():
                setattr(ref, k, fn)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["bf16", "program_router", "program_mixer"] + WRONG + LOWER)
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 and the program's own "
                    "readings (default: all)")
    ap.add_argument("--whole", action="store_true",
                    help="a variant of the mixer alone runs the whole model too")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("nemotron3-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, whole=args.whole)
    return 0


if __name__ == "__main__":
    sys.exit(main())
