"""Where ``kimilinear-train``'s tolerances come from (``loss_tol``,
``route_tol``, ``grad_tol``, ``grad_tol_routed``, ``router_tol``,
``weight_tol``, ``mixer_tol``, ``rule_tol``, ``kda_mixer_tol``): the reference
(``chipbench/reference_kimilinear.py``) on the cell's own first batch and
weights, computed in a lower precision or with one piece of the mathematics
changed, and held against itself in float32 BY THE DRIVER'S OWN CHECKS
(``train_steps_kda.failed_checks``, the variant's answers in the program's
place): every line carries ``failed_checks`` and ``correct``. Run on the chip
by hand when a tolerance is in question; no cell and no check runs it:

    python chipbench/kimilinear_band.py --seeds 11 12 [--variants bf16 ...]
    python chipbench/kimilinear_band.py --alone --seeds 11 12 13 14 15 16

``--alone`` reads the mechanisms alone and runs no whole model (minutes where
a whole-model line costs a reference run): the rule, the KDA mixer, the
latent-attention mixer and the router, each variant against float32 on the
driver's own inputs; ``--judge LOG`` judges an earlier run's lines again by
the traffic file's limits as they are now, without the chip.

Variants, each one line per seed. ``bf16`` is what the configuration states:
weights and activations in bf16; the router, norms, softmaxes, the decay, the
rule's state and the loss in float32. It is the band a sound program lies in:
the tolerances sit above it and it comes out ``correct``. Every other variant
is ``bf16`` with one change, and must come out NOT correct:
  program_rule       not a variant of the reference: the PROGRAM's rule
                     (``ops.kda.kda_chunked`` on the route its shapes take) on
                     the driver's inputs, the reading ``rule_tol`` has to pass
  program_router     likewise the program's router (``router_tol``)
  scalar_rule        the scalar rule in KDA's place: one decay a head, g's
                     mean over the key channels
  no_decay           g = 0: the plain delta rule
  no_beta            beta = 1
  no_l2norm          q and k not normalised
  silu_gate          SiLU for the output gate's sigmoid (the scalar rule's form)
  rope_on_mla        the latent layer's 64 extra dims rotated (kanana-2's form,
                     adjacent pairs, theta = rope_theta)
  no_latent_norm     no RMSNorm on the latent
  bias_weighed       the selection bias in the weights as well as in the choice
  softmax_router     softmax over the experts for the sigmoid of each
  no_scale           weights not multiplied by routed_scaling_factor (2.446)
  no_shared          the shared expert left out
  layer0_routed      the leading dense layer computed as a routed one (with
                     layer 1's router, experts and shared expert)
  bf16_state         the rule's state S carried in bf16
  bf16_gamma         the decay cumulated over a chunk of 64 tokens in bf16
  bf16_router        router logits, sigmoid and weights in bf16
  bf16_softmax       the latent layer's scores and probabilities in bf16
and three more lowered precisions that are READ and that no limit tells apart
(``FINER``: each reads inside the program's own band on the KDA mixer alone;
``chipbench/KIMILINEAR.md`` and ``PERF.md`` section 7 have the readings):
  bf16_decay         the log-decay formed in bf16: the low-rank pair's sum with
                     dt_bias, softplus, A and their product each rounded
  bf16_l2norm        q's and k's sum of squares and its inverse root in bf16
  bf16_gate          the output gate's input and its sigmoid in bf16
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_kda as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402

WRONG = ["scalar_rule", "no_decay", "no_beta", "no_l2norm", "silu_gate", "rope_on_mla",
         "no_latent_norm", "bias_weighed", "softmax_router", "no_scale", "no_shared",
         "layer0_routed"]
LOWER = ["bf16_state", "bf16_gamma", "bf16_router", "bf16_softmax"]
FINER = ["bf16_decay", "bf16_l2norm", "bf16_gate"]
# the reference's functions that are pieces of its rule, of its KDA mixer and
# of its attention: a variant that swaps one is read on that mechanism alone
RULE = {"delta_rule"}
KDA = RULE | {"l2norm", "output_gate", "short_conv", "log_decay", "write_strength"}
ATTENTION = {"score_scale", "place_query", "place_key", "join", "latent_norm",
             "softmax_rows", "attention"}


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_kimilinear as ref

    f32, low = jnp.float32, jnp.bfloat16
    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=low)}
    plain = {k: getattr(ref, k) for k in ("delta_rule", "shared", "layer", "rule_step")}
    k = src["num_experts_per_token"]
    scale = float(src.get("routed_scaling_factor", 1.0))

    def choose_with(score=jax.nn.sigmoid, weigh=False, times=scale, dtype=f32):
        def choose(logits, bias, cfg):
            s = score(logits.astype(dtype))
            b = jax.lax.stop_gradient(bias.astype(dtype))
            _, chosen = jax.lax.top_k(s + b, k)
            weight = jnp.take_along_axis(s + b if weigh else s, chosen, axis=-1)
            weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
            weight = weight * jnp.asarray(times, dtype)
            return s.astype(f32), chosen.astype(jnp.int32), weight.astype(f32)
        return choose

    def logits_bf16(w, prefix, y):
        return (y.astype(low) @ w[prefix + "gate.weight"].astype(low).T)

    # a float32 value rounded to bf16's 8 bits of mantissa, as an OPERATION: a
    # pair of converts is taken out by XLA (excess precision is allowed) and
    # the variant then reads as float32 does (my chip run, PR 67)
    to_bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rule_with(g_of=lambda g: g, beta_of=lambda b: b):
        def rule(q, k, v, g, beta, remat=False):
            return plain["delta_rule"](q, k, v, g_of(g), beta_of(beta), remat)
        return rule

    def gamma_rounded(g):
        """g with its cumulative sum over each chunk of 64 tokens rounded to
        bf16: what a chunked form that keeps Gamma in bf16 decays by
        (straight through: the rounding has no gradient of its own)."""
        B, T = g.shape[:2]
        n = 64 if T % 64 == 0 else T
        gamma = jnp.cumsum(g.reshape((B, T // n, n) + g.shape[2:]), axis=2)
        rounded = to_bf16(gamma)
        steps = jnp.diff(rounded, axis=2, prepend=jnp.zeros_like(rounded[:, :, :1]))
        return g + jax.lax.stop_gradient(steps.reshape(g.shape) - g)

    def step_bf16_state(S, qt, kt, vt, gt, bt):
        S, o = plain["rule_step"](S, qt, kt, vt, gt, bt)
        return S + jax.lax.stop_gradient(to_bf16(S) - S), o

    def decay_bf16(w, prefix, x, cfg):
        lin = cfg["linear_attn_config"]
        Hk, dk = lin["num_heads"], lin["head_dim"]
        B, T, _ = x.shape
        a = ref.linear(ref.linear(x, w[prefix + "f_a_proj.weight"]),
                       w[prefix + "f_b_proj.weight"])
        a = to_bf16(a.astype(f32) + to_bf16(w[prefix + "dt_bias"].astype(f32)))
        A = to_bf16(jnp.exp(to_bf16(w[prefix + "A_log"].astype(f32).reshape(Hk))))
        return to_bf16(-A[:, None] * to_bf16(jax.nn.softplus(a.reshape(B, T, Hk, dk))))

    def l2norm_bf16(x):
        x32 = x.astype(f32)
        root = to_bf16(jax.lax.rsqrt(
            to_bf16(jnp.sum(to_bf16(x32 * x32), axis=-1, keepdims=True)) + 1e-6))
        return (x32 * root).astype(x.dtype)

    def rotated(x):
        """kanana-2's rotation of a head's extra dims: stored as adjacent
        pairs, the even ones moved in front, rotate-half."""
        B, T, H, dr = x.shape
        x = x.reshape(B, T, H, dr // 2, 2).swapaxes(-1, -2).reshape(B, T, H, dr)
        inv = 1.0 / (float(src["rope_theta"]) ** (jnp.arange(0, dr, 2, dtype=f32) / dr))
        ang = jnp.arange(T, dtype=f32)[:, None] * inv[None, :]
        ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
        half = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], axis=-1)
        return x * jnp.cos(ang).astype(x.dtype) + half * jnp.sin(ang).astype(x.dtype)

    def layer0_routed(w, i, x, cfg, remat=False):
        if i != 0:
            return plain["layer"](w, i, x, cfg, remat)
        # layer 0's own mixer and norms, layer 1's router and experts
        alias = dict(w)
        for name in w:
            if name.startswith("model.layers.1.block_sparse_moe."):
                alias[name.replace("layers.1.", "layers.0.")] = w[name]
        routed = ref._Static({**cfg, "first_k_dense_replace": 0})
        out = plain["layer"](alias, 0, x, routed, remat)[0]
        return out, None, None, None      # no routing row: the counts stay 4 layers'

    changed = {
        "scalar_rule": {"delta_rule": rule_with(lambda g: jnp.broadcast_to(
            g.mean(axis=-1, keepdims=True), g.shape))},
        "no_decay": {"delta_rule": rule_with(jnp.zeros_like)},
        "no_beta": {"delta_rule": rule_with(beta_of=jnp.ones_like)},
        "no_l2norm": {"l2norm": lambda x: x},
        "silu_gate": {"output_gate": jax.nn.silu},
        "rope_on_mla": {"place_query": rotated, "place_key": rotated},
        "no_latent_norm": {"latent_norm": lambda c, gain, eps: c},
        "bias_weighed": {"choose": choose_with(weigh=True)},
        "softmax_router": {"choose": choose_with(score=lambda z: jax.nn.softmax(z, axis=-1))},
        "no_scale": {"choose": choose_with(times=1.0)},
        "no_shared": {"shared": lambda w, prefix, y, remat=False: 0.0},
        "layer0_routed": {"layer": layer0_routed},
        "bf16_state": {"rule_step": step_bf16_state},
        "bf16_gamma": {"delta_rule": rule_with(gamma_rounded)},
        "bf16_router": {"router_logits": logits_bf16, "choose": choose_with(dtype=low)},
        "bf16_decay": {"log_decay": decay_bf16},
        "bf16_l2norm": {"l2norm": l2norm_bf16},
        "bf16_gate": {"output_gate": lambda z: to_bf16(jax.nn.sigmoid(to_bf16(z)))},
        "bf16_softmax": {"softmax_rows": lambda scores: to_bf16(
            jax.nn.softmax(to_bf16(scores), axis=-1))},
    }
    return {"float32": {}, "bf16": bf16,
            **{name: {**bf16, **fns} for name, fns in changed.items()}}


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up, drop nothing, rotate nothing and
    walk the configuration's rules by construction)."""
    failed = driver.failed_checks(
        {"losses": [line.get("loss", exact_loss)], "reference_loss": exact_loss,
         "route_gap": line.get("route_gap", 0.0), "held_gap": line.get("held_gap", 0.0),
         "counters_add_up": True, "overflow": [0, 0], "bias_grad": 0.0,
         "bias_update_gap": 0.0, "grad_gaps": line.get("grad_gaps") or {"-": 0.0},
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "weight_gap": line.get("weight_gap", 0.0),
         "mixer_gaps": line.get("mixer_gaps") or {"y": 0.0},
         "rule_gaps": line.get("rule_gaps") or {"o": 0.0},
         "kda_mixer_gaps": line.get("kda_mixer_gaps") or {"y": 0.0},
         "rotated": 0, "kda_layers": [0, 0]}, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def judge_log(path: str, traffic: dict) -> list:
    """The ``band`` lines of an earlier run's output, judged again by the
    limits the traffic file has NOW."""
    import json

    lines = [json.loads(x) for x in open(path) if x.startswith('{"phase": "band"')]
    exact = {x["seed"]: x["loss"] for x in lines if x["variant"] == "float32" and "loss" in x}
    out = [judged(x, exact.get(x["seed"], 0.0), traffic) for x in lines]
    for line in out:
        harness.emit(**{k: v for k, v in line.items() if k != "grad_gaps"})
    return out


def measure(cell: dict, seeds, names, rehearsal=None, few=None, alone=False) -> list:
    """One record per (variant, seed): the variant against float32, through
    the driver's own checks. ``few``: every variant but ``bf16`` and the
    ``program_*`` readings runs on the first ``few`` seeds only. ``alone``:
    the mechanisms alone, no whole model."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_kimilinear as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    bias_std = float(traffic["select_bias_std"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    low = jnp.float32 if rehearsal.get("band_dtype") == "float32" else jnp.bfloat16
    every = variants(src)
    exact, out = {}, []
    plain_router = driver.reference_router(src)
    plain_choose = ref.choose            # bound now: a variant swaps the module's
    exact_router = lambda logits, bias: plain_choose(logits, bias, src)[1:]
    seed32 = harness.seed32
    router_in = lambda seed: driver.router_inputs(
        seed32(seed), batch * seq, mcfg.n_experts, bias_std)
    rule_in = lambda seed: driver.rule_inputs(seed32(seed), batch, seq, mcfg, low)
    mixer_in = lambda seed, which: driver.mixer_inputs(
        driver.initial_params(model, seed32(seed), bias_std), seed32(seed), batch, seq,
        mcfg, low, which, float(traffic["mixer_score_gain"]) if which == "mla" else 1.0)
    exact_rule, exact_mixer, band = {}, {}, {}

    def record(name, seed, **line):
        line = {"variant": name, "seed": seed, **line}
        for key in ("router", "mixer", "rule", "kda_mixer"):
            if line.get(key + "_gaps"):
                line[key + "_gap"] = max(line[key + "_gaps"].values())
        out.append(judged(line, exact.get(seed, {}).get("loss", 0.0), traffic))
        harness.emit(phase="band", **{k: v for k, v in out[-1].items() if k != "grad_gaps"},
                     **({"grad_gaps": out[-1]["grad_gaps"]} if "grad_gaps" in out[-1] else {}))

    def alone_readings(name, seed, fns):
        """The variant on each mechanism alone (the module's functions are the
        variant's now): {"rule_gaps", "kda_mixer_gaps", "mixer_gaps",
        "router_gaps"}; a mechanism the variant does not touch reads as
        ``bf16`` does."""
        if seed not in exact_rule:
            # kept on the HOST: six seeds' answers at the cell's shape are more
            # than the chip holds beside a reading
            exact_rule[seed] = jax.device_get(
                driver.rule_answers(driver.reference_rule, *rule_in(seed)))
            exact_mixer[seed] = {which: jax.device_get(driver.mixer_answers(
                driver.reference_mixer(src, which), *mixer_in(seed, which)))
                for which in ("kda", "mla")}
        base = band.setdefault(seed, {})
        got = {}
        # float32 comes first, with nothing swapped: the readings of the plain
        # reference in the lower precision are taken then, for every variant
        # that leaves a mechanism alone
        touched = set() if name == "float32" else set(fns) - {"loss_parts"}
        for key, pieces, read in (
                ("rule_gaps", RULE | {"rule_step"}, lambda: driver.rule_gaps(
                    driver.reference_rule, rule_in(seed), exact_rule[seed])),
                ("kda_mixer_gaps", KDA | {"rule_step"}, lambda: driver.mixer_gaps(
                    driver.reference_mixer(src, "kda", low), mixer_in(seed, "kda"),
                    exact_mixer[seed]["kda"])),
                ("mixer_gaps", ATTENTION, lambda: driver.mixer_gaps(
                    driver.reference_mixer(src, "mla", low), mixer_in(seed, "mla"),
                    exact_mixer[seed]["mla"])),
                ("router_gaps", {"choose", "router_logits"}, lambda: driver.router_gaps(
                    plain_router, router_in(seed), exact_router))):
            if touched & pieces:
                got[key] = read()
            else:
                if key not in base:
                    base[key] = read()
                got[key] = base[key]
        if name == "float32":
            return {"rule_gaps": {"o": 0.0}, "kda_mixer_gaps": {"y": 0.0},
                    "mixer_gaps": {"y": 0.0}, "router_gaps": {"choice": 0.0, "weight": 0.0}}
        return got

    for name in ["float32"] + [n for n in names if n != "float32"]:
        many = name == "bf16" or (name == "float32" and "bf16" in names) or alone
        if name == "program_router":
            for seed in seeds:
                record(name, seed, router_gaps=driver.router_gaps(
                    driver.program_router(mcfg), router_in(seed), exact_router))
            continue
        if name == "program_rule":
            for seed in seeds:
                record(name, seed, rule_gaps=driver.rule_gaps(
                    driver.program_rule(), rule_in(seed), exact_rule.get(seed)))
            continue
        if name == "program_mixers":
            for seed in seeds:
                record(name, seed, **{key: driver.mixer_gaps(
                    driver.program_mixer(model, which), mixer_in(seed, which),
                    driver.mixer_answers(driver.reference_mixer(src, which),
                                         *mixer_in(seed, which)))
                    for key, which in (("kda_mixer_gaps", "kda"), ("mixer_gaps", "mla"))})
            continue
        fns = every[name]
        plain = {k: getattr(ref, k) for k in fns}
        for k, fn in fns.items():
            setattr(ref, k, fn)
        try:
            program = None if alone else driver.reference_program(src)
            for seed in (seeds if many else seeds[:few]):
                if alone:
                    record(name, seed, **alone_readings(name, seed, fns))
                    continue
                ids = next(batches(mcfg.vocab_size, batch, seq, seed))["input_ids"]
                weights = driver.to_source_names(
                    driver.initial_params(model, seed32(seed), bias_std), src)
                got = driver.reference_first_step(program, weights, jnp.asarray(ids))
                del weights
                if name == "float32":
                    exact[seed] = got
                base = exact[seed]
                gaps = grad_gaps(got["grads"] if name == "float32"
                                 else got.pop("grads"), base["grads"])
                worst = max(gaps, key=lambda leaf: gaps[leaf]
                            if gaps[leaf] == gaps[leaf] else float("inf"))
                record(name, seed, loss=got["loss"],
                       loss_gap=abs(got["loss"] - base["loss"]),
                       route_gap=route_gap(got["expert_tokens"], base["expert_tokens"]),
                       held_gap=route_gap(got["held_rows"], base["held_rows"]),
                       weight_gap=driver.weight_gap(
                           got["expert_weight"], got["expert_tokens"],
                           base["expert_weight"], base["expert_tokens"]),
                       grad_gap=gaps[worst], grad_gap_leaf=worst,
                       grad_gap_routed=max(g for leaf, g in gaps.items()
                                           if driver.is_routed(leaf)),
                       grad_gap_others=max(g for leaf, g in gaps.items()
                                           if not driver.is_routed(leaf)),
                       grad_gaps=gaps, **alone_readings(name, seed, fns))
        finally:
            for k, fn in plain.items():
                setattr(ref, k, fn)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["program_rule", "program_router", "program_mixers", "bf16"]
                    + WRONG + LOWER)
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 and the program_* "
                    "readings (default: all)")
    ap.add_argument("--alone", action="store_true",
                    help="the mechanisms alone: no whole-model reference run")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("kimilinear-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, alone=args.alone)
    return 0


if __name__ == "__main__":
    sys.exit(main())
