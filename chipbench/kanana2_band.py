"""Where ``kanana2-train``'s ``loss_tol``, ``route_tol``, ``grad_tol``,
``grad_tol_routed``, ``router_tol``, ``weight_tol`` and ``mixer_tol`` come
from: the reference
(``chipbench/reference_kanana2.py``) on the cell's own first batch and
weights, computed in a lower precision or with one piece of the mathematics
changed, and held against itself in float32 BY THE DRIVER'S OWN CHECKS
(``train_steps_mla.failed_checks``, the variant's answers in the program's
place): every line carries ``failed_checks`` and ``correct``. Run on the chip
by hand when a tolerance is in question; no cell and no check runs it:

    python chipbench/kanana2_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``held_gap``,
``grad_gap`` and the leaf it is on, ``router_gap``, ``weight_gap``,
``mixer_gap``). ``bf16`` is what the
configuration states: weights and activations in bf16; the router, norms,
softmaxes and the loss in float32. It is the band a sound program lies in:
the tolerances sit above it and it comes out ``correct``. Every other variant
is ``bf16`` with one change, and must come out NOT correct:
  program_router     not a variant of the reference: the PROGRAM's router
                     (``moe.gating.topk_select`` with the forms its own
                     configuration gives) on the driver's logits, the reading
                     ``router_tol`` has to pass (``router_gap`` only)
  no_bias            the selection bias left out of the choice
  bias_weighed       the bias in the weights as well as in the choice
  softmax_router     softmax over the experts for the sigmoid of each
  no_scale           weights not multiplied by routed_scaling_factor
  no_norm            weights not normalised over the chosen
  scale_by_content   scores over sqrt(128), the content width, for sqrt(192)
  rope_none          no rotation at all
  rope_content_too   the first 64 content dims rotated as well as the rope dims
  key_not_rotated    q's rope dims rotated, the one rotary key not
  no_latent_norm     no RMSNorm on the latent
  no_shared          the shared experts left out
  shared_gated       the shared experts through a sigmoid gate (of a
                     zero-initialised projection, as the other families': 0.5)
  layer0_routed      the leading dense layer computed as a routed one (with
                     layer 1's router, experts and shared experts)
  bf16_router        router logits, sigmoid and weights in bf16
  bf16_softmax       attention's softmax in bf16 (scores, exponentials and
                     probabilities)
  latent_rounded     the normed latent kept to 6 mantissa bits, one below the
                     bf16 the program rounds it to
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_mla as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402

WRONG = ["no_bias", "bias_weighed", "softmax_router", "no_scale", "no_norm",
         "scale_by_content", "rope_none", "rope_content_too", "key_not_rotated",
         "no_latent_norm", "no_shared", "shared_gated", "layer0_routed"]
LOWER = ["bf16_router", "bf16_softmax", "latent_rounded"]
# the reference's functions that are pieces of its attention
ATTENTION = {"score_scale", "rope", "rope_key", "rope_query", "join",
             "latent_norm", "softmax_rows", "attention"}


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_kanana2 as ref

    f32, low = jnp.float32, jnp.bfloat16
    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=low)}
    plain = {k: getattr(ref, k) for k in ("rope", "join", "shared", "rms_norm", "layer")}
    k = src["num_experts_per_tok"]
    scale = float(src.get("routed_scaling_factor", 1.0))

    def choose_with(score=jax.nn.sigmoid, select=True, weigh=False, norm=True,
                    times=scale, dtype=f32):
        def choose(logits, bias, cfg):
            s = score(logits.astype(dtype))
            b = jax.lax.stop_gradient(bias.astype(dtype))
            _, chosen = jax.lax.top_k(s + b if select else s, k)
            weight = jnp.take_along_axis(s + b if weigh else s, chosen, axis=-1)
            if norm:
                weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
            weight = weight * jnp.asarray(times, dtype)
            return s.astype(f32), chosen.astype(jnp.int32), weight.astype(f32)
        return choose

    def logits_bf16(w, prefix, y):
        return (y.astype(low) @ w[prefix + "gate.weight"].astype(low).T)

    def join_rotating_content(content, rotary):
        """[content | rotary] with the content's first dr dims rotated too."""
        dr = src["qk_rope_head_dim"]
        turned = plain["rope"](content[..., :dr], float(src["rope_theta"]))
        return plain["join"](jnp.concatenate([turned, content[..., dr:]], axis=-1), rotary)

    def shared_gated(w, prefix, y, remat=False):
        return 0.5 * plain["shared"](w, prefix, y, remat)

    def layer0_routed(w, i, x, cfg, remat=False):
        if i != 0:
            return plain["layer"](w, i, x, cfg, remat)
        # layer 0's own attention and norms, layer 1's router and experts
        alias = dict(w)
        for name in w:
            if name.startswith("model.layers.1.mlp."):
                alias[name.replace("layers.1.", "layers.0.")] = w[name]
        routed = ref._Static({**cfg, "first_k_dense_replace": 0})
        out = plain["layer"](alias, 0, x, routed, remat)[0]
        return out, None, None, None      # no routing row: the counts stay 4 layers'

    # rounding the normed latent to bf16 once more is the identity on a bf16
    # value: the variant keeps 6 mantissa bits of it, one below bf16's 7
    # (straight through: the rounding has no gradient of its own)
    def latent_rounded(c, gain, eps):
        n = plain["rms_norm"](c, gain, eps).astype(f32)
        bits = jax.lax.bitcast_convert_type(n, jnp.uint32)
        bits = (bits + jnp.uint32(0x10000)) & jnp.uint32(0xFFFE0000)   # 6 stored bits
        low6 = jax.lax.bitcast_convert_type(bits, f32)
        return (n + jax.lax.stop_gradient(low6 - n)).astype(c.dtype)

    changed = {
        "no_bias": {"choose": choose_with(select=False)},
        "bias_weighed": {"choose": choose_with(weigh=True)},
        "softmax_router": {"choose": choose_with(score=lambda z: jax.nn.softmax(z, axis=-1))},
        "no_scale": {"choose": choose_with(times=1.0)},
        "no_norm": {"choose": choose_with(norm=False)},
        "scale_by_content": {"score_scale": lambda cfg: cfg["qk_nope_head_dim"] ** -0.5},
        "rope_none": {"rope": lambda x, theta: x},
        "rope_content_too": {"join": join_rotating_content},
        "key_not_rotated": {"rope_key": lambda k_r, theta: k_r},
        "no_latent_norm": {"latent_norm": lambda c, gain, eps: c},
        "no_shared": {"shared": lambda w, prefix, y, remat=False: 0.0},
        "shared_gated": {"shared": shared_gated},
        "layer0_routed": {"layer": layer0_routed},
        "bf16_router": {"router_logits": logits_bf16, "choose": choose_with(dtype=low)},
        "bf16_softmax": {"softmax_rows": lambda s: jax.nn.softmax(s.astype(low), axis=-1)},
        "latent_rounded": {"latent_norm": latent_rounded},
    }
    return {"float32": {}, "bf16": bf16,
            **{name: {**bf16, **fns} for name, fns in changed.items()}}


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up and drop nothing by construction)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact_loss,
         "route_gap": line["route_gap"], "held_gap": line["held_gap"],
         "counters_add_up": True, "overflow": [0, 0], "bias_grad": 0.0,
         "bias_update_gap": 0.0,
         "grad_gaps": line["grad_gaps"],
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "weight_gap": line.get("weight_gap", 0.0),
         "mixer_gaps": line.get("mixer_gaps") or {"y": 0.0}}, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def judge_log(path: str, traffic: dict) -> list:
    """The ``band`` lines of an earlier run's output, judged again by the
    limits the traffic file has NOW."""
    import json

    lines = [json.loads(x) for x in open(path) if x.startswith('{"phase": "band"')]
    exact = {x["seed"]: x["loss"] for x in lines if x["variant"] == "float32"}
    out = [judged(x, exact[x["seed"]], traffic) for x in lines if "loss" in x]
    for line in out:
        harness.emit(**{k: v for k, v in line.items() if k != "grad_gaps"})
    return out


def measure(cell: dict, seeds, names, rehearsal=None, few=None) -> list:
    """One record per (variant, seed): the variant against float32, through
    the driver's own checks. ``few``: every variant but ``bf16`` and
    ``program_router`` runs on the first ``few`` seeds only."""
    import jax.numpy as jnp

    from chipbench import reference_kanana2 as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    bias_std = float(traffic["select_bias_std"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants(src)
    exact, out = {}, []
    plain_router = driver.reference_router(src)
    # the plain ``choose``, bound now: a variant swaps the module's
    plain_choose = ref.choose
    exact_router = lambda logits, bias: plain_choose(logits, bias, src)[1:]
    inputs = lambda seed: driver.router_inputs(
        harness.seed32(seed), batch * seq, mcfg.n_experts, bias_std)
    # the mixer alone: the variant's attention in bf16 against the plain one in
    # float32, on the driver's own inputs
    mixer_of = lambda seed: driver.mixer_inputs(
        driver.initial_params(model, harness.seed32(seed), bias_std),
        harness.seed32(seed), batch, seq, mcfg,
        float(traffic["mixer_score_gain"]), jnp.bfloat16)
    exact_mixer, band_mixer = {}, {}

    def record(name, seed, router, **whole):
        line = {"variant": name, "seed": seed, **whole,
                "router_gap": max(router.values()), "router_gaps": router}
        out.append(judged(line, exact[seed]["loss"], traffic) if whole else line)
        harness.emit(phase="band", **out[-1])

    for name in ["float32"] + [n for n in names if n != "float32"]:
        many = name == "bf16" or (name == "float32" and "bf16" in names)
        if name == "program_router":
            for seed in seeds:
                record(name, seed, driver.router_gaps(
                    driver.program_router(mcfg), inputs(seed), exact_router))
            continue
        fns = every[name]
        plain = {k: getattr(ref, k) for k in fns}
        for k, fn in fns.items():
            setattr(ref, k, fn)
        try:
            program = driver.reference_program(src)
            for seed in (seeds if many else seeds[:few]):
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
                # a variant with its own router is read on the router alone
                # too (its ``choose`` is the module's now); the others route
                # as the reference does: distance 0
                router = (driver.router_gaps(plain_router, inputs(seed), exact_router)
                          if "choose" in fns else {"choice": 0.0, "weight": 0.0})
                # a variant that changes a piece of the attention is read on
                # the mixer alone; the others read there as ``bf16`` does
                if name == "float32":
                    exact_mixer[seed] = driver.mixer_answers(
                        driver.reference_mixer(src), *mixer_of(seed))
                    mixer = {k: 0.0 for k in exact_mixer[seed]}
                elif name == "bf16" or ATTENTION & set(fns) or seed not in band_mixer:
                    mixer = driver.mixer_gaps(
                        driver.reference_mixer(src, jnp.bfloat16), mixer_of(seed),
                        exact_mixer[seed])
                    if not ATTENTION & set(fns):
                        band_mixer[seed] = mixer
                else:
                    mixer = band_mixer[seed]
                record(name, seed, router, loss=got["loss"],
                       loss_gap=abs(got["loss"] - base["loss"]),
                       route_gap=route_gap(got["expert_tokens"], base["expert_tokens"]),
                       held_gap=route_gap(got["held_rows"], base["held_rows"]),
                       weight_gap=driver.weight_gap(
                           got["expert_weight"], got["expert_tokens"],
                           base["expert_weight"], base["expert_tokens"]),
                       mixer_gap=max(mixer.values()), mixer_gaps=mixer,
                       grad_gap=gaps[worst], grad_gap_leaf=worst,
                       grad_gap_routed=max(g for leaf, g in gaps.items()
                                           if driver.is_routed(leaf)),
                       grad_gap_others=max(g for leaf, g in gaps.items()
                                           if not driver.is_routed(leaf)),
                       grad_gaps=gaps)
        finally:
            for k, fn in plain.items():
                setattr(ref, k, fn)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["program_router", "bf16"] + WRONG + LOWER)
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 and program_router "
                    "(default: all)")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("kanana2-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few)
    return 0


if __name__ == "__main__":
    sys.exit(main())
