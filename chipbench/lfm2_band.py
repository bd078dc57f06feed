"""Where ``lfm2-train``'s ``loss_tol``, ``route_tol``, ``grad_tol``,
``grad_tol_routed``, ``grad_tol_embed``, ``router_tol``, ``weight_tol``,
``mixer_tol``, ``mixer_tol_attn`` and ``mixer_tol_attn_gain`` come from: the
reference (``chipbench/reference_lfm2.py``) on the cell's own first batch and
weights, computed in a lower precision or with one piece of the
mathematics changed, and held against itself in float32 BY THE DRIVER'S OWN
CHECKS (``train_steps_sconv.failed_checks``, the variant's answers in the
program's place): every line carries ``failed_checks`` and ``correct``. Run on
the chip by hand when a tolerance is in question; no cell and no check runs it:

    python chipbench/lfm2_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``held_gap``,
``grad_gap`` and the leaf it is on, ``router_gap``, ``mixer_gap``). ``bf16`` is
what the configuration states: weights and activations in bf16; the router,
norms, softmaxes, the taps' sum and the loss in float32. It is the band a sound
program lies in: the tolerances sit above it and it comes out ``correct``.
Every other variant is ``bf16`` with one change, and must come out NOT correct.
A variant that changes only a piece of a mixer is read on the two mixers ALONE
(cheap; the whole-model readings it is judged with are ``bf16``'s of that seed)
unless ``--whole`` asks for its whole-model pass too:
  program_router     not a variant of the reference: the PROGRAM's router
                     (``moe.gating.topk_select`` with the forms its own
                     configuration gives) on the driver's logits and bias, the
                     reading ``router_tol`` has to pass (``router_gap`` only)
  program_mixers     likewise the PROGRAM's two mixers (``Transformer._sconv``
                     and ``._gqa`` on the timed routes) on the driver's inputs,
                     the readings ``mixer_tol`` / ``mixer_tol_attn`` have to
                     pass (``mixer_gap`` only)
  taps_2, taps_4     a convolution of 2 taps (the earliest dropped), of 4 (one
                     more, of the third's weight, a row earlier)
  taps_reversed      the taps in reverse order
  one_late           the convolution one position late (t reads t - 1 .. t + 1)
  no_gate_before     x through the taps without B
  no_gate_after      the taps' sum handed on without C
  c_x_exchanged      the projection's second and third block exchanged
  silu_after_taps    SiLU on the taps' sum (the DeltaNet prologue's form)
  qk_norm_whole      q and k normed over the WHOLE projection (OLMoE's form;
                     the gain tiled over the heads)
  qk_norm_after_rope the per-head norm after the rotation
  no_qk_norm         no q/k norm
  one_gain           k normed with q's gain
  softmax_router     softmax over the experts for the sigmoid of each
  bias_weighed       the selection bias in the weights too
  no_norm            weights not normalised over the chosen
  top_8              twice the experts a token (8 for 4)
  shared_added       expert 0 of every routed layer added for every token, as
                     a shared expert would be
  layer0_routed      the leading dense layer computed as a routed one (with
                     layer 2's router, bias and experts)
  untied_head        the head a matrix of its own (equal to the embedding
                     now: the loss is the same, the embedding's gradient
                     lacks the head's part)
  bf16_taps          the gate products and the taps' sum accumulated in bf16
  bf16_router        router logits, sigmoid and weights in bf16
  bf16_softmax       attention's softmax in bf16 (scores, exponentials and
                     probabilities)
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_sconv as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_mla import (mixer_answers, router_gaps,  # noqa: E402
                                               router_inputs, weight_gap)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402
from chipbench.drivers.train_steps_swa import both_mixer_gaps  # noqa: E402

WRONG = ["taps_2", "taps_4", "taps_reversed", "one_late", "no_gate_before",
         "no_gate_after", "c_x_exchanged", "silu_after_taps", "qk_norm_whole",
         "qk_norm_after_rope", "no_qk_norm", "one_gain", "softmax_router",
         "bias_weighed", "no_norm", "top_8", "shared_added", "layer0_routed",
         "untied_head"]
LOWER = ["bf16_taps", "bf16_router", "bf16_softmax"]
# the reference's functions that are pieces of a mixer
MIXER = {"taps_sum", "conv_mix", "head_norms", "qk", "softmax_rows"}
WHICH = ("sconv", "attn")


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_lfm2 as ref

    f32, low = jnp.float32, jnp.bfloat16
    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=low)}
    plain = {k: getattr(ref, k) for k in ("taps_sum", "conv_mix", "head_norms", "qk",
                                          "experts", "layer", "mlp", "rms_norm", "rope")}
    k = int(src["num_experts_per_tok"])
    scale = float(src.get("routed_scaling_factor", 1.0))

    def choose_with(score=jax.nn.sigmoid, norm=True, weigh_bias=False, top=k, dtype=f32):
        def choose(logits, bias, cfg):
            s = score(logits.astype(dtype))
            biased = s + jax.lax.stop_gradient(bias.astype(dtype))
            _, chosen = jax.lax.top_k(biased, top)
            weight = jnp.take_along_axis(biased if weigh_bias else s, chosen, axis=-1)
            if norm:
                weight = weight / (weight.sum(axis=-1, keepdims=True) + jnp.asarray(1e-6, dtype))
            weight = weight * jnp.asarray(scale, dtype)
            return s.astype(f32), chosen.astype(jnp.int32), weight.astype(f32)
        return choose

    def logits_bf16(w, prefix, y):
        return (y.astype(low) @ w[prefix + "gate.weight"].astype(low).T)

    def blocks(fn):
        """conv_mix from (B, C, x, taps) -> the mixed rows."""
        return lambda bcx, taps: fn(*jnp.split(bcx, 3, axis=-1), taps)

    def taps_other(K):
        def taps_sum(u, taps):
            if K == 2:
                return plain["taps_sum"](u, taps[..., 1:])
            return plain["taps_sum"](u, jnp.concatenate([taps[..., 2:], taps], axis=-1))
        return taps_sum

    def one_late(u, taps):
        return plain["taps_sum"](jnp.pad(u, ((0, 0), (0, 1), (0, 0)))[:, 1:], taps)

    def taps_bf16(u, taps):
        T, K = u.shape[1], taps.shape[-1]
        out = jnp.zeros(u.shape, low)
        for j in range(K):
            shifted = jnp.pad(u.astype(low), ((0, 0), (K - 1 - j, 0), (0, 0)))[:, :T]
            out = out + taps[:, 0, j].astype(low)[None, None, :] * shifted
        return out.astype(u.dtype)

    def mix_bf16(gate_in, gate_out, x, taps):
        return (gate_out.astype(low) * taps_bf16(gate_in.astype(low) * x.astype(low),
                                                 taps)).astype(x.dtype)

    def norm_whole(q, k_, w, prefix, eps):
        flat = lambda x, name: plain["rms_norm"](
            x.reshape(x.shape[:2] + (-1,)), jnp.tile(w[prefix + name], x.shape[2]),
            eps).reshape(x.shape)
        return flat(q, "q_layernorm.weight"), flat(k_, "k_layernorm.weight")

    def one_gain(q, k_, w, prefix, eps):
        return plain["head_norms"](
            q, k_, {**w, prefix + "k_layernorm.weight": w[prefix + "q_layernorm.weight"]},
            prefix, eps)

    def norm_after_rope(w, prefix, y, cfg):
        B, T, D = y.shape
        H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        theta = float(cfg["rope_theta"])
        q = plain["rope"](ref.linear(y, w[prefix + "q_proj.weight"]).reshape(B, T, H, D // H), theta)
        k_ = plain["rope"](ref.linear(y, w[prefix + "k_proj.weight"]).reshape(B, T, KV, D // H), theta)
        return plain["head_norms"](q, k_, w, prefix, cfg["norm_eps"])

    def shared_added(w, prefix, y, cfg, remat=False):
        out, *rest = plain["experts"](w, prefix, y, cfg, remat)
        first = int(cfg.get("expert_first", 0))
        return (out + plain["mlp"](w, f"{prefix}experts.{first}.", y), *rest)

    held = driver.layers_held(src)
    lead, routed = held[0], next(i for i in held if i >= int(src.get("num_dense_layers", 0)))

    def layer0_routed(w, i, x, cfg, remat=False):
        if i != lead:
            return plain["layer"](w, i, x, cfg, remat)
        # the leading layer's own mixer and norms, the first routed layer's FFN
        alias = dict(w)
        for name in w:
            if name.startswith(f"model.layers.{routed}.feed_forward."):
                alias[name.replace(f"layers.{routed}.", f"layers.{lead}.")] = w[name]
        as_routed = ref._Static({**cfg, "num_dense_layers": 0})
        out = plain["layer"](alias, lead, x, as_routed, remat)[0]
        return out, None, None, None      # no routing row: the counts stay the routed layers'

    def untied(w, cfg):
        return jax.lax.stop_gradient(w["model.embed_tokens.weight"])

    changed = {
        "taps_2": {"taps_sum": taps_other(2)},
        "taps_4": {"taps_sum": taps_other(4)},
        "taps_reversed": {"taps_sum": lambda u, taps: plain["taps_sum"](u, taps[..., ::-1])},
        "one_late": {"taps_sum": one_late},
        "no_gate_before": {"conv_mix": blocks(lambda b, c, x, t: c * ref.taps_sum(x, t))},
        "no_gate_after": {"conv_mix": blocks(lambda b, c, x, t: ref.taps_sum(b * x, t))},
        "c_x_exchanged": {"conv_mix": blocks(lambda b, c, x, t: x * ref.taps_sum(b * c, t))},
        "silu_after_taps": {"taps_sum": lambda u, taps: jax.nn.silu(plain["taps_sum"](u, taps))},
        "qk_norm_whole": {"head_norms": norm_whole},
        "qk_norm_after_rope": {"qk": norm_after_rope},
        "no_qk_norm": {"head_norms": lambda q, k_, w, prefix, eps: (q, k_)},
        "one_gain": {"head_norms": one_gain},
        "softmax_router": {"choose": choose_with(score=lambda z: jax.nn.softmax(z, axis=-1))},
        "bias_weighed": {"choose": choose_with(weigh_bias=True)},
        "no_norm": {"choose": choose_with(norm=False)},
        "top_8": {"choose": choose_with(top=2 * k)},
        "shared_added": {"experts": shared_added},
        "layer0_routed": {"layer": layer0_routed},
        "untied_head": {"head_weight": untied},
        "bf16_taps": {"conv_mix": blocks(mix_bf16)},
        "bf16_router": {"router_logits": logits_bf16, "choose": choose_with(dtype=low)},
        "bf16_softmax": {"softmax_rows": lambda s: jax.nn.softmax(s.astype(low), axis=-1)},
    }
    return {"float32": {}, "bf16": bf16,
            **{name: {**bf16, **fns} for name, fns in changed.items()}}


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up, drop nothing and move no bias by
    construction)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact_loss,
         "route_gap": line["route_gap"], "held_gap": line["held_gap"],
         "counters_add_up": True, "overflow": [0, 0], "bias_grad": 0.0,
         "bias_update_gap": 0.0, "grad_gaps": line["grad_gaps"],
         "weight_gap": line["weight_gap"],
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "mixer_gaps": line.get("mixer_gaps") or {"sconv/y": 0.0}}, traffic)
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
    variant of a mixer alone runs its whole-model pass too."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_lfm2 as ref
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
    gain = float(traffic["mixer_score_gain"])

    def mixer_of(seed):
        drawn = driver.initial_params(model, harness.seed32(seed), bias_std)
        return {which: driver.mixer_inputs(drawn, src, which, harness.seed32(seed),
                                           batch, seq, gain, low) for which in WHICH}

    exact_mixer, band_mixer = {}, {}

    def reference_mixers(dtype):
        return {which: driver.reference_mixer(src, which, dtype) for which in WHICH}

    def record(name, seed, router, mixer, **whole_model):
        line = {"variant": name, "seed": seed, **whole_model,
                "router_gap": max(router.values()), "router_gaps": router,
                "mixer_gap": max(mixer.values()), "mixer_gaps": mixer}
        out.append(judged(line, exact[seed]["loss"], traffic) if "grad_gaps" in line else line)
        harness.emit(phase="band", **out[-1])

    nothing = {"choice": 0.0, "weight": 0.0}
    for name in ["float32"] + [n for n in names if n != "float32"]:
        many = name in ("bf16", "program_router", "program_mixers") or (
            name == "float32" and "bf16" in names)
        if name == "program_router":
            for seed in seeds:
                record(name, seed, router_gaps(
                    driver.program_router(mcfg), inputs(seed), exact_router), {"sconv/y": 0.0})
            continue
        if name == "program_mixers":
            for seed in seeds:
                record(name, seed, nothing, both_mixer_gaps(
                    {which: driver.program_mixer(model, which, seq) for which in WHICH},
                    mixer_of(seed), exact_mixer[seed]))
            continue
        fns = every[name]
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
                        weight_gap=weight_gap(got["expert_weight"], got["expert_tokens"],
                                              base["expert_weight"], base["expert_tokens"]),
                        grad_gap=gaps[worst], grad_gap_leaf=worst,
                        grad_gap_routed=max(g for leaf, g in gaps.items()
                                            if driver.is_routed(leaf)),
                        grad_gap_others=max(g for leaf, g in gaps.items()
                                            if not driver.is_routed(leaf)),
                        grad_gaps=gaps)
                    if name == "bf16":
                        whole_of[seed] = readings
                # a variant that changes a piece of a mixer is read on the
                # mixers alone; the others read there as ``bf16`` does
                if name == "float32":
                    given = mixer_of(seed)
                    exact_mixer[seed] = jax.device_get(
                        {which: mixer_answers(fn, *given[which])
                         for which, fn in reference_mixers(None).items()})
                    del given
                    mixer = {"sconv/y": 0.0}
                elif name == "bf16" or of_mixer or seed not in band_mixer:
                    mixer = both_mixer_gaps(
                        reference_mixers(low), mixer_of(seed), exact_mixer[seed])
                    if not of_mixer:
                        band_mixer[seed] = mixer
                else:
                    mixer = band_mixer[seed]
                # a variant with its own router is read on the router alone
                router = (router_gaps(plain_router, inputs(seed), exact_router)
                          if "choose" in fns else nothing)
                record(name, seed, router, mixer, **readings)
        finally:
            for k, fn in plain.items():
                setattr(ref, k, fn)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+",
                    default=["bf16", "program_router", "program_mixers"] + WRONG + LOWER)
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 and the program's own "
                    "readings (default: all)")
    ap.add_argument("--whole", action="store_true",
                    help="a variant of a mixer alone runs the whole model too")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("lfm2-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, whole=args.whole)
    return 0


if __name__ == "__main__":
    sys.exit(main())
