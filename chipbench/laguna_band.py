"""Where ``laguna-train``'s ``loss_tol``, ``route_tol``, ``grad_tol``,
``grad_tol_routed``, ``router_tol`` and ``mixer_tol`` come from: the reference
(``chipbench/reference_laguna.py``) on the cell's own first batch and weights,
computed in a lower precision or with one piece of the mathematics changed, and
held against itself in float32 BY THE DRIVER'S OWN CHECKS
(``train_steps_swa.failed_checks``, the variant's answers in the program's
place): every line carries ``failed_checks`` and ``correct``. Run on the chip
by hand when a tolerance is in question; no cell and no check runs it:

    python chipbench/laguna_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``held_gap``,
``grad_gap`` and the leaf it is on, ``router_gap``, ``mixer_gap``). ``bf16`` is
what the configuration states: weights and activations in bf16; the router,
norms, softmaxes and the loss in float32. It is the band a sound program lies
in: the tolerances sit above it and it comes out ``correct``. Every other
variant is ``bf16`` with one change, and must come out NOT correct. A variant
that changes only a piece of the attention is read on the two mixers ALONE
(cheap; the whole-model readings it is judged with are ``bf16``'s of that
seed) unless ``--whole`` asks for its whole-model pass too:
  program_router     not a variant of the reference: the PROGRAM's router
                     (``moe.gating.topk_select`` with the forms its own
                     configuration gives) on the driver's logits, the reading
                     ``router_tol`` has to pass (``router_gap`` only)
  program_mixers     likewise the PROGRAM's two mixers (``Transformer._gqa``
                     on the timed route) on the driver's inputs, the reading
                     ``mixer_tol`` has to pass (``mixer_gap`` only)
  window_511         the window layers see 511 keys (one short)
  window_513         ... 513 (one long)
  window_ignored     the window layers see every earlier key
  swa_table_on_full  the window layers' RoPE (theta 10,000, all dims, plain)
                     on the full layers
  full_table_on_swa  the full layers' half-rotary YaRN table on the window layers
  yarn_no_factor     YaRN's frequencies without the 1.41589 on cos and sin
  yarn_all_dims      all 128 dims of a full layer's head rotated (YaRN over 128)
  groups_of_8_on_full  a full layer's 48 query heads read the KV heads in the
                     window layers' groups of 8 (48 heads' projections read as
                     64's: heads 0-7 share KV head 0, ... KV heads 6-7 unread)
  softmax_router     softmax over the experts for the sigmoid of each
  no_scale           weights not multiplied by 2.5
  no_norm            weights not normalised over the chosen
  shared_gated       the shared expert through a sigmoid gate (of a
                     zero-initialised projection, as the other families': 0.5)
  layer0_routed      the leading dense layer computed as a routed one (with
                     layer 1's router, experts and shared expert)
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
from chipbench.drivers import train_steps_swa as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_mla import mixer_answers, router_gaps  # noqa: E402
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402

WRONG = ["window_511", "window_513", "window_ignored", "swa_table_on_full",
         "full_table_on_swa", "yarn_no_factor", "yarn_all_dims",
         "groups_of_8_on_full", "softmax_router", "no_scale", "no_norm",
         "shared_gated", "layer0_routed"]
LOWER = ["bf16_router", "bf16_softmax"]
# the reference's functions that are pieces of its attention
ATTENTION = {"window_of", "rope_parameters", "kv_head", "softmax_rows"}


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_laguna as ref

    f32, low = jnp.float32, jnp.bfloat16
    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=low)}
    plain = {k: getattr(ref, k) for k in ("shared", "layer", "window_of",
                                          "rope_parameters", "kv_head")}
    k = src["num_experts_per_tok"]
    scale = float(src.get("moe_routed_scaling_factor", 1.0))
    W = int(src["sliding_window"])

    def choose_with(score=jax.nn.sigmoid, norm=True, times=scale, dtype=f32):
        def choose(logits, cfg):
            s = score(logits.astype(dtype))
            _, chosen = jax.lax.top_k(s, k)
            weight = jnp.take_along_axis(s, chosen, axis=-1)
            if norm:
                weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
            weight = weight * jnp.asarray(times, dtype)
            return s.astype(f32), chosen.astype(jnp.int32), weight.astype(f32)
        return choose

    def logits_bf16(w, prefix, y):
        return (y.astype(low) @ w[prefix + "gate.weight"].astype(low).T)

    def window(n):
        return lambda i, cfg: (n if plain["window_of"](i, cfg) else 0)

    def tables(**by_kind):
        """rope_parameters with a kind's entry replaced."""
        def rope_parameters(cfg, kind):
            swap = by_kind.get(kind)
            if callable(swap):
                return swap(plain["rope_parameters"](cfg, kind))
            return plain["rope_parameters"](cfg, swap or kind)
        return rope_parameters

    def groups_of_8(h, H, KV):
        return h // 8 if H // KV != 8 else plain["kv_head"](h, H, KV)

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
        routed = ref._Static({**cfg, "mlp_layer_types": ["sparse"] * len(cfg["mlp_layer_types"])})
        out = plain["layer"](alias, 0, x, routed, remat)[0]
        return out, None, None, None      # no routing row: the counts stay 4 layers'

    changed = {
        "window_511": {"window_of": window(W - 1)},
        "window_513": {"window_of": window(W + 1)},
        "window_ignored": {"window_of": lambda i, cfg: 0},
        "swa_table_on_full": {"rope_parameters": tables(full_attention="sliding_attention")},
        "full_table_on_swa": {"rope_parameters": tables(sliding_attention="full_attention")},
        "yarn_no_factor": {"rope_parameters": tables(
            full_attention=lambda rp: {**rp, "attention_factor": 1.0})},
        "yarn_all_dims": {"rope_parameters": tables(
            full_attention=lambda rp: {**rp, "partial_rotary_factor": 1.0})},
        "groups_of_8_on_full": {"kv_head": groups_of_8},
        "softmax_router": {"choose": choose_with(score=lambda z: jax.nn.softmax(z, axis=-1))},
        "no_scale": {"choose": choose_with(times=1.0)},
        "no_norm": {"choose": choose_with(norm=False)},
        "shared_gated": {"shared": shared_gated},
        "layer0_routed": {"layer": layer0_routed},
        "bf16_router": {"router_logits": logits_bf16, "choose": choose_with(dtype=low)},
        "bf16_softmax": {"softmax_rows": lambda s: jax.nn.softmax(s.astype(low), axis=-1)},
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
         "counters_add_up": True, "overflow": [0, 0],
         "grad_gaps": line["grad_gaps"],
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "mixer_gaps": line.get("mixer_gaps") or {"y": 0.0}}, traffic)
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
    variant of the attention alone runs its whole-model pass too."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_laguna as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants(src)
    low = jnp.bfloat16
    exact, whole_of, out = {}, {}, []
    plain_router = driver.reference_router(src)
    plain_choose = ref.choose          # bound now: a variant swaps the module's
    exact_router = lambda logits, _: plain_choose(logits, src)[1:]
    inputs = lambda seed: driver.router_inputs(
        harness.seed32(seed), batch * seq, mcfg.n_experts)
    gain = float(traffic["mixer_score_gain"])

    def mixer_of(seed):
        drawn = driver.initial_params(model, harness.seed32(seed))
        return {which: driver.mixer_inputs(drawn, src, which, harness.seed32(seed),
                                           batch, seq, gain, low)
                for which in ("swa", "full")}

    exact_mixer, band_mixer = {}, {}

    def reference_mixers(dtype):
        return {which: driver.reference_mixer(src, which, dtype) for which in ("swa", "full")}

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
                    driver.program_router(mcfg), inputs(seed), exact_router), {"y": 0.0})
            continue
        if name == "program_mixers":
            for seed in seeds:
                record(name, seed, nothing, driver.both_mixer_gaps(
                    {which: driver.program_mixer(model, which, seq)
                     for which in ("swa", "full")}, mixer_of(seed), exact_mixer[seed]))
            continue
        fns = every[name]
        plain = {k: getattr(ref, k) for k in fns}
        for k, fn in fns.items():
            setattr(ref, k, fn)
        try:
            of_attention = bool(ATTENTION & set(fns))
            program = driver.reference_program(src)
            for seed in (seeds if many else seeds[:few]):
                # the whole model first, on a device that holds nothing else
                # (its program reserves one block at the bottom of memory)
                if of_attention and not whole and seed in whole_of:
                    readings = {**whole_of[seed], "whole_model_of": "bf16"}
                else:
                    ids = next(batches(mcfg.vocab_size, batch, seq, seed))["input_ids"]
                    weights = driver.to_source_names(
                        driver.initial_params(model, harness.seed32(seed)), src)
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
                        grad_gap=gaps[worst], grad_gap_leaf=worst,
                        grad_gap_routed=max(g for leaf, g in gaps.items()
                                            if driver.is_routed(leaf)),
                        grad_gap_others=max(g for leaf, g in gaps.items()
                                            if not driver.is_routed(leaf)),
                        grad_gaps=gaps)
                    if name == "bf16":
                        whole_of[seed] = readings
                # a variant that changes a piece of the attention is read on
                # the mixers alone; the others read there as ``bf16`` does
                if name == "float32":
                    given = mixer_of(seed)
                    exact_mixer[seed] = jax.device_get(
                        {which: mixer_answers(fn, *given[which])
                         for which, fn in reference_mixers(None).items()})
                    del given
                    mixer = {"y": 0.0}
                elif name == "bf16" or of_attention or seed not in band_mixer:
                    mixer = driver.both_mixer_gaps(
                        reference_mixers(low), mixer_of(seed), exact_mixer[seed])
                    if not of_attention:
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
                    help="a variant of the attention alone runs the whole model too")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("laguna-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, whole=args.whole)
    return 0


if __name__ == "__main__":
    sys.exit(main())
