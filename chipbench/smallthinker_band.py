"""Where ``smallthinker-train``'s ``loss_tol``, ``route_tol``, ``grad_tol``,
``grad_tol_routed``, ``router_tol``, ``mixer_tol``, ``mixer_tol_full`` and
``edge_min`` come from: the reference (``chipbench/reference_smallthinker.py``)
on the cell's own first batch and weights, computed in a lower precision or
with one piece of the mathematics changed, and held against itself in float32
BY THE DRIVER'S OWN CHECKS (``train_steps_prerouter.failed_checks``, the
variant's answers in the program's place): every line carries
``failed_checks`` and ``correct``. Run on the chip by hand when a tolerance is
in question; no cell and no check runs it:

    python chipbench/smallthinker_band.py --seeds 11 12 [--variants bf16 ...]

Variants, each one line per seed (``loss_gap``, ``route_gap``, ``held_gap``,
``grad_gap`` and the leaf it is on, ``router_gap``, ``mixer_gap``,
``edge_gaps``). ``bf16`` is what the configuration states: weights and
activations in bf16; the router, norms, softmaxes and the loss in float32. It
is the band a sound program lies in: the tolerances sit above it and it comes
out ``correct``. Every other variant is ``bf16`` with one change, and must
come out NOT correct. A variant that changes only a piece of the attention is
read on the two mixers ALONE and on the window's edge (cheap; the whole-model
readings it is judged with are ``bf16``'s of that seed) unless ``--whole``:
  program_router       not a variant of the reference: the PROGRAM's router
                       (``moe.gating.topk_select`` with the forms its own
                       configuration gives) on the driver's logits
  program_mixers       likewise the PROGRAM's two mixers (``Transformer._gqa``
                       on the timed route) on the driver's inputs, and the
                       window's edge through the program's window mixer
  router_reads_y2      the router reads the post-attention norm (after attention)
  router_reads_normed  the router reads the NORMED block input
  no_renorm            softmax over all 64, the chosen NOT renormalised
  sigmoid_scores       sigmoid of each logit, the chosen renormalised
  silu_gate            silu in place of relu on the experts' gate
  ungated_relu         an expert is Wd relu(Wu y): no gate
  full_rotated         the full layers rotated like the window layers
  window_unrotated     the window layers rotate nothing
  window_4095          the window layers see 4095 keys (one short)
  window_4097          ... 4097 (one long)
  window_ignored       the window layers see every earlier key
  heads_7x4            query head h reads KV head h % 4 (28 heads grouped
                       4 x 7 read as 7 x 4)
  bf16_router          router logits, softmax and weights in bf16
  bf16_softmax         attention's softmax in bf16 (scores, exponentials and
                       probabilities)
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_prerouter as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_mla import mixer_answers, router_gaps  # noqa: E402
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402

WRONG = ["router_reads_y2", "router_reads_normed", "no_renorm", "sigmoid_scores",
         "silu_gate", "ungated_relu", "full_rotated", "window_unrotated",
         "window_4095", "window_4097", "window_ignored", "heads_7x4"]
LOWER = ["bf16_router", "bf16_softmax"]
# the reference's functions that are pieces of its attention
ATTENTION = {"window_of", "rotated", "kv_head", "softmax_rows"}


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_smallthinker as ref

    f32, low = jnp.float32, jnp.bfloat16
    bf16 = {"loss_parts": functools.partial(ref.loss_parts, dtype=low)}
    plain_window, plain_mlp = ref.window_of, ref.mlp
    k = src["moe_num_active_primary_experts"]
    W = int(src["sliding_window_size"])

    def choose_with(score, norm=True, dtype=f32):
        """top k of ``score(logits)``, the chosen scores renormalised or not."""
        def choose(logits, cfg):
            s = score(logits.astype(dtype))
            weight, chosen = jax.lax.top_k(s, k)
            if norm:
                weight = weight / weight.sum(axis=-1, keepdims=True)
            return (jax.nn.softmax(logits.astype(f32), axis=-1),
                    chosen.astype(jnp.int32), weight.astype(f32))
        return choose

    softmax = lambda z: jax.nn.softmax(z, axis=-1)

    def logits_bf16(w, prefix, r):
        return r.astype(low) @ w[prefix + "primary_router.weight"].astype(low).T

    def window(n):
        return lambda i, cfg: (n if plain_window(i, cfg) else 0)

    def ungated(w, name, y):
        return ref.linear(jax.nn.relu(ref.linear(y, w[name + "up.weight"])),
                          w[name + "down.weight"])

    changed = {
        "router_reads_y2": {"router_reads": lambda x, y, y2: y2},
        "router_reads_normed": {"router_reads": lambda x, y, y2: y},
        "no_renorm": {"choose": choose_with(softmax, norm=False)},
        "sigmoid_scores": {"choose": choose_with(jax.nn.sigmoid)},
        # (``mlp`` again as a NEW function: jax.checkpoint keeps a traced
        # ``mlp`` by the function's identity, and would hand back relu's)
        "silu_gate": {"gate_act": jax.nn.silu,
                      "mlp": lambda w, name, y: plain_mlp(w, name, y)},
        "ungated_relu": {"mlp": ungated},
        "full_rotated": {"rotated": lambda i, cfg: True},
        "window_unrotated": {"rotated": lambda i, cfg: False},
        "window_4095": {"window_of": window(W - 1)},
        "window_4097": {"window_of": window(W + 1)},
        "window_ignored": {"window_of": lambda i, cfg: 0},
        "heads_7x4": {"kv_head": lambda h, H, KV: h % KV},
        "bf16_router": {"router_logits": logits_bf16,
                        "choose": choose_with(softmax, dtype=low)},
        "bf16_softmax": {"softmax_rows": lambda s: jax.nn.softmax(s.astype(low), axis=-1)},
    }
    return {"float32": {}, "bf16": bf16,
            **{name: {**bf16, **fns} for name, fns in changed.items()}}


def judged(line: dict, exact_loss: float, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up and drop nothing by construction; no
    compiled step, so no scope to count)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact_loss,
         "route_gap": line["route_gap"], "held_gap": line["held_gap"],
         "counters_add_up": True, "overflow": [0, 0],
         "grad_gaps": line["grad_gaps"],
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "mixer_gaps": line.get("mixer_gaps") or {"y": 0.0},
         "edge_gaps": line.get("edge_gaps")}, traffic)
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

    from chipbench import reference_smallthinker as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants(src)
    low = jnp.bfloat16
    W = int(src["sliding_window_size"])
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

    exact_mixer, band_mixer, band_edge = {}, {}, {}

    def reference_mixers(dtype):
        return {which: driver.reference_mixer(src, which, dtype) for which in ("swa", "full")}

    def edge_of(mixer, given, seed):
        return driver.edge_gaps(mixer, *given["swa"][:2], W, harness.seed32(seed))

    def record(name, seed, router, mixer, edge, **whole_model):
        line = {"variant": name, "seed": seed, **whole_model,
                "router_gap": max(router.values()), "router_gaps": router,
                "mixer_gap": max(mixer.values()), "mixer_gaps": mixer, "edge_gaps": edge}
        out.append(judged(line, exact[seed]["loss"], traffic) if "grad_gaps" in line else line)
        harness.emit(phase="band", **out[-1])

    nothing = {"choice": 0.0, "weight": 0.0}
    for name in ["float32"] + [n for n in names if n != "float32"]:
        many = name in ("bf16", "program_router", "program_mixers") or (
            name == "float32" and "bf16" in names)
        if name == "program_router":
            for seed in seeds:
                record(name, seed, router_gaps(
                    driver.program_router(mcfg), inputs(seed), exact_router), {"y": 0.0}, None)
            continue
        if name == "program_mixers":
            for seed in seeds:
                mixers = {which: driver.program_mixer(model, which, seq)
                          for which in ("swa", "full")}
                given = mixer_of(seed)
                record(name, seed, nothing,
                       driver.both_mixer_gaps(mixers, given, exact_mixer[seed]),
                       edge_of(mixers["swa"], given, seed))
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
                    mixer, edge = {"y": 0.0}, None
                elif name == "bf16" or of_attention or seed not in band_mixer:
                    given = mixer_of(seed)
                    mixers = reference_mixers(low)
                    mixer = driver.both_mixer_gaps(mixers, given, exact_mixer[seed])
                    edge = edge_of(mixers["swa"], given, seed)
                    del given
                    if not of_attention:
                        band_mixer[seed], band_edge[seed] = mixer, edge
                else:
                    mixer, edge = band_mixer[seed], band_edge[seed]
                # a variant with its own router is read on the router alone
                router = (router_gaps(plain_router, inputs(seed), exact_router)
                          if "choose" in fns else nothing)
                record(name, seed, router, mixer, edge, **readings)
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
    cell = harness.load_cell("smallthinker-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, whole=args.whole)
    return 0


if __name__ == "__main__":
    sys.exit(main())
