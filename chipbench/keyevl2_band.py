"""Where ``keyevl2-train``'s limits come from: the reference
(``chipbench/reference_keyevl2.py``) on the cell's own first batch and weights,
computed in a lower precision or with one piece of the mathematics changed,
and held against itself in float32 BY THE DRIVER'S OWN CHECKS
(``train_steps_dsa.failed_checks``, the variant's answers in the program's
place): every line carries ``failed_checks`` and ``correct``. Run on the chip
by hand when a limit is in question; no cell and no check runs it:

    python chipbench/keyevl2_band.py --seeds 11 12 [--variants bf16 ...] [--alone]

Variants, each one line per seed (``loss_gap``, ``kl_gap``, ``route_gap``,
``held_gap``, ``grad_gap`` and the leaf it is on, the mechanism's readings).
``bf16`` is what the configuration states: weights and activations in bf16;
norms, softmaxes, the indexer's sums, the router and the loss in float32. It
is the band a sound program lies in: the limits sit above it and it comes out
``correct``. Every other variant is ``bf16`` with one change, and must come
out NOT correct. ``--alone`` reads the variants on the mechanism alone
(``train_steps_dsa.mechanism_gaps`` with the variant in the program's place:
cheap; the whole-model readings it is judged with are ``bf16``'s of that seed):
  program              not a variant of the reference: the PROGRAM's own
                       mixer (``Transformer._dsa`` on the timed route) and
                       router on the driver's inputs
  select_unrotated     the indexer's q and k are NOT rotated (the selection
                       taken before the rotation)
  relu_dropped         the indexer's heads are summed without the relu
  weights_left_out     the heads' weights w are left out (a plain sum)
  topk_per_query_2047  one key fewer a query
  target_unnormalised  p is the SUM of the heads' probabilities, not the mean
  input_not_detached   the indexer reads y, not sg(y): its loss reaches the
                       stream
  bf16_threshold       the scores are rounded to bf16 before the k-th largest
                       is taken
  bf16_softmax         the core's softmax in bf16 (the scores and the
                       probabilities rounded to bf16)
  bf16_index           the indexer's dot products and weighted sum in bf16
  bf16_router          the router's logits, probabilities and weights rounded
                       to bf16 (read on the router alone)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_dsa as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_mla import router_gaps  # noqa: E402
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap  # noqa: E402

WRONG = ["select_unrotated", "relu_dropped", "weights_left_out", "topk_per_query_2047",
         "target_unnormalised", "input_not_detached"]
LOWER = ["bf16_threshold", "bf16_softmax", "bf16_index", "bf16_router"]


def variants(src: dict) -> dict:
    """{name: the reference's functions to replace while it is traced}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_keyevl2 as ref

    low = jnp.bfloat16
    plain_rope, plain_choose, plain_inputs = ref.rope, ref.choose_keys, ref.indexer_inputs
    plain_scores = ref.index_scores

    def plain_router(logits, cfg, rounding):
        """``ref.choose`` with its probabilities rounded by ``rounding``."""
        p = rounding(jax.nn.softmax(logits.astype(jnp.float32), axis=-1))
        top, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
        return p, chosen.astype(jnp.int32), rounding(top / jnp.sum(top, axis=-1, keepdims=True))
    topk = int(src["sa_config"]["topk"])

    def unrotated(x, theta, positions, sections=None):
        # (the indexer calls ``rope`` without sections: leave the model's own)
        return x if sections is None else plain_rope(x, theta, positions, sections)

    def attached(w, prefix, y, cfg, positions):
        with driver.swapped(jax.lax, stop_gradient=lambda x: x):
            return plain_inputs(w, prefix, y, cfg, positions)

    def scores_bf16(qi, ki, wi):
        Hi, Di = qi.shape[-2:]
        dots = jnp.einsum("bqhd,bkd->bqhk", qi.astype(low), ki.astype(low))
        weighed = jnp.einsum("bqh,bqhk->bqk", wi.astype(low), ref.indexer_gate(dots))
        return ((Hi ** -0.5) * (Di ** -0.5) * weighed).astype(jnp.float32)

    # (``reduce_precision``: XLA keeps excess precision through a pair of
    # converts, f32 -> bf16 -> f32, and would round nothing)
    rounded = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def softmax_bf16(x, axis=-1):
        return rounded(jax.nn.softmax(rounded(x), axis=axis))

    return {
        "float32": {}, "bf16": {},
        "select_unrotated": {"rope": unrotated},
        "relu_dropped": {"indexer_gate": lambda x: x},
        "weights_left_out": {"index_scores": lambda qi, ki, wi: plain_scores(
            qi, ki, jnp.ones_like(wi))},
        "topk_per_query_2047": {"choose_keys": lambda s, seen, k: plain_choose(
            s, seen, min(k, topk) - 1)},
        "target_unnormalised": {"head_average": lambda p: jnp.sum(p, axis=0)},
        "input_not_detached": {"indexer_inputs": attached},
        "bf16_threshold": {"choose_keys": lambda s, seen, k: plain_choose(rounded(s), seen, k)},
        "bf16_softmax": {"core_softmax": softmax_bf16},
        "bf16_router": {"choose": lambda logits, cfg: plain_router(rounded(logits), cfg, rounded)},
        "bf16_index": {"index_scores": scores_bf16},
    }


def judged(line: dict, exact: dict, traffic: dict, topk: int) -> dict:
    """``line`` with ``failed_checks`` and ``correct``: a variant's answers in
    the program's place, by the driver's own checks (one loss, so nothing to
    fall; the reference's counters add up and drop nothing by construction; no
    compiled step, so no scope to count)."""
    failed = driver.failed_checks(
        {"losses": [line["loss"]], "reference_loss": exact["loss"],
         "kl": (line["kl"], exact["kl"]),
         "route_gap": line["route_gap"], "held_gap": line["held_gap"],
         "counters_add_up": True, "overflow": [0, 0],
         "grad_gaps": line["grad_gaps"],
         "router_gaps": line.get("router_gaps") or {"choice": 0.0},
         "mechanism": line["mechanism"],
         "selected": line.get("selected") or (topk, topk, topk),
         "pair_share": (0.0, 0.0)}, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def measure(cell: dict, seeds, names, rehearsal=None, few=None, alone=False) -> list:
    """One record per (variant, seed): the variant against float32, through
    the driver's own checks. ``few``: every variant but ``bf16`` and the
    program's own readings runs on the first ``few`` seeds only. ``alone``:
    every variant but ``bf16`` is read on the mechanism alone and judged with
    ``bf16``'s whole-model readings of its seed."""
    import jax.numpy as jnp

    from chipbench import reference_keyevl2 as ref
    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq"])
    mcfg = harness.model_config(cell, rehearsal)
    src = driver.source_config(cell, rehearsal)
    model = Transformer(mcfg)
    every = variants(src)
    low = jnp.bfloat16
    topk = min(int(src["sa_config"]["topk"]), seq)
    gain = float(traffic["mixer_score_gain"])
    exact, whole_of, out = {}, {}, []
    plain_choose = ref.choose          # bound now: a variant swaps the module's
    exact_router = lambda logits, _: plain_choose(logits, src)[1:]

    def given(seed):
        drawn = driver.initial_params(model, harness.seed32(seed))
        return driver.mixer_inputs(drawn, src, harness.seed32(seed), batch, seq, gain, low)

    def record(name, seed, **line):
        line = {"variant": name, "seed": seed, **line}
        out.append(judged(line, exact[seed], traffic, topk) if "grad_gaps" in line else line)
        harness.emit(phase="band", **out[-1])

    for name in ["float32"] + [n for n in names if n != "float32"]:
        if name == "program":
            for seed in seeds:
                router = router_gaps(driver.program_router(mcfg), driver.router_inputs(
                    harness.seed32(seed), batch * seq, mcfg.n_experts),
                    driver.reference_router(src))
                record(name, seed, router_gaps=router,
                       mechanism=driver.mechanism_gaps(model, src, *given(seed)))
            continue
        fns = every[name]
        dtype = None if name == "float32" else low
        many = name in ("float32", "bf16")
        program = driver.reference_program(src, dtype)      # traced at its first call
        for seed in (seeds if many else seeds[:few]):
            if alone and not many and seed in whole_of:
                readings = {**whole_of[seed], "whole_model_of": "bf16"}
            else:
                ids = next(batches(mcfg.vocab_size, batch, seq, seed))["input_ids"]
                weights = driver.to_source_names(
                    driver.initial_params(model, harness.seed32(seed)), src)
                with driver.swapped(ref, **fns):
                    got = driver.reference_first_step(program, weights, jnp.asarray(ids))
                del weights
                if name == "float32":
                    exact[seed] = got
                base = exact[seed]
                gaps = grad_gaps(got["grads"] if name == "float32" else got.pop("grads"),
                                 base["grads"])
                worst = max(gaps, key=lambda leaf: gaps[leaf]
                            if gaps[leaf] == gaps[leaf] else float("inf"))
                of = lambda pick: max(g for leaf, g in gaps.items() if pick(leaf))
                readings = dict(
                    loss=got["loss"], kl=got["kl"], loss_gap=abs(got["loss"] - base["loss"]),
                    kl_gap=abs(got["kl"] - base["kl"]) / abs(base["kl"]),
                    route_gap=route_gap(got["expert_tokens"], base["expert_tokens"]),
                    held_gap=route_gap(got["held_rows"], base["held_rows"]),
                    grad_gap=gaps[worst], grad_gap_leaf=worst,
                    grad_gap_routed=of(driver.is_routed), grad_gap_indexer=of(driver.is_indexer),
                    grad_gap_others=of(lambda leaf: not driver.is_routed(leaf)
                                       and not driver.is_indexer(leaf)),
                    grad_gaps=gaps)
                if name == "bf16":
                    whole_of[seed] = readings
            if name == "float32":
                mechanism = {"index": 0.0, "select": 0.0, "leak": 0.0, "y": 0.0}
            else:
                mechanism = driver.mechanism_gaps(None, src, *given(seed), dtype=low,
                                                  variant=fns)
            selected = None
            if "choose_keys" in fns and name.startswith("topk"):
                selected = (topk - 1, topk - 1, topk)
            router = None
            if "choose" in fns:         # a variant with its own router: the router alone
                with driver.swapped(ref, **fns):
                    router = router_gaps(driver.reference_router(src), driver.router_inputs(
                        harness.seed32(seed), batch * seq, mcfg.n_experts), exact_router)
            record(name, seed, mechanism=mechanism, selected=selected, router_gaps=router,
                   **readings)
    return out


def judge_log(path: str, traffic: dict, topk: int) -> list:
    """The ``band`` lines of an earlier run's output, judged again by the
    limits the traffic file has NOW."""
    import json

    lines = [json.loads(x) for x in open(path) if x.startswith('{"phase": "band"')]
    exact = {x["seed"]: x for x in lines if x["variant"] == "float32"}
    out = [judged(x, exact[x["seed"]], traffic, topk) for x in lines
           if "grad_gaps" in x and x["variant"] != "float32"]
    for line in out:
        harness.emit(**{k: v for k, v in line.items() if k != "grad_gaps"})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+", default=["bf16", "program"] + WRONG + LOWER)
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 and the program's own "
                    "readings (default: all)")
    ap.add_argument("--alone", action="store_true",
                    help="read the wrong models and lower precisions on the "
                    "mechanism alone")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's band lines by the traffic "
                    "file's limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell("keyevl2-train")
    if args.judge:
        judge_log(args.judge, cell["traffic"], int(cell["config"]["sa_config"]["topk"]))
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, alone=args.alone)
    return 0


if __name__ == "__main__":
    sys.exit(main())
