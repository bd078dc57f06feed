"""Where ``ouro-train``'s ``loss_tol``, ``grad_tol``, ``gate_tol``, ``exit_tol``
and ``pdf_tol`` come from: the reference (``chipbench/reference_ouro.py``) on
the cell's own first batch and weights, computed in a lower precision or with
one piece of the mathematics changed, and held against itself in float32 BY
THE DRIVER'S OWN CHECKS (``train_steps_loop.failed_checks``, the variant's
answers in the program's place): every line carries ``failed_checks`` and
``correct``. The PROGRAM's own readings are the cell's runs' (their ``window``
lines carry the same numbers under the same checks). Run on the chip by hand
when a tolerance is in question; no cell and no check runs it:

    python chipbench/ouro_band.py --seeds 11 12 [--variants bf16 ...] [--few 1]
    python chipbench/ouro_band.py --alone --seeds 11 12 ...   (seconds a seed:
        only the two mechanisms the driver reads ALONE, the exit block and the
        update, in the program's form and in each lower precision's)
    python chipbench/ouro_band.py --judge LOG      (no run: an earlier log's
        lines, band or cell, under the traffic file's limits as they are)

Variants, one line per seed. ``bf16`` is what the configuration states (weights
and activations in bf16; norms, softmaxes, the gate, the exit distribution, its
entropy and the loss in float32): the band a sound program lies in, which must
come out ``correct``. Every other variant is ``bf16`` with one change and must
come out NOT correct:
  three_steps        T = 3: the stack run three times, three exits
  next_unnormed      the next step fed the un-normed stream (the final norm
                     only on what the head and the gate read)
  gate_unnormed      the gate reading the un-normed stream
  last_times_lam     p_T = lam_T x what is left (the mass no longer sums to 1)
  lam_last_used      p_t = lam_t x what is left at EVERY exit, renormalised
  beta_zero          beta 0: no entropy term
  beta_negative      beta's sign turned
  last_exit_only     the last exit's loss alone (no gate in the loss)
  detached_weights   the exit weights detached from the CE term (the gate
                     learns from the entropy alone)
  no_out_norms       the two OUTPUT norms of a block left out (pre-norm)
  bf16_exit          the gate's logit, the exit distribution, its entropy and
                     the weighing formed in bf16
  bf16_master        every weight, gains and gate too, rounded to bf16 before
                     the run (a master kept in bf16)
The whole model's five readings do not tell the last two from ``bf16`` at the
cell's size (8,192 tokens average a rounded distribution out; one step from a
rounded master is one step from other weights a bf16 spacing away). What
refuses them is read ALONE (``alone_readings``; the driver reads the same two of
the program): ``bf16_exit``'s exit block on the driver's seeded streams against
the float32 one, token by token (``alone_tol``), and ``bf16_master``'s second
update, Adam's step added to a master rounded to bf16 and rounded again,
against the step (``update_tol``; ``no_update``: an update that is lost, 1 by
construction). ``float32`` there is the reference's own exit block and a
float32 master under the same step: what a sound program reads.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_steps_loop as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402

CELL = "ouro-train"
WRONG = ["three_steps", "next_unnormed", "gate_unnormed", "last_times_lam", "lam_last_used",
         "beta_zero", "beta_negative", "last_exit_only", "detached_weights", "no_out_norms"]
LOWER = ["bf16_exit", "bf16_master"]


def variants(src: dict) -> dict:
    """{name: (the reference's functions to replace while it is traced, keys
    of the configuration to replace, a function of the weights or None)}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_ouro as ref

    f32, low = jnp.float32, jnp.bfloat16
    beta = float(src.get("exit_entropy_beta", ref.BETA))

    def every_lam(lams, renormalise):
        left, p = jnp.ones_like(lams[0]), []
        for lam in lams:
            p.append(lam * left)
            left = left * (1.0 - lam)
        p = jnp.stack(p)
        return p / p.sum(axis=0) if renormalise else p

    def low_distribution(lams):
        lams = [lam.astype(low) for lam in lams]
        left, p = jnp.ones_like(lams[0]), []
        for lam in lams[:-1]:
            p.append(lam * left)
            left = left * (1 - lam)
        return jnp.stack(p + [left])

    def low_objective(p, ce, beta):
        p = p.astype(low)
        h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1)), 0), axis=0)
        return (jnp.sum(p * ce.astype(low), axis=0) - jnp.asarray(beta, low) * h).astype(f32)

    def low_gate(w, h):
        z = h.astype(low) @ w["model.early_exit_gate.weight"].astype(low)[0]
        return jax.nn.sigmoid(z + w["model.early_exit_gate.bias"].astype(low)[0]).astype(f32)

    rounded = lambda w: {k: v.astype(low).astype(f32) for k, v in w.items()}
    return {
        "bf16": ({}, {}, None),
        "three_steps": ({}, {"total_ut_steps": int(src["total_ut_steps"]) - 1}, None),
        "next_unnormed": ({"next_input": lambda raw, normed: raw}, {}, None),
        "gate_unnormed": ({"gate_reads": lambda raw, normed: raw}, {}, None),
        "last_times_lam": ({"exit_distribution": lambda lams: every_lam(lams, False)}, {}, None),
        "lam_last_used": ({"exit_distribution": lambda lams: every_lam(lams, True)}, {}, None),
        "beta_zero": ({}, {"exit_entropy_beta": 0.0}, None),
        "beta_negative": ({}, {"exit_entropy_beta": -beta}, None),
        "last_exit_only": ({"objective": lambda p, ce, beta: ce[-1]}, {}, None),
        "detached_weights": ({"objective": lambda p, ce, beta: jnp.sum(
            jax.lax.stop_gradient(p) * ce, axis=0) - beta * ref.entropy(p)}, {}, None),
        "no_out_norms": ({"out_norm": lambda x, gain, eps: x}, {}, None),
        "bf16_exit": ({"exit_distribution": low_distribution, "objective": low_objective,
                       "gate": low_gate}, {}, None),
        "bf16_master": ({}, {}, rounded),
    }


def alone_readings(cell: dict, seed: int, rehearsal=None) -> dict:
    """{variant: {"exit_alone_gaps", "update_gaps"}} of the two mechanisms the
    driver reads alone, at the cell's shapes from ``seed``: ``float32`` (the
    reference's exit block; a float32 master), ``bf16_exit``, ``bf16_master``,
    ``no_update``; a variant lacks the reading it does not change. The
    moments are those two seeded gradients leave (Adam's step is the
    gradient's sign at first, whatever its size)."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    src = driver.source_config(cell, rehearsal)
    config = {**cell["config"]["chipbench"]["train_config"], **rehearsal.get("train_config", {})}
    mcfg = harness.model_config(cell, rehearsal)
    low, f32, s32 = jnp.bfloat16, jnp.float32, harness.seed32(seed)
    dtype = low if config.get("bf16", {}).get("enabled") else f32
    inputs = driver.exit_inputs(s32, int(src["total_ut_steps"]), int(traffic["batch_per_chip"]),
                                int(traffic["seq"]), mcfg.d_model, dtype)
    table = variants(src)["bf16_exit"][0]

    def low_block(streams, weight, bias):
        named = {"model.early_exit_gate.weight": weight[None, :],
                 "model.early_exit_gate.bias": bias.reshape(1)}
        p = table["exit_distribution"]([table["gate"](named, h) for h in streams]).astype(low)
        return p, -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1)), 0), axis=0)

    exits = {"float32": driver.exit_alone_gaps(driver.reference_exit_block, inputs),
             "bf16_exit": driver.exit_alone_gaps(low_block, inputs)}
    del inputs

    optimizer = config["optimizer"]["params"]
    betas = tuple(optimizer.get("betas", (0.9, 0.999)))
    lr = driver.warmup_lr(config["scheduler"], float(optimizer["lr"]), 2)
    step = lambda w, mu, nu: driver.adam_step(w, mu, nu, 2, lr, betas, float(
        optimizer.get("eps", 1e-8)), float(optimizer.get("weight_decay", 0.0)))

    @jax.jit
    def masters(w, key):
        g1, g2 = 1e-3 * jax.random.normal(key, (2, *w.shape), f32)
        mu = (1 - betas[0]) * (betas[0] * g1 + g2)
        nu = (1 - betas[1]) * (betas[1] * g1 * g1 + g2 * g2)
        rounded = w.astype(low).astype(f32)
        return mu, nu, w + step(w, mu, nu), rounded, (
            rounded + step(rounded, mu, nu)).astype(low).astype(f32)

    weights = driver.flat_tree(driver.initial_params(Transformer(mcfg), s32))
    keys = jax.random.split(jax.random.PRNGKey(s32 + 3), len(weights))
    moved = {"float32": {}, "bf16_master": {}, "no_update": {}}
    for key, (leaf, w) in zip(keys, sorted(weights.items())):
        mu, nu, wide, rounded, coarse = masters(w, key)
        gap = lambda after, before: driver.update_gaps(
            {leaf: after}, {leaf: before}, ({leaf: mu}, {leaf: nu}), 2, lr, optimizer)
        moved["float32"].update(gap(wide, w))          # (nothing for a leaf too small to read)
        moved["bf16_master"].update(gap(coarse, rounded))
        moved["no_update"].update(gap(w, w))
    return {"float32": {"exit_alone_gaps": exits["float32"], "update_gaps": moved["float32"]},
            "bf16_exit": {"exit_alone_gaps": exits["bf16_exit"]},
            "bf16_master": {"update_gaps": moved["bf16_master"]},
            "no_update": {"update_gaps": moved["no_update"]}}


@contextlib.contextmanager
def patched(changes: dict):
    """The reference's module with ``changes`` in place while a variant is
    traced and run."""
    from chipbench import reference_ouro as ref

    held = {name: getattr(ref, name) for name in changes}
    for name, fn in changes.items():
        setattr(ref, name, fn)
    try:
        yield
    finally:
        for name, fn in held.items():
            setattr(ref, name, fn)


def judged(line: dict, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct`` by ``traffic``'s limits.
    A reading the line does not carry (the whole model's on an ``--alone``
    line; a mechanism alone that the variant does not change) is handed in at
    a gap of 0: not read."""
    ref = line.get("reference") or {"loss": 0.0, "exit_ce": [0.0], "exit_mass": [0.0]}
    got = {"losses": [line.get("loss", ref["loss"])], "reference": ref,
           "grad_gaps": line.get("grad_gaps") or {"not read": 0.0},
           "counters": {"loop_exit_ce": line.get("exit_ce", ref["exit_ce"]),
                        "loop_exit_mass": line.get("exit_mass", ref["exit_mass"]),
                        "loop_layer_visits": 1, "loss_rows": 1},
           "visits_expected": 1, "rows_expected": 1,
           "exit_alone_gaps": line.get("exit_alone_gaps") or {"not read": 0.0},
           "update_gaps": line.get("update_gaps") or {"not read": 0.0}}
    failed = driver.failed_checks(got, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def summary(line: dict) -> dict:
    """The five numbers the limits are set on."""
    ref = line["reference"]
    span = lambda ours, theirs: (max(abs(a - b) for a, b in zip(ours, theirs))
                                 if len(ours) == len(theirs) else math.inf)
    gate = [g for leaf, g in line["grad_gaps"].items() if driver.is_gate(leaf)]
    most = lambda gaps: max(gaps.values()) if gaps else None
    return {"loss_gap": abs(line["loss"] - ref["loss"]),
            "grad_gap": max(g for leaf, g in line["grad_gaps"].items()
                            if not driver.is_gate(leaf)),
            "gate_gap": max(gate) if gate else None,
            "exit_gap": span(line["exit_ce"], ref["exit_ce"]),
            "pdf_gap": span(line["exit_mass"], ref["exit_mass"]),
            "alone_gap": most(line.get("exit_alone_gaps")),
            "update_gap": most(line.get("update_gaps"))}


def judge_log(path: str, traffic: dict) -> list:
    """An earlier log's lines under ``traffic``'s limits as they are now: the
    band's own lines, and a cell's ``window`` lines (the program's readings)."""
    out, setup = [], {}
    for text in open(path):
        text = text.strip()
        if not text.startswith("{"):
            continue
        try:
            line = json.loads(text)
        except ValueError:
            continue
        if line.get("phase") == "setup" and "reference_exit_ce" in line:
            setup = line
        if line.get("phase") == "window" and "first_step_grad_gaps" in line and setup:
            counters = line["first_step_counters"]
            line = {"phase": "band", "variant": "program", "seed": None,
                    "loss": line["first_loss"],
                    "reference": {"loss": setup["reference_loss"],
                                  "exit_ce": setup["reference_exit_ce"],
                                  "exit_mass": setup["reference_exit_mass"]},
                    "grad_gaps": line["first_step_grad_gaps"],
                    "exit_ce": counters["loop_exit_ce"],
                    "exit_mass": counters["loop_exit_mass"],
                    "exit_alone_gaps": line.get("exit_alone_gaps"),
                    "update_gaps": line.get("update_gaps")}
        if line.get("phase") != "band":
            continue
        line = judged(line, traffic)
        harness.emit(phase="judged", variant=line["variant"], seed=line["seed"],
                     correct=line["correct"], **(summary(line) if "loss" in line else {}),
                     failed_checks=[m[:60] for m in line["failed_checks"]])
        out.append(line)
    return out


def measure_alone(cell: dict, seeds, rehearsal=None) -> list:
    """``--alone``: one ``band`` line per seed and variant of
    ``alone_readings``, with those readings only."""
    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    most = lambda gaps: max(gaps.values()) if gaps else None
    out = []
    for seed in seeds:
        for name, read in alone_readings(cell, seed, rehearsal).items():
            line = judged({"phase": "band", "variant": name, "seed": seed, **read}, traffic)
            worst = {"alone_gap": most(read.get("exit_alone_gaps")),
                     "update_gap": most(read.get("update_gaps"))}
            if "update_gaps" in read:
                worst["update_gap_leaf"] = max(read["update_gaps"], key=read["update_gaps"].get)
            harness.emit(**line, **worst)
            out.append(line)
    return out


def measure(cell: dict, seeds, names, rehearsal=None, few=None) -> list:
    """One ``band`` line per variant and seed. ``few``: seeds for every
    variant but ``bf16`` (default: all). ``bf16_exit`` and ``bf16_master``
    carry their mechanism alone beside the whole model's readings."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.models import Transformer

    rehearsal = rehearsal or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    src = driver.source_config(cell, rehearsal)
    mcfg = harness.model_config(cell, rehearsal)
    model = Transformer(mcfg)
    seq, rows = int(traffic["seq"]), int(traffic["batch_per_chip"])
    base = jnp.float32 if rehearsal.get("train_config", {}).get(
        "bf16", {}).get("enabled") is False else jnp.bfloat16
    table = variants(src)
    out = []
    for n, seed in enumerate(seeds):
        s32 = harness.seed32(seed)
        first = next(batches(mcfg.vocab_size, rows, seq, seed))
        ids = jnp.asarray(first["input_ids"])
        weights = jax.jit(lambda: driver.to_source_names(
            driver.initial_params(model, s32), src))()
        exact = driver.reference_first_step(driver.reference_program(src), weights, ids, src)
        grads = exact.pop("grads")
        alone = alone_readings(cell, seed, rehearsal) if set(names) & set(LOWER) else {}
        for name in names:
            if name != "bf16" and few is not None and n >= few:
                continue
            changes, keys, relaid = table[name]
            with patched(changes):
                got = driver.reference_first_step(
                    driver.reference_program({**src, **keys}, base),
                    relaid(weights) if relaid else weights, ids, src)
            line = {"phase": "band", "variant": name, "seed": seed, "loss": got["loss"],
                    "reference": exact, "grad_gaps": driver.host_gaps(got["grads"], grads),
                    "exit_ce": got["exit_ce"], "exit_mass": got["exit_mass"],
                    **alone.get(name, {})}
            line = judged({**line, **summary(line)}, traffic)
            harness.emit(**line)
            out.append(line)
        del weights, exact, grads
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+", default=["bf16", *WRONG, *LOWER])
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 (default: all)")
    ap.add_argument("--alone", action="store_true",
                    help="only the exit block and the update alone (no reference run)")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's lines by the traffic file's "
                    "limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell(CELL)
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    if args.alone:
        measure_alone(cell, args.seeds)
        return 0
    measure(cell, args.seeds, args.variants, few=args.few)
    return 0


if __name__ == "__main__":
    sys.exit(main())
