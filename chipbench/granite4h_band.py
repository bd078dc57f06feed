"""Where ``granite4h-train``'s ``loss_tol``, ``grad_tol``, ``grad_tol_embed``,
``state_tol``, ``decay_tol``, ``stat_tol`` and ``attn_tol`` come from: the
reference (``chipbench/reference_granite4h.py``) on the cell's own first batch
and weights, computed in a lower precision or with one piece of the
mathematics changed, and held against itself in float32 BY THE DRIVER'S OWN
CHECKS (``train_steps_ssm_dense.failed_checks``, the variant's answers in the
program's place): every line carries ``failed_checks`` and ``correct``. The
PROGRAM's own readings are the cell's runs' (their ``window`` lines carry the
same numbers under the same checks). Run on the chip by hand when a tolerance
is in question; no cell and no check runs it:

    python chipbench/granite4h_band.py --seeds 11 12 [--variants bf16 ...] [--few 1]
    python chipbench/granite4h_band.py --judge LOG      (no run: an earlier
        log's lines, band or cell, under the traffic file's limits as they are)

Variants, one line per seed. ``bf16`` is what the configuration states
(weights and activations in bf16; norms, softmaxes, the taps' sum, the scan's
state and decay, the multipliers' products and the loss in float32): the band
a sound program lies in, which must come out ``correct``. Every other variant
is ``bf16`` with one change and must come out NOT correct. Each is read on the
whole model (first loss, every leaf's gradient) AND on the mechanism it
changes alone (the scan, the gated norm, the attention layer), where it has
one; ``--alone`` skips the whole-model pass of those that have:
  attn_scale_1_8     1 / sqrt(head size) = 1/8 for the attention_multiplier 1/64
  residual_1         r = 1: the sublayers' outputs added as they are
  logits_undivided   the logits not divided by logits_scaling
  embed_1            e = 1: the looked-up rows unscaled
  norm_before_gate   the RMSNorm first, then the gate
  eight_groups       the gated norm over 8 runs of 512 channels for ONE of 4096
  bf16_state         the scan's state kept in bf16 between tokens
  bf16_decay         the decay's exponent (step x A) formed in bf16
  bf16_stat          the gated norm's mean of squares formed in bf16
  multipliers_bf16   the multipliers applied in bf16 to values already
                     rounded (``e x``, ``r out``, then the sum) where the
                     reference multiplies and adds in float32
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
from chipbench.drivers import train_steps_ssm_dense as driver  # noqa: E402
from chipbench.drivers.train_steps import batches  # noqa: E402
from chipbench.drivers.train_steps_mla import mixer_answers  # noqa: E402
from chipbench.drivers.train_steps_ssm import scan_gaps_of  # noqa: E402

CELL = "granite4h-train"
WRONG = ["attn_scale_1_8", "residual_1", "logits_undivided", "embed_1",
         "norm_before_gate", "eight_groups"]
LOWER = ["bf16_state", "bf16_decay", "bf16_stat", "multipliers_bf16"]


def variants(src: dict) -> dict:
    """{name: (the reference's functions to replace while it is traced, keys
    of the configuration to replace, the mechanism it changes alone or None)}."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_granite4h as ref

    f32, low = jnp.float32, jnp.bfloat16
    neutral = 1.0 / math.sqrt(ref.head_dim(src))

    def norm_before_gate(o, z, gain, groups, eps):
        B, T, inner = o.shape
        g = o.astype(f32).reshape(B, T, groups, inner // groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return (g.reshape(B, T, inner) * gain.astype(f32)
                * jax.nn.silu(z.astype(f32))).astype(o.dtype)

    def decay_low(dt, A):
        return jnp.exp((dt.astype(low) * A.astype(low)).astype(f32))

    def scaled_low(x, m):
        return x * jnp.asarray(m, x.dtype)

    def added_low(h, out, r):
        return h + out * jnp.asarray(r, out.dtype)

    return {
        "bf16": ({}, {}, None),
        "attn_scale_1_8": ({}, {"attention_multiplier": neutral}, "attn"),
        "residual_1": ({}, {"residual_multiplier": 1.0}, None),
        "logits_undivided": ({}, {"logits_scaling": 1.0}, None),
        "embed_1": ({}, {"embedding_multiplier": 1.0}, None),
        "norm_before_gate": ({"gated_norm": norm_before_gate}, {}, "norm"),
        "eight_groups": ({"norm_groups": lambda cfg: 8}, {}, "norm"),
        "bf16_state": ({"state_dtype": lambda: low}, {}, "scan"),
        "bf16_decay": ({"decay_of": decay_low}, {}, "scan"),
        "bf16_stat": ({"stat_dtype": lambda: low}, {}, "norm"),
        "multipliers_bf16": ({"scaled": scaled_low, "added": added_low}, {}, None),
    }


@contextlib.contextmanager
def patched(changes: dict):
    """The reference's module with ``changes`` in place while a variant is
    traced and run."""
    from chipbench import reference_granite4h as ref

    held = {name: getattr(ref, name) for name in changes}
    for name, fn in changes.items():
        setattr(ref, name, fn)
    try:
        yield
    finally:
        for name, fn in held.items():
            setattr(ref, name, fn)


def reference_gate_norm(src: dict):
    """The reference's epilogue as the driver's ``stat_gap`` takes it: (o, x,
    z, D, gain) -> the skip in float32, then ``gated_norm`` (looked up when
    called) over ``norm_groups`` runs."""
    import jax.numpy as jnp

    from chipbench import reference_granite4h as ref

    def norm(o, x, z, D, gain):
        f32 = jnp.float32
        u = o.astype(f32) + jnp.repeat(D, o.shape[-1] // D.shape[0]) * x.astype(f32)
        return ref.gated_norm(u, z, gain, ref.norm_groups(src), ref.eps_of(src)).astype(o.dtype)

    return norm


def judged(line: dict, traffic: dict) -> dict:
    """``line`` with ``failed_checks`` and ``correct`` by ``traffic``'s limits."""
    got = {"losses": [line["loss"]], "reference_loss": line["reference_loss"],
           "grad_gaps": line["grad_gaps"], "scan_gaps": line["scan_gaps"],
           "stat_gap": line["stat_gap"], "attn_gaps": line["attn_gaps"],
           "scan_chunks": 1, "scan_chunks_expected": 1,
           "routes": {"ssd": "xla", "ssm_conv": "xla", "ssm_gate_norm": "xla",
                      "ssd_alone": "xla", "step_kernels": None}}
    failed = driver.failed_checks(got, traffic)
    return {**line, "failed_checks": failed, "correct": not failed}


def judge_log(path: str, traffic: dict) -> list:
    """An earlier log's lines under ``traffic``'s limits as they are now: the
    band's own lines, and a cell's ``window`` lines (the program's readings)."""
    out = []
    for text in open(path):
        text = text.strip()
        if not text.startswith("{"):
            continue
        try:
            line = json.loads(text)
        except ValueError:
            continue
        if line.get("phase") == "window" and "first_step_grad_gaps" in line:
            line = {"phase": "band", "variant": "program", "seed": None,
                    "loss": line["first_loss"],
                    "reference_loss": line["first_loss"] - math.copysign(
                        line["first_loss_abs_err"], 1.0),
                    "loss_gap": line["first_loss_abs_err"],
                    "grad_gaps": line["first_step_grad_gaps"], "scan_gaps": line["scan_gaps"],
                    "stat_gap": line["stat_gap"], "attn_gaps": line["attn_gaps"]}
        if line.get("phase") != "band":
            continue
        line = judged(line, traffic)
        worst = lambda gaps: max(gaps.values(), default=0.0)
        harness.emit(phase="judged", variant=line["variant"], seed=line["seed"],
                     correct=line["correct"], loss_gap=line["loss_gap"],
                     grad_gap=worst({k: v for k, v in line["grad_gaps"].items() if k != "embed"}),
                     embed_gap=line["grad_gaps"].get("embed"),
                     scan_gap=worst({k: v for k, v in line["scan_gaps"].items()
                                     if k.startswith("scan/")}),
                     scan32_gap=worst({k: v for k, v in line["scan_gaps"].items()
                                       if k.startswith("scan32/")}),
                     stat_gap=line["stat_gap"], attn_gap=worst(line["attn_gaps"]),
                     failed_checks=[m[:60] for m in line["failed_checks"]])
        out.append(line)
    return out


def measure(cell: dict, seeds, names, rehearsal=None, few=None, alone=False) -> list:
    """One ``band`` line per variant and seed. ``few``: seeds for every
    variant but ``bf16`` (default: all). ``alone``: a variant that changes a
    mechanism is read on that mechanism alone, its whole-model readings
    ``bf16``'s of that seed."""
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
        # the mechanisms' inputs and their float32 answers, once a seed
        params = driver.initial_params(model, s32)
        x, cot = driver.mixer_input(s32, rows, seq, mcfg.d_model, base)
        scan_args, scan_cot = driver.scan_inputs(
            driver.first_leaves(params, src, "ssm", base), x, src, s32, base)
        scan_exact = driver.exact_scan_answers(scan_args, scan_cot)
        norm_in = driver.norm_inputs(s32, seq, mcfg, base)
        attn_lw = driver.first_leaves(params, src, "attn", base)
        attn_exact = mixer_answers(driver.reference_attention(src), attn_lw, x, cot)
        del params
        whole = {}

        def readings(name):
            changes, keys, mechanism = table[name]
            cfg = {**src, **keys}
            with patched(changes):
                line = {
                    "scan_gaps": scan_gaps_of(driver.reference_scan(), scan_args, scan_cot,
                                              scan_exact),
                    "stat_gap": driver.stat_gap(reference_gate_norm(cfg), norm_in,
                                                mcfg.ssm_groups, mcfg.norm_eps),
                    "attn_gaps": driver.attention_gaps(
                        driver.reference_attention(cfg, base), attn_lw, x, cot, attn_exact)}
                if alone and mechanism and "bf16" in whole:
                    line.update(whole["bf16"], whole_model_of="bf16")
                else:
                    got = driver.reference_first_step(
                        driver.reference_program(cfg, base), weights, ids, src)
                    gaps = driver.host_gaps(got["grads"], exact["grads"])
                    line.update(loss=got["loss"], grad_gaps=gaps)
                    whole[name] = {"loss": got["loss"], "grad_gaps": gaps}
            return line

        for name in names:
            if name != "bf16" and few is not None and n >= few:
                continue
            line = readings(name)
            nan_last = lambda gaps: lambda k: gaps[k] if gaps[k] == gaps[k] else math.inf
            worst = max(line["grad_gaps"], key=nan_last(line["grad_gaps"]))
            line = judged({"phase": "band", "variant": name, "seed": seed,
                           "mechanism": table[name][2], "reference_loss": exact["loss"],
                           "loss_gap": abs(line["loss"] - exact["loss"]),
                           "grad_gap": line["grad_gaps"][worst], "grad_gap_leaf": worst,
                           **line}, traffic)
            harness.emit(**line)
            out.append(line)
        del weights, exact
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--variants", nargs="+", default=["bf16", *WRONG, *LOWER])
    ap.add_argument("--few", type=int, default=None,
                    help="seeds for every variant but bf16 (default: all)")
    ap.add_argument("--alone", action="store_true",
                    help="a variant that changes a mechanism is read on it alone")
    ap.add_argument("--judge", metavar="LOG", default=None,
                    help="judge an earlier run's lines by the traffic file's "
                    "limits as they are now, and run nothing")
    args = ap.parse_args(argv)
    cell = harness.load_cell(CELL)
    if args.judge:
        judge_log(args.judge, cell["traffic"])
        return 0
    harness.cache_programs()
    measure(cell, args.seeds, args.variants, few=args.few, alone=args.alone)
    return 0


if __name__ == "__main__":
    sys.exit(main())
