"""Training cells of a latent-attention sparse stack (kanana-2 / DeepSeek-V3
shaped: MLA mixers, a leading dense layer, a sigmoid router with a selection
bias, ungated shared experts, one expert-parallel rank's share of the routed
experts): ``train_steps_moe``'s window (``sxt.initialize(...).train_batch`` on
a new seeded batch every step, steps chained on the donated state, two in
flight untraced, one at a time traced) held to the benchmark's own plain
float32 reference of the architecture (``chipbench/reference_kanana2.py``:
whole attention scores a head, a loop over the held experts).

As in ``train_steps_moe`` / ``train_steps_hybrid`` the reference runs FIRST
and alone on the chip, from the same initial weights relaid under the source's
names, one row at a time: the first batch's loss (the cross-entropy plus the
sequence-wise balance loss at the configuration's ``aux_loss_alpha``), the
token-choices every one of the router's experts receives in every ROUTED
layer and what they weigh in all, the rows that fall on the held experts, and
by ``jax.grad`` the gradient, which waits on the host. The trainer's first
gradient is read out of Adam's first moment after one update ((1 - beta1) x
the gradient). ``correct`` = every loss finite, the loss fell (the first
batch's, read once more after the last step: on new ids every step the window's
fall is as small as the difference between two batches), the first loss
within ``loss_tol``, the first step's expert counts over ALL the router's
experts of all routed layers within ``route_tol`` (share of token-choices that
differ), every leaf's gradient within ``grad_tol`` of the reference's norm
(``grad_tol_routed`` for the routed experts' matrices and the routers), the
program's held-row counter equal to its own expert counts summed over the held
range and within ``route_tol`` of the reference's held rows, no row dropped
(``moe_overflow_rows`` 0 in the first and the last step), the selection bias
after the first step equal to the reference's aux-free update
(``bias_update_speed``) of the one before it on the step's own counts (a
buffer: nothing of the optimizer's reaches it), the mean weight of a
token-choice of every expert of every routed layer, from the timed step's own
``moe_expert_weight`` counter, within ``weight_tol`` of the reference's
(``weight_gap``: a bias that is weighed as well as selected on shows there,
in the program's own routed layers), the router's own distance from the
reference's within ``router_tol`` and the mixer's within ``mixer_tol`` (both
below).

The router alone. Through the whole model a weight that is a few percent off
(the selection bias weighed as well as selected on: the chosen scores all sit
near 0.9, so the normalised weights move by b / 0.9) drowns in what bf16 does
to a gradient. So one reading takes the router alone: the function the
program's routed layer calls for its choice and weights
(``moe.gating.topk_select``, with the forms the program's OWN configuration
gives: ``router_forms`` asks ``TransformerConfig``) on seeded float32 logits
of the cell's own shape [tokens, experts] and the seed's bias, against the
reference's ``choose`` on the same numbers: the share of token-choices that
differ and the largest difference of a weight (``router_gaps``). Both sides
are float32 and agree to rounding; a router in bf16, a bias that is weighed, a
missing scale or normalisation, a softmax read 1e-3 to 1 there.

The mixer alone. Through the whole model a token-choice that a rounding flips
moves every gradient by a tenth of its norm, and at the init's scale the
attention scores spread over 0.6, every softmax is nearly flat and no
arithmetic inside it can show. So one reading takes the latent-attention
mixer alone: the function the program's layer calls (``Transformer._mla``:
the projections, the latent's norm, the rotation and the attention route of
the timed step) on the seed's own first routed layer's leaves, the query
projection times ``mixer_score_gain`` (scores spread over several units, as
a trained head's), a seeded normed input and a seeded cotangent of the cell's
own shape in the trainer's compute dtype, against the reference's
``attention`` in float32 on the same numbers: the output, the input's gradient
and the five leaves', each as a share of the reference's norm
(``mixer_gaps``). A softmax in bf16 reads several times the program's distance
there (``chipbench/kanana2_band.py``, variant ``bf16_softmax``); ``mixer_tol``
sits between the two.

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm, the latent's norm) is drawn from [0.5, 1.5) and the
selection bias from a normal of ``select_bias_std`` (traffic file): at their
initial 1 and 0 a model that leaves them out computes the same function.

Traffic parameters: ``train_steps_moe``'s, ``grad_tol_routed``, ``router_tol``,
``weight_tol``, ``mixer_tol``, ``mixer_score_gain`` and ``select_bias_std``. ``chipbench/kanana2_band.py`` measures the band the
tolerances are set from, and runs every wrong model and lower precision through
``failed_checks`` below, in the program's place. Counters derived here from
``engine.last_step_stats()`` (``moe_expert_tokens`` and ``moe_expert_weight``
[routed layers, E], ``moe_held_rows``, ``moe_overflow_rows``): ``moe_expert_load_max_over_mean``,
``moe_dropped_token_share``, ``moe_held_row_share`` (worst routed layer), all
of the window's last step; the fact ``held_rows_per_step`` of the last TRACED
step in a traced run, else of the last step. ``routes.mla_core`` in the
``setup`` line is what the program says it runs (``ops.flash_attention
.attention_route``), not a restatement.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_mla, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap

# the program's leaves under the source's names
_BLOCK = {"ln1_w": "input_layernorm.weight",
          "ln2_w": "post_attention_layernorm.weight",
          "mla_wq": "self_attn.q_proj.weight",
          "mla_wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
          "mla_kv_norm_w": "self_attn.kv_a_layernorm.weight",
          "mla_wkv_b": "self_attn.kv_b_proj.weight",
          "mla_wo": "self_attn.o_proj.weight"}
_DENSE = {"w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
          "w_down": "mlp.down_proj.weight"}
_ROUTED = {"moe_gate": "mlp.gate.weight",
           "moe_select_bias": "mlp.gate.e_score_correction_bias",
           "moe_shared_w_gate": "mlp.shared_experts.gate_proj.weight",
           "moe_shared_w_up": "mlp.shared_experts.up_proj.weight",
           "moe_shared_w_down": "mlp.shared_experts.down_proj.weight"}
_PER_EXPERT = {"moe_w_gate": "gate_proj.weight", "moe_w_up": "up_proj.weight",
               "moe_w_down": "down_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}
GAINS = ("ln1_w", "ln2_w", "mla_kv_norm_w")


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model held here: the leading
    dense layers under ``lead``, the routed ones under ``layers`` (written out
    here so that the mapping does not move with the program)."""
    out = [((leaf,), (), name) for leaf, name in _TOP.items()]
    lead = int(src.get("first_k_dense_replace", 0))
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["n_routed_experts"])
    for i in range(src["num_hidden_layers"]):
        p = f"model.layers.{i}."
        top, at = ("lead", (i,)) if i < lead else ("layers", (i - lead,))
        mine = {**_BLOCK, **(_DENSE if i < lead else _ROUTED)}
        out += [((top, leaf), at, p + theirs) for leaf, theirs in mine.items()]
        if i >= lead:
            out += [((top, leaf), at + (e,), f"{p}mlp.experts.{first + e}.{theirs}")
                    for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


def _relaid(path, x):
    """One tensor between the program's layout and torch's: a matrix is
    [in, out] here and [out, in] there (the embedding [V, D] on both sides).
    Its own inverse."""
    return x.T if x.ndim == 2 and path != ("embed",) else x


def to_source_names(params: dict, src: dict) -> dict:
    """The program's tree -> a flat dict under the source's names, each tensor
    as torch stores it. Stays on the device; float32 as the master is."""
    out = {}
    for path, index, name in source_names(src):
        leaf = params
        for key in path:
            leaf = leaf[key]
        out[name] = _relaid(path, leaf[index])
    return out


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat: {"/".join(path): the program's stacked
    array}. A name ``named`` lacks (a wrong model without that tensor) counts
    as zeros of its neighbours' shape: a gradient that is not there."""
    import jax.numpy as jnp

    cells = {}
    for path, index, name in source_names(src):
        cells.setdefault(path, {})[index] = (
            None if name not in named else _relaid(path, named[name]))
    for at in cells.values():
        some = next((x for x in at.values() if x is not None), None)
        for index, x in at.items():
            if x is None:
                at[index] = jnp.zeros_like(some) if some is not None else jnp.zeros(())

    def stacked(at, depth, prefix=()):
        if depth == 0:
            return at[prefix]
        n = 1 + max(index[len(prefix)] for index in at
                    if index[:len(prefix)] == prefix)
        return jnp.stack([stacked(at, depth - 1, prefix + (i,)) for i in range(n)])

    return {"/".join(path): stacked(at, len(next(iter(at))))
            for path, at in cells.items()}


def reference_program(src: dict):
    """The reference on a whole batch as ONE jitted program that takes one
    row at a time (``lax.map``; each row, and inside it each layer, each
    head's scores and each expert, is computed again in the backward):
    (weights, ids [B, T + 1]) -> loss, expert_tokens [routed layers, E],
    held_rows [routed layers], expert_weight [routed layers, E], d loss / d
    weights in the program's layout."""
    import jax

    from chipbench import reference_kanana2 as ref

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None], remat=True)
            return (parts["loss"], parts["expert_tokens"], parts["held_rows"],
                    parts["expert_weight"])

        ce, tokens, held, weight = jax.lax.map(jax.checkpoint(row), ids)
        return ce.mean(), (tokens.sum(axis=0), held.sum(axis=0), weight.sum(axis=0))

    def first(w, ids):
        (loss, (tokens, held, weight)), grad = jax.value_and_grad(
            batch_loss, has_aux=True)(w, ids)
        # the selection bias is a buffer: no gradient to compare
        return loss, tokens, held, weight, {
            leaf: g for leaf, g in from_source_names(grad, src).items()
            if not leaf.endswith("/moe_select_bias")}

    return jax.jit(first)


def reference_first_step(program, weights: dict, ids) -> dict:
    """``reference_program``'s answer, on the HOST."""
    import jax

    loss, tokens, held, weight, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "expert_tokens": tokens, "held_rows": held,
            "expert_weight": weight, "grads": grads}


def initial_params(model, seed: int, bias_std: float) -> dict:
    """``model.init`` from ``seed`` with the gains and the selection bias
    redrawn (the module's docstring says why)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    draw = lambda x: jax.random.uniform(next(keys), x.shape, jnp.float32, 0.5, 1.5)
    params["ln_f_w"] = draw(params["ln_f_w"])
    for top in ("lead", "layers"):
        leaves = params.get(top, {})
        for name in sorted(leaves):
            if name in GAINS:
                leaves[name] = draw(leaves[name])
            elif name == "moe_select_bias":
                leaves[name] = bias_std * jax.random.normal(
                    next(keys), leaves[name].shape, jnp.float32)
    return params


def router_forms(mcfg) -> dict:
    """What the program's routed layer hands ``topk_select``, asked of its
    configuration (``models/transformer.Transformer._ffn`` passes the same)."""
    return dict(k=mcfg.moe_top_k, normalize_weights=mcfg.moe_norm_topk,
                aux=mcfg.moe_aux, score=mcfg.moe_score,
                weight_scale=mcfg.moe_weight_scale)


def router_inputs(seed: int, tokens: int, experts: int, bias_std: float):
    """(logits [tokens, experts] float32, a standard normal as a random
    router's are over a normed input; bias [experts]) from ``seed``."""
    import jax
    import jax.numpy as jnp

    a, b = jax.random.split(jax.random.PRNGKey(seed + 2))
    return (jax.random.normal(a, (tokens, experts), jnp.float32),
            bias_std * jax.random.normal(b, (experts,), jnp.float32))


def program_router(mcfg):
    """(logits, bias) -> (chosen [N, k], weight [N, k]) as the program routes."""
    from shuffle_exchange_tpu.moe.gating import topk_select

    forms = router_forms(mcfg)

    def router(logits, bias):
        idx, w, *_ = topk_select(
            logits, select_bias=bias if mcfg.moe_select_bias else None, **forms)
        return idx, w

    return router


def reference_router(src: dict):
    """The same of the reference's ``choose`` (looked up when called: the band
    script swaps it)."""
    from chipbench import reference_kanana2 as ref

    def router(logits, bias):
        _, chosen, weight = ref.choose(logits, bias, src)
        return chosen, weight

    return router


def router_gaps(router, inputs, exact) -> dict:
    """{"choice": share of the token-choices on which ``router`` and ``exact``
    (both (logits, bias) -> (chosen, weight)) differ as sets, "weight": the
    largest difference of an expert's weight for a token, over the scale}."""
    import jax
    import jax.numpy as jnp

    E = inputs[0].shape[-1]

    def dense(chosen, weight):
        hot = jax.nn.one_hot(chosen, E, dtype=jnp.float32)
        return hot.sum(axis=1), (hot * weight.astype(jnp.float32)[..., None]).sum(axis=1)

    def gaps(logits, bias):
        (ma, wa), (mb, wb) = dense(*router(logits, bias)), dense(*exact(logits, bias))
        return (jnp.abs(ma - mb).sum() / 2.0 / mb.sum(),
                jnp.abs(wa - wb).max() / jnp.maximum(wb.max(), 1e-30))

    choice, weight = jax.device_get(jax.jit(gaps)(*inputs))
    return {"choice": float(choice), "weight": float(weight)}


def weight_gap(got_weight, got_tokens, want_weight, want_tokens) -> float:
    """How far the PROGRAM's routed layers weigh their choices from the
    reference's, on the first batch, from the timed step's own counters:
    the mean weight of a token-choice of every expert of every routed layer
    (``moe_expert_weight`` / ``moe_expert_tokens``, both [routed layers, E]),
    the difference's norm over the reference's. The counts divide the flips of
    a rounding out; a bias that is weighed as well as selected on moves every
    expert's mean by about b / its score."""
    import numpy as np

    mean = lambda w, n: np.asarray(w, np.float64) / np.maximum(np.asarray(n, np.float64), 1.0)
    want = mean(want_weight, want_tokens)
    return float(np.linalg.norm(mean(got_weight, got_tokens) - want)
                 / max(np.linalg.norm(want), 1e-30))


MIXER_LEAVES = ("mla_wq", "mla_wkv_a", "mla_kv_norm_w", "mla_wkv_b", "mla_wo")


def mixer_inputs(params: dict, seed: int, batch: int, seq: int, mcfg,
                 score_gain: float, dtype):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for the mixer alone, from
    ``seed``: the first routed layer's five latent-attention leaves of the
    seed's weights, the query projection times ``score_gain`` (at the init's
    scale the scores spread over 0.6 and every softmax is nearly flat, so no
    arithmetic inside it can show: a trained head's spread over several
    units), a standard normal x as a normed residual is; leaves and x rounded
    to ``dtype`` as the trainer hands them over, the cotangent float32."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed + 3), 2)
    lw = {name: params["layers"][name][0] for name in MIXER_LEAVES}
    lw["mla_wq"] = lw["mla_wq"] * score_gain
    x = jax.random.normal(keys[0], (batch, seq, mcfg.d_model), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def mixer_answers(mixer, lw, x, cotangent) -> dict:
    """{"y", "dx", "d<leaf>"...} of ``mixer(lw, x)`` [B, T, D] under the
    cotangent, as one jitted program; float32."""
    import jax
    import jax.numpy as jnp

    def both(lw, x, cotangent):
        y, back = jax.vjp(lambda lw, x: mixer(lw, x).astype(jnp.float32), lw, x)
        dlw, dx = back(cotangent)
        return {"y": y, "dx": dx, **{"d" + k: v for k, v in dlw.items()}}

    return jax.tree.map(lambda a: a.astype(jnp.float32), jax.jit(both)(lw, x, cotangent))


def program_mixer(model, seq: int):
    """(leaves, x) -> the program's own latent-attention mixer
    (``Transformer._mla``: projections, norm, rotation and the attention
    route the timed step runs), in the dtype of what it is handed."""
    from shuffle_exchange_tpu.models.transformer import rope_table

    cfg = model.config
    rope = rope_table(seq, cfg.rotary_dims, cfg.rope_theta)
    return lambda lw, x: model._mla(lw, x, rope)


def reference_mixer(src: dict, dtype=None):
    """The same of the reference's ``attention`` (looked up when called: the
    band script swaps its pieces), one row at a time, in float32 at highest
    precision; ``dtype``: in that one instead (the band's lower precisions)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_kanana2 as ref

    def mixer(lw, x):
        named = {"a." + _BLOCK[k]: _relaid((k,), v.astype(jnp.float32))
                 for k, v in lw.items()}
        row = lambda one: ref.attention(
            named, "a.self_attn.", one[None].astype(dtype or jnp.float32), src,
            remat=True)[0]
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(jax.checkpoint(row), x)

    return mixer


def mixer_gaps(mixer, inputs, exact: dict) -> dict:
    """{"y": ..., "dx": ..., "dmla_wq": ...}: ``mixer``'s distance from
    ``exact`` (``mixer_answers`` of ``reference_mixer`` in float32) on
    ``inputs`` (``mixer_inputs``), each as a share of the reference's norm."""
    return grad_gaps(mixer_answers(mixer, *inputs), exact)


def is_routed(leaf: str) -> bool:
    """A routed expert's matrix or a router: the leaves ``grad_tol_routed``
    is for."""
    return "/moe_w_" in leaf or leaf.endswith("/moe_gate")


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``first_loss_again`` (the first batch's loss once more, after the
    last step; None: nothing to fall), ``reference_loss``, ``route_gap`` and ``held_gap`` (None: the
    program handed out no counters), ``counters_add_up``, ``overflow`` [first
    step, last step], ``grad_gaps`` {leaf: share of the reference's norm},
    ``bias_grad`` (the largest entry of the selection bias's first moment:
    0), ``bias_update_gap`` (the largest distance of an entry of the bias
    after the first step from the reference's ``bias_update`` of the one
    before), ``router_gaps`` (``router_gaps`` above), ``weight_gap`` (``weight_gap``
    above; None: no ``moe_expert_weight``), ``mixer_gaps`` (``mixer_gaps``
    above). The band script hands it a wrong model's or a lower precision's
    answers in the program's place."""
    vals = got["losses"]
    loss_tol, route_tol, grad_tol, router_tol, weight_tol, mixer_tol = (
        float(traffic[k]) for k in ("loss_tol", "route_tol", "grad_tol", "router_tol",
                                    "weight_tol", "mixer_tol"))
    routed_tol = float(traffic.get("grad_tol_routed", grad_tol))
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    over = {leaf: gap / (routed_tol if is_routed(leaf) else grad_tol)
            for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))         # a NaN gap is the worst of all
    part = max(got["router_gaps"], key=nan_last(got["router_gaps"]))
    piece = max(got["mixer_gaps"], key=nan_last(got["mixer_gaps"]))
    weighed = got["weight_gap"]
    again = got.get("first_loss_again")
    have = got["route_gap"] is not None
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - got["reference_loss"]) <= loss_tol,
         f"first loss {vals[0]} vs the float32 reference {got['reference_loss']}: "
         f"off by more than {loss_tol}"),
        (again is None or again < vals[0],
         f"loss did not fall: the first batch read {vals[0]} before the run's "
         f"steps and {again} after them"),
        (have, "the program handed out no moe_expert_tokens / moe_held_rows / "
         "moe_overflow_rows"),
        (have and got["route_gap"] <= route_tol,
         f"first step's expert counts differ from the reference's in "
         f"{got['route_gap']} of the token-choices: more than {route_tol}"),
        (have and got["held_gap"] <= route_tol,
         f"first step's held rows differ from the reference's in {got['held_gap']} "
         f"of them: more than {route_tol}"),
        (got["counters_add_up"],
         "the held-row counter and the overflow counter do not add up to the "
         "router's own counts over the held experts, or the router's counts "
         "to tokens x k a routed layer"),
        (over[worst] <= 1.0,
         f"first step's gradient of {worst} differs from the reference's by "
         f"{got['grad_gaps'][worst]:.5f} of its norm: more than "
         f"{routed_tol if is_routed(worst) else grad_tol} (1 = no such "
         f"gradient, or the optimizer's state held no first moment to read it from)"),
        (got["bias_grad"] == 0.0,
         f"a gradient reached the selection bias (largest entry of its first "
         f"moment {got['bias_grad']}): it is a buffer"),
        (got["bias_update_gap"] is not None and got["bias_update_gap"] <= 1e-6,
         f"the selection bias after the first step is {got['bias_update_gap']} "
         f"from the aux-free update of the one before it (bias_update_speed x "
         f"sign(mean load - load) on the step's own counts; the optimizer's "
         f"decay of a buffer reads so too)"),
        (have and got["overflow"] == [0, 0],
         f"held rows dropped (did not fit the buffer): {got['overflow'][0]} in "
         f"the first step, {got['overflow'][1]} in the last"),
        (got["router_gaps"][part] <= router_tol,
         f"the router alone: {part} differs from the reference's by "
         f"{got['router_gaps'][part]:.3g}: more than {router_tol} (a router "
         f"below float32, a bias that is weighed, a missing scale or "
         f"normalisation read so)"),
        (weighed is not None and weighed <= weight_tol,
         f"the routed layers' mean weight of a token-choice, expert by expert, "
         f"differs from the reference's by {weighed} of its norm: more than "
         f"{weight_tol} (None: the program handed out no moe_expert_weight; a "
         f"bias that is weighed as well as selected on reads so)"),
        (got["mixer_gaps"][piece] <= mixer_tol,
         f"the latent-attention mixer alone: {piece} differs from the "
         f"reference's by {got['mixer_gaps'][piece]:.3g} of its norm: more than "
         f"{mixer_tol} (a softmax below float32 reads so)"),
    ]
    return [message for ok, message in checks if not ok]


def run(ctx: dict) -> dict:
    cell = ctx["cell"]
    rehearsal = ctx.get("rehearsal") or {}
    # first: a program that cannot build the configuration says so at once
    mcfg = harness.model_config(cell, rehearsal)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled
    from shuffle_exchange_tpu.ops.flash_attention import attention_route

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_mla holds the whole state on "
                                 f"one chip for its reference; the cell asks for {chips}")
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
    bias_std = float(traffic["select_bias_std"])
    src = source_config(cell, rehearsal)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))
    bf16 = bool(config.get("bf16", {}).get("enabled"))

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chip; the weights are drawn again for
    # the trainer: the same seed, the same weights
    drawn = initial_params(model, seed, bias_std)
    inputs = mixer_inputs(drawn, seed, batch, seq, mcfg,
                          float(traffic["mixer_score_gain"]),
                          jnp.bfloat16 if bf16 else jnp.float32)
    weights = to_source_names(drawn, src)
    del drawn
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    # the router alone, in float32 on both sides
    route_gaps = router_gaps(
        program_router(mcfg),
        router_inputs(seed, batch * seq, mcfg.n_experts, bias_std),
        reference_router(src))
    # the mixer alone, in the trainer's compute dtype against float32
    mix_gaps = mixer_gaps(program_mixer(model, seq), inputs,
                          mixer_answers(reference_mixer(src), *inputs))
    del inputs
    engine = sxt.initialize(model=model, params=initial_params(model, seed, bias_std),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    H = mcfg.n_heads
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    mla_core = attention_route(
        jax.ShapeDtypeStruct((batch, seq, H, mcfg.head_dim), dtype),
        jax.ShapeDtypeStruct((batch, seq, H, mcfg.head_dim), dtype),
        jax.ShapeDtypeStruct((batch, seq, H, mcfg.mla_v_dim), dtype),
        impl=mcfg.attention_impl)

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows",
                 "moe_expert_weight") if k in got}

    bias_of = lambda: np.asarray(engine.state.master["layers"]["moe_select_bias"])
    bias_before = bias_of()
    losses = [engine.train_batch(first)]
    first_stats = stats_now()
    # the buffer after one step: the reference's aux-free update of the bias
    # it had, on the program's own counts (which ``route_tol`` holds to the
    # reference's), and nothing of the optimizer's
    bias_gap = None
    if "moe_expert_tokens" in first_stats:
        from chipbench import reference_kanana2 as ref

        bias_gap = float(np.abs(bias_of() - np.asarray(ref.bias_update(
            bias_before, first_stats["moe_expert_tokens"],
            float(src.get("bias_update_speed") or 0.0)))).max())
    beta1 = settings["train_config"]["optimizer"]["params"].get("betas", (0.9,))[0]
    moment = first_moment(engine.state.opt_state)
    first_gaps = ({leaf: 1.0 for leaf in reference["grads"]} if moment is None
                  else grad_gaps(moment, reference.pop("grads"), 1.0 / (1.0 - beta1)))
    bias_grad = max((float(jnp.abs(m).max()) for leaf, m in (moment or {}).items()
                     if leaf.endswith("/moe_select_bias")), default=0.0)
    del moment
    for _ in range(max(1, int(traffic["warmup_steps"])) - 1):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 routes={"grouped_gemm": "megablox" if pallas_enabled()
                         else "ragged_dot", "mla_core": mla_core},
                 remat=[mcfg.remat, mcfg.remat_policy],
                 reference_loss=reference["loss"], first_loss=float(losses[0]),
                 router_gaps=route_gaps, mixer_gaps=mix_gaps,
                 compiled_step_bytes=step_bytes,
                 peak_memory_in_bytes=peak_bytes, **warm)

    # -- the window (train_steps's) -------------------------------------------
    traced = bool(ctx["trace"])
    trace_steps = int(traffic.get("trace_steps", 4))
    in_window = meter.mark()
    window_losses = []
    tracing, trace_at, traced_steps, traced_stats = False, None, 0, {}
    t0 = time.perf_counter()
    ctx["window_start"](t0)
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx["seconds"]:
            break
        if traced and not tracing and trace_at is None \
                and now - t0 >= ctx["seconds"] / 3:
            jax.block_until_ready(window_losses[-1:] or losses[-1:])
            ctx["start_trace"]()
            tracing, trace_at = True, len(window_losses)
        if traced:
            # a traced run times each step alone; the untraced run below
            # keeps two steps in flight and times the window as a whole
            with spans.span("train_step"):
                loss = engine.train_batch(next(data))
                jax.block_until_ready(loss)
            window_losses.append(loss)
            if tracing:
                traced_steps += 1
                if traced_steps >= trace_steps:
                    ctx["stop_trace"]()
                    tracing = False
                    # the rows the traced kernels had (the router moves on
                    # over a window: the last step's are not theirs)
                    traced_stats = stats_now()
        else:
            window_losses.append(engine.train_batch(next(data)))
            if len(window_losses) >= 2:
                jax.block_until_ready(window_losses[-2])
    jax.block_until_ready(window_losses[-1])
    t1 = time.perf_counter()
    if tracing:
        ctx["stop_trace"]()
    window_s = t1 - t0
    in_win = meter.since(in_window)
    steps = len(window_losses)

    # -- correct, outside the window ------------------------------------------
    vals = [float(x) for x in losses + window_losses]
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    per_layer = batch * seq * mcfg.moe_top_k
    routed_layers = mcfg.routed_layers
    last_stats = stats_now()
    # "the loss fell", read on the SAME ids: at this cell's learning rate a
    # window's steps lower the loss by about 0.01, which is the difference
    # between two batches
    again = float(engine.train_batch(first))
    counted = {"moe_expert_tokens", "moe_held_rows", "moe_overflow_rows"}
    have = counted <= set(first_stats) and counted <= set(last_stats)
    lo = int(src.get("expert_first", 0))
    hi = lo + int(src.get("num_experts_held") or src["n_routed_experts"])
    first_gap = held_gap = load = dropped = held_share = held_rows_step = None
    weighed = None
    overflow, counters_add_up = [None, None], False
    if have:
        first_gap = route_gap(first_stats["moe_expert_tokens"],
                              reference["expert_tokens"])
        held_gap = route_gap(first_stats["moe_held_rows"], reference["held_rows"])
        if "moe_expert_weight" in first_stats:
            weighed = weight_gap(first_stats["moe_expert_weight"],
                                 first_stats["moe_expert_tokens"],
                                 reference["expert_weight"], reference["expert_tokens"])
        # every held token-choice is computed or counted as dropped, and the
        # router's counts come to tokens x k in every ROUTED layer (a dense
        # layer has no row)
        counters_add_up = all(
            s["moe_expert_tokens"].shape[0] == routed_layers
            and np.array_equal(s["moe_held_rows"] + s["moe_overflow_rows"],
                               s["moe_expert_tokens"][:, lo:hi].sum(axis=1))
            and np.array_equal(s["moe_expert_tokens"].sum(axis=1),
                               np.full(routed_layers, per_layer))
            for s in (first_stats, last_stats))
        overflow = [int(s["moe_overflow_rows"].sum()) for s in (first_stats, last_stats)]
        counts = last_stats["moe_expert_tokens"]
        load = float((counts.max(axis=1) / counts.mean(axis=1)).max())
        dropped = 100.0 * overflow[1] / (per_layer * routed_layers)
        held_share = 100.0 * float(last_stats["moe_held_rows"].max()) / per_layer
        held_rows_step = float(traced_stats.get(
            "moe_held_rows", last_stats["moe_held_rows"]).sum())
    failed = failed_checks(
        {"losses": vals, "first_loss_again": again,
         "reference_loss": reference["loss"], "route_gap": first_gap,
         "held_gap": held_gap, "counters_add_up": counters_add_up,
         "overflow": overflow, "grad_gaps": first_gaps, "bias_grad": bias_grad,
         "router_gaps": route_gaps, "weight_gap": weighed, "mixer_gaps": mix_gaps,
         "bias_update_gap": bias_gap},
        traffic)
    worst = max(first_gaps, key=lambda leaf: first_gaps[leaf]
                if first_gaps[leaf] == first_gaps[leaf] else math.inf)
    correct = not failed
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_again=again,
                 first_loss_abs_err=abs(vals[0] - reference["loss"]),
                 first_step_route_gap=first_gap, first_step_held_gap=held_gap,
                 first_step_grad_gap=first_gaps[worst],
                 first_step_grad_gap_leaf=worst,
                 first_step_grad_gap_routed=max(
                     (g for leaf, g in first_gaps.items() if is_routed(leaf)), default=None),
                 first_step_grad_gap_others=max(
                     (g for leaf, g in first_gaps.items() if not is_routed(leaf)), default=None),
                 first_step_grad_gaps=first_gaps, router_gaps=route_gaps,
                 first_step_weight_gap=weighed, mixer_gaps=mix_gaps,
                 first_step_bias_update_gap=bias_gap,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped,
                 moe_held_row_share=held_share, traced_steps=traced_steps,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    counters = {"compiles_in_window": in_win["programs_compiled"],
                "steps": steps}
    if have:
        counters.update(moe_expert_load_max_over_mean=load,
                        moe_dropped_token_share=dropped,
                        moe_held_row_share=held_share)
    return {
        "correct": correct, "attempted": steps,
        "failed": sum(1 for v in vals[len(losses):] if not math.isfinite(v)),
        "end_to_end": {"train_tokens_per_s_chip": per_chip_rate},
        "window_s": window_s, "program_bytes": step_bytes,
        "counters": counters,
        "facts": {"model_cfg": mcfg, "seq": seq, "batch": batch,
                  "chips": chips, "tokens_per_step": batch * seq,
                  "step_s": [b - a for a, b in spans.named("train_step")],
                  "traced_steps": traced_steps,
                  "held_rows_per_step": held_rows_step,
                  "mla_flops_per_token": None if held_rows_step is None else
                  arith_mla.train_flops_per_token(
                      mcfg, seq, held_rows_step / (batch * seq))},
    }
