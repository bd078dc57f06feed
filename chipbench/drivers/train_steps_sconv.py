"""Training cells of a short-convolution / attention hybrid sparse stack (LFM2
shaped: ``conv`` layers, a gated causal depthwise convolution between two
projections, beside ``full_attention`` layers with a per-head q/k RMSNorm
before RoPE; leading dense layers; a sigmoid router with a selection bias, no
shared expert, one expert-parallel rank's share of the routed experts; a tied
head): ``train_steps_swa``'s window (``sxt.initialize(...).train_batch`` on a
new seeded batch every step, steps chained on the donated state, two in flight
untraced, one at a time traced) held to the benchmark's own plain float32
reference of the architecture (``chipbench/reference_lfm2.py``).

As in the other sparse drivers the reference runs FIRST and alone on the chip,
from the same initial weights relaid under the source's names, one row at a
time: the first batch's loss, the token-choices every one of the router's
experts receives in every ROUTED layer with their summed weights, the rows
that fall on the held experts, and by ``jax.grad`` the gradient, which waits on
the host. The trainer's first gradient is read out of Adam's first moment after
one update. ``correct`` = ``train_steps_mla``'s list (finite losses, the first
batch's loss fell, first loss within ``loss_tol``, expert counts and held rows
within ``route_tol``, every leaf's gradient within ``grad_tol`` /
``grad_tol_routed`` / ``grad_tol_embed``, the counters add up, nothing dropped, no gradient on the
selection bias and the buffer after one step the aux-free update of the one
before, the timed step's own weights within ``weight_tol``, the router alone
within ``router_tol``) and, below, each new mixer ALONE.

The mixers alone. Through the whole model a convolution with its taps in
reverse order or a q/k norm after the rotation moves every gradient by less
than what one flipped token-choice does. So two readings take the new
mechanisms alone: the functions the program's layers call
(``Transformer._sconv``: both projections and the pass between them on the
route the timed step runs; ``Transformer._gqa`` as mixer "attn" with its
per-head norms, rotation and attention route) on the seed's first routed conv
layer's and its attention layer's leaves, a seeded normed input and a seeded
cotangent of the cell's own shape in the trainer's compute dtype, against the
reference's ``short_conv`` / ``attention`` in float32 on the same numbers: the
output, the input's gradient and every leaf's, each as a share of the
reference's norm (``mixer_gaps``, keys ``sconv/...`` within ``mixer_tol`` and
``attn/...`` within ``mixer_tol_attn``). The attention reading multiplies the
query norm's gain by ``mixer_score_gain``: the per-head norms fix the scores'
spread at about one unit whatever the projections' scale, a trained head's is
several.

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm, the q/k norms) is drawn from [0.5, 1.5) and the selection
bias from a normal of ``select_bias_std``: at their initial 1 and 0 a model
that leaves them out computes the same function.

Traffic parameters: ``train_steps_mla``'s, ``mixer_tol_attn`` beside
``mixer_tol``, ``mixer_tol_attn_gain`` for the two q/k gains' gradients (64
numbers each: 0.010-0.017 of their norm by the seed where the matrices' read
0.012-0.014; a wrong q/k norm reads 0.67 and more there) and
``grad_tol_embed`` beside ``grad_tol`` (the tied embedding's
gradient alone: an untied head's lacks the head's part and reads 0.29 of its
norm where the program reads 0.20, inside the other leaves' band). ``chipbench/lfm2_band.py`` measures the band the tolerances are
set from, and runs every wrong model and lower precision through
``failed_checks`` below, in the program's place. ``routes`` in the ``setup``
line is what the program says it runs (``ops.short_conv.sconv_route``,
``ops.flash_attention.attention_route``), not a restatement; the fact
``sconv_route`` carries the first to the roofline's reducer.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_sconv, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_mla import (is_routed, mixer_answers, program_router,
                                               router_gaps, router_inputs, weight_gap)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap
from chipbench.drivers.train_steps_swa import both_mixer_gaps

# the program's leaves under the source's names
_NORMS = {"ln1_w": "operator_norm.weight", "ln2_w": "ffn_norm.weight"}
_MIXER = {"sconv": {"sconv_w_in": "conv.in_proj.weight", "sconv_w": "conv.conv.weight",
                    "sconv_w_out": "conv.out_proj.weight"},
          "attn": {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
                   "wv": "self_attn.v_proj.weight", "wo": "self_attn.out_proj.weight",
                   "q_norm_w": "self_attn.q_layernorm.weight",
                   "k_norm_w": "self_attn.k_layernorm.weight"}}
_DENSE = {"w_gate": "feed_forward.w1.weight", "w_up": "feed_forward.w3.weight",
          "w_down": "feed_forward.w2.weight"}
_ROUTED = {"moe_gate": "feed_forward.gate.weight",
           "moe_select_bias": "feed_forward.expert_bias"}
_PER_EXPERT = {"moe_w_gate": "w1.weight", "moe_w_up": "w3.weight", "moe_w_down": "w2.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.embedding_norm.weight"}
GAINS = ("ln1_w", "ln2_w", "q_norm_w", "k_norm_w")


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def layers_held(src: dict) -> list:
    """The model's own indices of the layers held here."""
    return [int(i) for i in src.get("layers_held") or range(src["num_hidden_layers"])]


def layer_kinds(src: dict) -> list:
    """[(mixer, ffn)] of the layers held here."""
    dense = int(src.get("num_dense_layers", 0))
    return [("sconv" if src["layer_types"][i] == "conv" else "attn",
             "mlp" if i < dense else "moe") for i in layers_held(src)]


def layer_places(src: dict) -> list:
    """[(top, kind's name or None, index into that kind's stacked leaves)] a
    layer held here: the leading dense layers under ``lead``, the others under
    ``layers/<mixer>_<ffn>`` at [period, index among the kind's layers of the
    period] (written out here so that the mapping does not move with the
    program)."""
    kinds = layer_kinds(src)
    lead = next(i for i, (_, ffn) in enumerate(kinds) if ffn != "mlp")
    rest = kinds[lead:]
    period = next(p for p in range(1, len(rest) + 1) if len(rest) % p == 0
                  and rest[:p] * (len(rest) // p) == rest)
    out = [("lead", None, (i,)) for i in range(lead)]
    several = len(set(rest[:period])) > 1
    for j, kind in enumerate(rest):
        at = sum(1 for k in rest[j - j % period:j] if k == kind)
        out.append(("layers", "_".join(kind), (j // period, at)) if several
                   else ("layers", None, (j,)))
    return out


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model held here; a layer's name
    carries the model's OWN index."""
    tops = dict(_TOP, **({} if src.get("tie_word_embeddings", True)
                         else {"unembed": "lm_head.weight"}))
    out = [((leaf,), (), name) for leaf, name in tops.items()]
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["num_experts"])
    for i, (mixer, ffn), (top, kind, at) in zip(layers_held(src), layer_kinds(src),
                                                layer_places(src)):
        p = f"model.layers.{i}."
        path = (top,) if kind is None else (top, kind)
        mine = {**_NORMS, **_MIXER[mixer], **(_DENSE if ffn == "mlp" else _ROUTED)}
        out += [(path + (leaf,), at, p + theirs) for leaf, theirs in mine.items()]
        if ffn == "moe":
            out += [(path + (leaf,), at + (e,),
                     f"{p}feed_forward.experts.{first + e}.{theirs}")
                    for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


def _relaid(path, x):
    """One tensor between the program's layout and torch's: a matrix is
    [in, out] here and [out, in] there (the embedding [V, D] on both sides);
    the taps are [K, D] here and [D, 1, K] there."""
    if path[-1] == "sconv_w":
        return x.T[:, None, :] if x.ndim == 2 else x[:, 0, :].T
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
    row at a time (``lax.map``; each row, and inside it each layer, each head
    and each expert, is computed again in the backward): (weights, ids
    [B, T + 1]) -> loss, expert_tokens and expert_weight [routed layers, E],
    held_rows [routed layers], d loss / d weights in the program's layout."""
    import jax

    from chipbench import reference_lfm2 as ref

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

    def redraw(leaves):
        for name in sorted(leaves):
            if isinstance(leaves[name], dict):
                redraw(leaves[name])
            elif name in GAINS:
                leaves[name] = draw(leaves[name])
            elif name == "moe_select_bias":
                leaves[name] = bias_std * jax.random.normal(
                    next(keys), leaves[name].shape, jnp.float32)

    redraw({top: params[top] for top in ("lead", "layers") if top in params})
    return params


def reference_router(src: dict):
    """(logits, bias) -> (chosen, weight) of the reference's ``choose``
    (looked up when called: the band script swaps it)."""
    from chipbench import reference_lfm2 as ref

    def router(logits, bias):
        _, chosen, weight = ref.choose(logits, bias, src)
        return chosen, weight

    return router


def mixer_layers(src: dict) -> dict:
    """{"sconv": the first ROUTED conv layer's place among the layers held
    here, "attn": the first attention layer's}."""
    kinds = layer_kinds(src)
    return {"sconv": kinds.index(("sconv", "moe")), "attn": kinds.index(("attn", "moe"))}


def mixer_inputs(params: dict, src: dict, which: str, seed: int, batch: int,
                 seq: int, score_gain: float, dtype):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for one mixer alone, from
    ``seed``: that layer's mixer leaves of the seed's weights (the attention
    layer's query gain times ``score_gain``), a standard normal x as a normed
    residual is; leaves and x rounded to ``dtype`` as the trainer hands them
    over, the cotangent float32."""
    import jax
    import jax.numpy as jnp

    top, kind, at = layer_places(src)[mixer_layers(src)[which]]
    leaves = params[top] if kind is None else params[top][kind]
    keys = jax.random.split(jax.random.PRNGKey(seed + (3 if which == "sconv" else 4)), 2)
    lw = {name: leaves[name][at] for name in _MIXER[which]}
    if which == "attn":
        lw["q_norm_w"] = lw["q_norm_w"] * score_gain
    x = jax.random.normal(keys[0], (batch, seq, src["hidden_size"]), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def program_mixer(model, which: str, seq: int):
    """(leaves, x) -> the program's own mixer of that kind, on the routes the
    timed step runs."""
    if which == "sconv":
        return lambda lw, x: model._sconv(lw, x, None)
    rope = model.rope_for("attn", seq)
    return lambda lw, x: model._gqa(lw, x, rope, mixer="attn")


def reference_mixer(src: dict, which: str, dtype=None):
    """The same of the reference's ``short_conv`` / ``attention`` (looked up
    when called: the band script swaps their pieces), one row at a time, in
    float32 at highest precision; ``dtype``: in that one instead (the band's
    lower precisions)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_lfm2 as ref

    def mixer(lw, x):
        named = {"a." + _MIXER[which][k]: _relaid((k,), v.astype(jnp.float32))
                 for k, v in lw.items()}

        def row(one):
            y = one[None].astype(dtype or jnp.float32)
            if which == "sconv":
                return ref.short_conv(named, "a.conv.", y)[0]
            return ref.attention(named, "a.self_attn.", y, src, remat=True)[0]

        with jax.default_matmul_precision("highest"):
            return jax.lax.map(jax.checkpoint(row), x)

    return mixer


def program_routes(mcfg, batch: int, seq: int, dtype) -> dict:
    """What the program says it runs at the cell's shapes."""
    import jax

    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled
    from shuffle_exchange_tpu.ops.flash_attention import attention_route
    from shuffle_exchange_tpu.ops.short_conv import sconv_route

    shape = lambda heads: jax.ShapeDtypeStruct((batch, seq, heads, mcfg.head_dim), dtype)
    return {"grouped_gemm": "megablox" if pallas_enabled() else "ragged_dot",
            "attn_core": attention_route(shape(mcfg.n_heads), shape(mcfg.kv_heads),
                                         shape(mcfg.kv_heads), impl=mcfg.attention_impl),
            "sconv_mix": sconv_route(
                jax.ShapeDtypeStruct((batch, seq, 3 * mcfg.d_model), dtype),
                jax.ShapeDtypeStruct((mcfg.sconv_taps, mcfg.d_model), dtype))}


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``train_steps_mla.failed_checks``'s keys
    (``losses``, ``first_loss_again``, ``reference_loss``, ``route_gap``,
    ``held_gap``, ``counters_add_up``, ``overflow``, ``grad_gaps``,
    ``bias_grad``, ``bias_update_gap``, ``router_gaps``, ``weight_gap``) and
    ``mixer_gaps`` (``both_mixer_gaps``: keys ``sconv/...`` and ``attn/...``).
    The band script hands it a wrong model's or a lower precision's answers in
    the program's place."""
    vals = got["losses"]
    loss_tol, route_tol, grad_tol, router_tol, weight_tol, mixer_tol = (
        float(traffic[k]) for k in ("loss_tol", "route_tol", "grad_tol", "router_tol",
                                    "weight_tol", "mixer_tol"))
    routed_tol = float(traffic.get("grad_tol_routed", grad_tol))
    embed_tol = float(traffic.get("grad_tol_embed", grad_tol))
    attn_tol = float(traffic.get("mixer_tol_attn", mixer_tol))
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    # the tied embedding has a limit of its own: its gradient is the lookup's
    # part AND the head's, and one without the head's (an untied head) reads
    # inside the other leaves' band
    tol_of = lambda leaf: (routed_tol if is_routed(leaf) else
                           embed_tol if leaf == "embed" else grad_tol)
    over = {leaf: gap / tol_of(leaf) for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))         # a NaN gap is the worst of all
    part = max(got["router_gaps"], key=nan_last(got["router_gaps"]))
    # the two q/k gains' gradients are 64 numbers each, sums whose rounding
    # does not average out: they swing with the seed where the matrices' do not
    gain_tol = float(traffic.get("mixer_tol_attn_gain", attn_tol))
    limit = lambda key: (mixer_tol if not key.startswith("attn/") else
                         gain_tol if key.endswith("_norm_w") else attn_tol)
    mixed = {key: gap / limit(key) for key, gap in got["mixer_gaps"].items()}
    piece = max(mixed, key=nan_last(mixed))
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
         f"{tol_of(worst)} (1 = no such "
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
         f"below float32, a bias that is weighed, a missing normalisation, a "
         f"softmax read so)"),
        (weighed is not None and weighed <= weight_tol,
         f"the routed layers' mean weight of a token-choice, expert by expert, "
         f"differs from the reference's by {weighed} of its norm: more than "
         f"{weight_tol} (None: the program handed out no moe_expert_weight)"),
        (mixed[piece] <= 1.0,
         f"the mixer alone: {piece} differs from the reference's by "
         f"{got['mixer_gaps'][piece]:.3g} of its norm: more than {limit(piece)} "
         f"(taps of another count or order, a missing gate, a q/k norm of "
         f"another form or place, arithmetic below float32 read so)"),
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
    from chipbench import reference_lfm2 as ref
    from shuffle_exchange_tpu.models import Transformer

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_sconv holds the whole state on "
                                 f"one chip for its reference; the cell asks for {chips}")
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
    bias_std = float(traffic["select_bias_std"])
    src = source_config(cell, rehearsal)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))
    bf16 = bool(config.get("bf16", {}).get("enabled"))
    dtype = jnp.bfloat16 if bf16 else jnp.float32

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chip; the weights are drawn again for
    # the trainer: the same seed, the same weights
    drawn = initial_params(model, seed, bias_std)
    gain = float(traffic["mixer_score_gain"])
    inputs = {which: mixer_inputs(drawn, src, which, seed, batch, seq, gain, dtype)
              for which in ("sconv", "attn")}
    weights = to_source_names(drawn, src)
    del drawn
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    # the router alone, in float32 on both sides
    route_gaps = router_gaps(program_router(mcfg),
                             router_inputs(seed, batch * seq, mcfg.n_experts, bias_std),
                             reference_router(src))
    # the mixers alone, in the trainer's compute dtype against float32
    mix_gaps = both_mixer_gaps(
        {which: program_mixer(model, which, seq) for which in inputs}, inputs,
        {which: mixer_answers(reference_mixer(src, which), *inputs[which])
         for which in inputs})
    del inputs
    engine = sxt.initialize(model=model, params=initial_params(model, seed, bias_std),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    routes = program_routes(mcfg, batch, seq, dtype)

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows",
                 "moe_expert_weight") if k in got}

    def bias_now():
        """The selection bias of every routed layer, in the counters' order
        ([routed layers, E]): read through the driver's own mapping."""
        named = to_source_names(engine.state.master, src)
        return np.stack([np.asarray(named[f"model.layers.{i}.feed_forward.expert_bias"])
                         for i, (_, ffn) in zip(layers_held(src), layer_kinds(src))
                         if ffn == "moe"])

    bias_before = bias_now()
    losses = [engine.train_batch(first)]
    first_stats = stats_now()
    # the buffer after one step: the reference's aux-free update of the bias
    # it had, on the program's own counts (which ``route_tol`` holds to the
    # reference's), and nothing of the optimizer's
    bias_gap = None
    if "moe_expert_tokens" in first_stats:
        bias_gap = float(np.abs(bias_now() - np.asarray(ref.bias_update(
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
                 routes=routes, remat=[mcfg.remat, mcfg.remat_policy],
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
    # "the loss fell", read on the SAME ids
    again = float(engine.train_batch(first))
    counted = {"moe_expert_tokens", "moe_held_rows", "moe_overflow_rows"}
    have = counted <= set(first_stats) and counted <= set(last_stats)
    lo = int(src.get("expert_first", 0))
    hi = lo + int(src.get("num_experts_held") or src["num_experts"])
    first_gap = held_gap = load = dropped = held_share = held_rows_step = weighed = None
    overflow, counters_add_up = [None, None], False
    if have:
        first_gap = route_gap(first_stats["moe_expert_tokens"],
                              reference["expert_tokens"])
        held_gap = route_gap(first_stats["moe_held_rows"], reference["held_rows"])
        if "moe_expert_weight" in first_stats:
            weighed = weight_gap(first_stats["moe_expert_weight"],
                                 first_stats["moe_expert_tokens"],
                                 reference["expert_weight"], reference["expert_tokens"])
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
         "bias_update_gap": bias_gap, "router_gaps": route_gaps,
         "weight_gap": weighed, "mixer_gaps": mix_gaps},
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
                 first_step_grad_gap_embed=first_gaps.get("embed"),
                 first_step_grad_gaps=first_gaps, router_gaps=route_gaps,
                 first_step_weight_gap=weighed, mixer_gaps=mix_gaps,
                 first_step_bias_update_gap=bias_gap, first_step_bias_grad=bias_grad,
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
                  "sconv_route": routes["sconv_mix"],
                  "sconv_flops_per_token": None if held_rows_step is None else
                  arith_sconv.train_flops_per_token(
                      mcfg, seq, held_rows_step / (batch * seq))},
    }
