"""Training cells of a window / full attention hybrid sparse stack (Laguna
shaped: ``sliding_attention`` and ``full_attention`` layers in one stack, each
type with its own head count and its own RoPE table, a leading dense layer, a
sigmoid router whose chosen scores are renormalised and scaled, one ungated
shared expert, one expert-parallel rank's share of the routed experts):
``train_steps_mla``'s window (``sxt.initialize(...).train_batch`` on a new
seeded batch every step, steps chained on the donated state, two in flight
untraced, one at a time traced) held to the benchmark's own plain float32
reference of the architecture (``chipbench/reference_laguna.py``: a dense
masked softmax a head and a query block, a loop over the held experts).

As in the other sparse drivers the reference runs FIRST and alone on the chip,
from the same initial weights relaid under the source's names, one row at a
time: the first batch's loss (the cross-entropy plus the sequence-wise balance
loss at the configuration's ``aux_loss_alpha``), the token-choices every one
of the router's experts receives in every ROUTED layer, the rows that fall on
the held experts, and by ``jax.grad`` the gradient, which waits on the host.
The trainer's first gradient is read out of Adam's first moment after one
update ((1 - beta1) x the gradient). ``correct`` = every loss finite, the loss
fell (the first batch's, read once more after the last step), the first loss
within ``loss_tol``, the first step's expert counts over ALL the router's
experts of all routed layers within ``route_tol`` (share of token-choices that
differ), every leaf's gradient within ``grad_tol`` of the reference's norm
(``grad_tol_routed`` for the routed experts' matrices and the routers), the
program's held-row counter equal to its own expert counts summed over the held
range and within ``route_tol`` of the reference's held rows, no row dropped
(``moe_overflow_rows`` 0 in the first and the last step), the router's own
distance from the reference's within ``router_tol`` and each of the two
mixers' within ``mixer_tol`` / ``mixer_tol_full`` (both below), and the window
reached the kernels (``swa_block_visit_share`` under 100 wherever the route is
a kernel).

The router alone: ``train_steps_mla``'s reading, without a bias: the function
the program's routed layer calls (``moe.gating.topk_select`` with the forms
the program's OWN configuration gives) on seeded float32 logits of the cell's
own shape, against the reference's ``choose`` on the same numbers.

The mixers alone. Through the whole model a window that is one key off, a
RoPE table of the other layer type or a missing YaRN factor moves every
gradient by less than what one flipped token-choice does. So two readings take
the new mechanisms alone: the function the program's layers call
(``Transformer._gqa``: projections, the kind's own rotation and the attention
route of the timed step) as mixer "swa" on the seed's first window layer's
leaves and as mixer "attn" on its first routed full layer's, the query
projection times ``mixer_score_gain`` (scores spread over several units, as a
trained head's), a seeded normed input and a seeded cotangent of the cell's own
shape in the trainer's compute dtype, against the reference's ``attention`` of
that layer in float32 on the same numbers: the output, the input's gradient
and the four leaves', each as a share of the reference's norm
(``mixer_gaps``, keys ``swa/...`` and ``full/...``).

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm) is drawn from [0.5, 1.5): at their initial 1 a model that
leaves them out computes the same function.

Traffic parameters: ``train_steps_moe``'s, ``grad_tol_routed``, ``router_tol``,
``mixer_tol`` (the window mixer's readings ``swa/...``), ``mixer_tol_full`` (the
full mixer's ``full/...``; ``mixer_tol`` without it) and ``mixer_score_gain``.
``chipbench/laguna_band.py`` measures the band the tolerances are set from, and
runs every wrong model and lower precision through ``failed_checks`` below, in
the program's place. Counters
derived here from ``engine.last_step_stats()`` as in ``train_steps_mla``, and
``swa_block_visit_share`` (static: ``ops.flash_attention.block_visit_share``
on the mask the program builds for this sequence length and window; None from
a program without it). ``routes`` in the ``setup`` line is what the program
says it runs (``ops.flash_attention.attention_route``), not a restatement.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_swa, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_mla import (_relaid, is_routed, mixer_answers,
                                               router_forms, router_gaps)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap

# the program's leaves under the source's names
_BLOCK = {"ln1_w": "input_layernorm.weight",
          "ln2_w": "post_attention_layernorm.weight",
          "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
          "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight"}
_DENSE = {"w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
          "w_down": "mlp.down_proj.weight"}
_ROUTED = {"moe_gate": "mlp.gate.weight",
           "moe_shared_w_gate": "mlp.shared_expert.gate_proj.weight",
           "moe_shared_w_up": "mlp.shared_expert.up_proj.weight",
           "moe_shared_w_down": "mlp.shared_expert.down_proj.weight"}
_PER_EXPERT = {"moe_w_gate": "gate_proj.weight", "moe_w_up": "up_proj.weight",
               "moe_w_down": "down_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}
GAINS = ("ln1_w", "ln2_w")
MIXER_LEAVES = ("wq", "wk", "wv", "wo")


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def layer_kinds(src: dict) -> list:
    """[(mixer, ffn)] of the layers held here, from the source's two lists."""
    L = src["num_hidden_layers"]
    return [("attn" if t == "full_attention" else "swa",
             "mlp" if f == "dense" else "moe")
            for t, f in zip(src["layer_types"][:L], src["mlp_layer_types"][:L])]


def layer_places(src: dict) -> list:
    """[(top, kind's name or None, index into that kind's stacked leaves)] a
    layer: the leading dense layers under ``lead``, the others under
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
    source's name)] for every tensor of the model held here."""
    out = [((leaf,), (), name) for leaf, name in _TOP.items()]
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["num_experts"])
    for i, ((_, ffn), (top, kind, at)) in enumerate(zip(layer_kinds(src),
                                                        layer_places(src))):
        p = f"model.layers.{i}."
        path = (top,) if kind is None else (top, kind)
        mine = {**_BLOCK, **(_DENSE if ffn == "mlp" else _ROUTED)}
        out += [(path + (leaf,), at, p + theirs) for leaf, theirs in mine.items()]
        if ffn == "moe":
            out += [(path + (leaf,), at + (e,), f"{p}mlp.experts.{first + e}.{theirs}")
                    for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


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
    head, each query block and each expert, is computed again in the
    backward): (weights, ids [B, T + 1]) -> loss, expert_tokens [routed
    layers, E], held_rows [routed layers], d loss / d weights in the
    program's layout."""
    import jax

    from chipbench import reference_laguna as ref

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None], remat=True)
            return parts["loss"], parts["expert_tokens"], parts["held_rows"]

        ce, tokens, held = jax.lax.map(jax.checkpoint(row), ids)
        return ce.mean(), (tokens.sum(axis=0), held.sum(axis=0))

    def first(w, ids):
        (loss, (tokens, held)), grad = jax.value_and_grad(
            batch_loss, has_aux=True)(w, ids)
        return loss, tokens, held, from_source_names(grad, src)

    return jax.jit(first)


def reference_first_step(program, weights: dict, ids) -> dict:
    """``reference_program``'s answer, on the HOST."""
    import jax

    loss, tokens, held, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "expert_tokens": tokens, "held_rows": held,
            "grads": grads}


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed`` with the gains redrawn (the module's
    docstring says why)."""
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

    redraw({top: params[top] for top in ("lead", "layers") if top in params})
    return params


def router_inputs(seed: int, tokens: int, experts: int):
    """(logits [tokens, experts] float32, a standard normal as a random
    router's are over a normed input; no bias) from ``seed``."""
    import jax
    import jax.numpy as jnp

    return (jax.random.normal(jax.random.PRNGKey(seed + 2), (tokens, experts),
                              jnp.float32), jnp.zeros((experts,), jnp.float32))


def program_router(mcfg):
    """(logits, unused) -> (chosen [N, k], weight [N, k]) as the program routes."""
    from shuffle_exchange_tpu.moe.gating import topk_select

    forms = router_forms(mcfg)

    def router(logits, _):
        idx, w, *_rest = topk_select(logits, **forms)
        return idx, w

    return router


def reference_router(src: dict):
    """The same of the reference's ``choose`` (looked up when called: the band
    script swaps it)."""
    from chipbench import reference_laguna as ref

    def router(logits, _):
        _, chosen, weight = ref.choose(logits, src)
        return chosen, weight

    return router


def mixer_layers(src: dict) -> dict:
    """{"swa": the first window layer's index, "full": the first ROUTED full
    layer's} (the leading layer is full too; the routed one is the kind the
    period repeats)."""
    kinds = layer_kinds(src)
    return {"swa": kinds.index(("swa", "moe")), "full": kinds.index(("attn", "moe"))}


def mixer_inputs(params: dict, src: dict, which: str, seed: int, batch: int,
                 seq: int, score_gain: float, dtype):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for one mixer alone, from
    ``seed``: that layer's four attention leaves of the seed's weights, the
    query projection times ``score_gain`` (at the init's scale every softmax is
    nearly flat, so no arithmetic inside it and no key at the window's edge can
    show: a trained head's spread over several units), a standard normal x as a
    normed residual is; leaves and x rounded to ``dtype`` as the trainer hands
    them over, the cotangent float32."""
    import jax
    import jax.numpy as jnp

    top, kind, at = layer_places(src)[mixer_layers(src)[which]]
    leaves = params[top] if kind is None else params[top][kind]
    keys = jax.random.split(jax.random.PRNGKey(seed + (3 if which == "swa" else 4)), 2)
    lw = {name: leaves[name][at] for name in MIXER_LEAVES}
    lw["wq"] = lw["wq"] * score_gain
    x = jax.random.normal(keys[0], (batch, seq, src["hidden_size"]), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def program_mixer(model, which: str, seq: int):
    """(leaves, x) -> the program's own mixer of that kind (``Transformer
    ._gqa``: projections, the kind's rotation by the table the program builds
    for it, the attention route the timed step runs)."""
    mixer = "swa" if which == "swa" else "attn"
    rope = model.rope_for(mixer, seq)
    return lambda lw, x: model._gqa(lw, x, rope, mixer=mixer)


def reference_mixer(src: dict, which: str, dtype=None):
    """The same of the reference's ``attention`` of that layer (looked up when
    called: the band script swaps its pieces), one row at a time, in float32 at
    highest precision; ``dtype``: in that one instead (the band's lower
    precisions)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_laguna as ref

    i = mixer_layers(src)[which]

    def mixer(lw, x):
        named = {"a." + _BLOCK[k]: _relaid((k,), v.astype(jnp.float32))
                 for k, v in lw.items()}
        row = lambda one: ref.attention(
            named, "a.self_attn.", one[None].astype(dtype or jnp.float32), src, i,
            remat=True)[0]
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(jax.checkpoint(row), x)

    return mixer


def both_mixer_gaps(mixers: dict, inputs: dict, exact: dict) -> dict:
    """{"swa/y": ..., "swa/dwq": ..., "full/y": ...}: each of ``mixers``
    ({"swa": fn, "full": fn}) from ``exact`` ({which: ``mixer_answers`` of the
    reference in float32}) on ``inputs`` ({which: ``mixer_inputs``}), each as
    a share of the reference's norm."""
    return {f"{which}/{k}": v for which in mixers
            for k, v in grad_gaps(mixer_answers(mixers[which], *inputs[which]),
                                  exact[which]).items()}


def visit_share(seq: int, window: int, itemsize: int):
    """``swa_block_visit_share`` as the program counts it, or None from a
    program that has no such count (the parent of PR 39)."""
    try:
        from shuffle_exchange_tpu.ops.flash_attention import block_visit_share
    except ImportError:
        return None
    return float(block_visit_share(seq, window, itemsize))


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``first_loss_again`` (the first batch's loss once more, after the
    last step; None: nothing to fall), ``reference_loss``, ``route_gap`` and
    ``held_gap`` (None: the program handed out no counters),
    ``counters_add_up``, ``overflow`` [first step, last step], ``grad_gaps``
    {leaf: share of the reference's norm}, ``router_gaps``, ``mixer_gaps``
    (``both_mixer_gaps``), ``window_route`` and ``visit_share`` (the window
    layers' attention route by name and the share of causal blocks its mask
    visits; a kernel route at 100 has not been handed the window). The band
    script hands it a wrong model's or a lower precision's answers in the
    program's place."""
    vals = got["losses"]
    loss_tol, route_tol, grad_tol, router_tol, mixer_tol = (
        float(traffic[k]) for k in ("loss_tol", "route_tol", "grad_tol", "router_tol",
                                    "mixer_tol"))
    routed_tol = float(traffic.get("grad_tol_routed", grad_tol))
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    over = {leaf: gap / (routed_tol if is_routed(leaf) else grad_tol)
            for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))         # a NaN gap is the worst of all
    part = max(got["router_gaps"], key=nan_last(got["router_gaps"]))
    # the full layers' mixer has a limit of its own: over 16,384 keys a row it
    # reads half as far again as the window's over 512, in every precision
    full_tol = float(traffic.get("mixer_tol_full", mixer_tol))
    limit = lambda key: full_tol if key.startswith("full/") else mixer_tol
    mixed = {key: gap / limit(key) for key, gap in got["mixer_gaps"].items()}
    piece = max(mixed, key=nan_last(mixed))
    again = got.get("first_loss_again")
    have = got["route_gap"] is not None
    kernel = got.get("window_route") not in (None, "reference")
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
        (have and got["overflow"] == [0, 0],
         f"held rows dropped (did not fit the buffer): {got['overflow'][0]} in "
         f"the first step, {got['overflow'][1]} in the last"),
        (got["router_gaps"][part] <= router_tol,
         f"the router alone: {part} differs from the reference's by "
         f"{got['router_gaps'][part]:.3g}: more than {router_tol} (a router "
         f"below float32, a missing scale or normalisation, a softmax read so)"),
        (mixed[piece] <= 1.0,
         f"the mixer alone: {piece} differs from the reference's by "
         f"{got['mixer_gaps'][piece]:.3g} of its norm: more than {limit(piece)} "
         f"(a window one key off, the other layer type's RoPE table, YaRN "
         f"without its factor, a softmax below float32 read so)"),
        (not kernel or (got.get("visit_share") or 100.0) < 100.0,
         f"the window layers' attention route {got.get('window_route')!r} "
         f"visits {got.get('visit_share')}% of the causal blocks: the window "
         f"did not reach the kernel"),
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
        raise harness.BenchError("train_steps_swa holds the whole state on "
                                 f"one chip for its reference; the cell asks for {chips}")
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
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
    drawn = initial_params(model, seed)
    gain = float(traffic["mixer_score_gain"])
    inputs = {which: mixer_inputs(drawn, src, which, seed, batch, seq, gain, dtype)
              for which in ("swa", "full")}
    weights = to_source_names(drawn, src)
    del drawn
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    # the router alone, in float32 on both sides
    route_gaps = router_gaps(program_router(mcfg),
                             router_inputs(seed, batch * seq, mcfg.n_experts),
                             reference_router(src))
    # the mixers alone, in the trainer's compute dtype against float32
    mix_gaps = both_mixer_gaps(
        {which: program_mixer(model, which, seq) for which in inputs}, inputs,
        {which: mixer_answers(reference_mixer(src, which), *inputs[which])
         for which in inputs})
    del inputs
    engine = sxt.initialize(model=model, params=initial_params(model, seed),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    shape = lambda heads: jax.ShapeDtypeStruct((batch, seq, heads, mcfg.head_dim), dtype)
    routes = {"grouped_gemm": "megablox" if pallas_enabled() else "ragged_dot",
              "swa_core": attention_route(
                  shape(mcfg.heads_of("swa")), shape(mcfg.kv_heads), shape(mcfg.kv_heads),
                  impl=mcfg.attention_impl, window=mcfg.swa_window),
              "full_core": attention_route(
                  shape(mcfg.n_heads), shape(mcfg.kv_heads), shape(mcfg.kv_heads),
                  impl=mcfg.attention_impl)}
    visits = visit_share(seq, mcfg.swa_window, 2 if bf16 else 4)

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows") if k in got}

    losses = [engine.train_batch(first)]
    first_stats = stats_now()
    beta1 = settings["train_config"]["optimizer"]["params"].get("betas", (0.9,))[0]
    moment = first_moment(engine.state.opt_state)
    first_gaps = ({leaf: 1.0 for leaf in reference["grads"]} if moment is None
                  else grad_gaps(moment, reference.pop("grads"), 1.0 / (1.0 - beta1)))
    del moment
    for _ in range(max(1, int(traffic["warmup_steps"])) - 1):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 routes=routes, swa_block_visit_share=visits,
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
    # "the loss fell", read on the SAME ids
    again = float(engine.train_batch(first))
    counted = {"moe_expert_tokens", "moe_held_rows", "moe_overflow_rows"}
    have = counted <= set(first_stats) and counted <= set(last_stats)
    lo = int(src.get("expert_first", 0))
    hi = lo + int(src.get("num_experts_held") or src["num_experts"])
    first_gap = held_gap = load = dropped = held_share = held_rows_step = None
    overflow, counters_add_up = [None, None], False
    if have:
        first_gap = route_gap(first_stats["moe_expert_tokens"],
                              reference["expert_tokens"])
        held_gap = route_gap(first_stats["moe_held_rows"], reference["held_rows"])
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
         "overflow": overflow, "grad_gaps": first_gaps,
         "router_gaps": route_gaps, "mixer_gaps": mix_gaps,
         "window_route": routes["swa_core"], "visit_share": visits},
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
                 mixer_gaps=mix_gaps, swa_block_visit_share=visits,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped,
                 moe_held_row_share=held_share, traced_steps=traced_steps,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    counters = {"compiles_in_window": in_win["programs_compiled"],
                "steps": steps}
    if visits is not None:
        counters["swa_block_visit_share"] = visits
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
                  "swa_flops_per_token": None if held_rows_step is None else
                  arith_swa.train_flops_per_token(
                      mcfg, seq, held_rows_step / (batch * seq))},
    }
