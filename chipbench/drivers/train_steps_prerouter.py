"""Training cells of a sparse window / full attention stack whose router
stands APART from its experts (SmallThinker shaped: per-layer ``rope_layout``
and ``sliding_window_layout``, full layers that rotate nothing beside window
layers that rotate by the model's own table, 7 query heads a KV head, every
layer routed with no shared expert and no dense layer, ReLU-gated experts, a
softmax over the chosen logits, and a router that reads the block's INPUT,
un-normed, before attention; one expert-parallel rank's share of the experts):
``train_steps_swa``'s window (``sxt.initialize(...).train_batch`` on a new
seeded batch every step, steps chained on the donated state, two in flight
untraced, one at a time traced) held to the benchmark's own plain float32
reference of the architecture (``chipbench/reference_smallthinker.py``).

As in the other sparse drivers the reference runs FIRST and alone on the chip,
from the same initial weights relaid under the source's names, one row at a
time: the first batch's loss (the cross-entropy plus HF's all-choices
balancing loss at the configuration's ``router_aux_loss_coef``), the
token-choices every one of the router's experts receives in every layer, the
rows that fall on the held experts, and by ``jax.grad`` the gradient. The
trainer's first gradient is read out of Adam's first moment after one update.
``correct`` = ``train_steps_swa``'s checks (losses finite, the first batch's
loss fell, first loss within ``loss_tol``, expert counts and held rows within
``route_tol``, every leaf's gradient within ``grad_tol`` / ``grad_tol_routed``,
the counters add up, nothing dropped, the router alone within ``router_tol``,
the two mixers alone within ``mixer_tol`` / ``mixer_tol_full``, the window
reached the kernels) and three of this architecture's own:

  the window's edge  (``edge_gaps``) the window mixer ALONE, twice: once on
      its seeded input and once with ONE row j of that input replaced. Output
      row j + window - 1 (whose window still holds key j) moves by at least
      ``edge_min`` of its norm; row j + window (the first whose window has
      left key j behind) moves by at most ``edge_outside_tol`` (a masked key
      weighs exactly nothing: 0). Over 4,096 keys a window that is one key off
      moves the mixer's whole output by less than bf16 does, so
      ``mixer_tol`` cannot see it; this reading is exact.
  the router's input  the program's configuration says ``moe_router_input``
      "block" AND the compiled step holds instructions under the scope
      ``pre_router`` (``step_scopes``): a program that routed on the
      post-attention norm opens no such scope.
  the rotation by kind  ``rope_layers_rotated`` (the layers ``Transformer
      .rope_for`` hands a table) equals the source's ``rope_layout`` count,
      and the COMPILED train step (``step_scopes``, as ``pre_router`` is read)
      holds ``nope_core`` instructions where the layout has an unrotated
      layer, ``swa_rope`` instructions where it has a rotated one, and no
      rotation's instruction under a ``nope_*`` scope: a timed step whose full
      kind was handed the window's table opens no ``nope_*`` scope, one whose
      window kind lost its table no ``swa_rope``.

Where the router reads (the block's input, its norm, the post-attention norm)
is a property of the whole block, so the whole model's expert counts hold it:
a router that reads anything else moves nearly every choice (``route_tol``).
The router alone (``router_tol``) is ``train_steps_swa``'s reading: the
program's ``topk_select`` with the forms its own configuration gives, on
seeded float32 logits [tokens, 64], against the reference's ``choose``.

Weights: ``initial_params`` (``train_steps_swa``'s, with the embedding at the
residual stream's scale, so that the block input a router reads carries the
token). Traffic parameters: ``train_steps_swa``'s, ``edge_min`` and
``edge_outside_tol``. ``chipbench/smallthinker_band.py`` measures the band the
tolerances are set from and runs every wrong model and lower precision through
``failed_checks`` below, in the program's place.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_swa, harness
from chipbench.drivers import train_steps_swa
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_mla import _relaid, is_routed, mixer_answers, router_gaps
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap
from chipbench.drivers.train_steps_swa import (both_mixer_gaps, program_mixer, program_router,
                                               reference_first_step, router_inputs,
                                               source_config, visit_share)

# the program's leaves under the source's names
_BLOCK = {"ln1_w": "input_layernorm.weight",
          "ln2_w": "post_attention_layernorm.weight",
          "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
          "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
          "moe_gate": "block_sparse_moe.primary_router.weight"}
_PER_EXPERT = {"moe_w_gate": "gate.weight", "moe_w_up": "up.weight",
               "moe_w_down": "down.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}
MIXER_LEAVES = ("wq", "wk", "wv", "wo")
EDGE_ROW = 1000            # the input row the edge reading replaces


def initial_params(model, seed: int) -> dict:
    """``train_steps_swa.initial_params`` (``model.init`` from ``seed``, the
    gains redrawn), with the embedding drawn again at the residual stream's
    scale, a standard normal. At the init's 0.02 the un-normed block input
    that the routers of every layer but the first read is the sublayers'
    outputs alone (they leave a norm at unit scale), above all what attention
    averaged over the prefix, nearly one vector for every token: a router
    that left the token's own row out would compute the same choices, a few
    experts would take 4-7 x the mean load, and whether they lie among the
    held ones, and with it the step's time, would be the seed's draw."""
    import jax
    import jax.numpy as jnp

    params = train_steps_swa.initial_params(model, seed)
    params["embed"] = jax.random.normal(jax.random.PRNGKey(seed + 6),
                                        params["embed"].shape, jnp.float32)
    return params


def layer_kinds(src: dict) -> list:
    """[(mixer, "moe")] of the layers held here, from the window layout."""
    return [("swa" if w else "attn", "moe")
            for w in src["sliding_window_layout"][:src["num_hidden_layers"]]]


def layer_places(src: dict) -> list:
    """[(kind's name or None, index into that kind's stacked leaves)] a layer:
    under ``layers/<mixer>_moe`` at [period, index among the kind's layers of
    the period], or a one-kind stack's flat [layer] (written out here so that
    the mapping does not move with the program)."""
    kinds = layer_kinds(src)
    period = next(p for p in range(1, len(kinds) + 1) if len(kinds) % p == 0
                  and kinds[:p] * (len(kinds) // p) == kinds)
    several = len(set(kinds[:period])) > 1
    return [("_".join(kind), (j // period, sum(
        1 for k in kinds[j - j % period:j] if k == kind))) if several else (None, (j,))
            for j, kind in enumerate(kinds)]


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model held here."""
    out = [((leaf,), (), name) for leaf, name in _TOP.items()]
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["moe_num_primary_experts"])
    for i, (kind, at) in enumerate(layer_places(src)):
        p = f"model.layers.{i}."
        path = ("layers",) if kind is None else ("layers", kind)
        out += [(path + (leaf,), at, p + theirs) for leaf, theirs in _BLOCK.items()]
        out += [(path + (leaf,), at + (e,),
                 f"{p}block_sparse_moe.experts.{first + e}.{theirs}")
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
    array} (``train_steps_swa.from_source_names`` by this stack's names). A
    name ``named`` lacks (a wrong model without that tensor) counts as zeros
    of its neighbours' shape: a gradient that is not there."""
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
    backward): (weights, ids [B, T + 1]) -> loss, expert_tokens [layers, E],
    held_rows [layers], d loss / d weights in the program's layout. The
    balancing loss is over ALL rows' tokens together, as HF's is."""
    import jax

    from chipbench import reference_smallthinker as ref

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None], remat=True)
            return (parts["ce"], parts["expert_tokens"], parts["held_rows"],
                    [{k: r[k] for k in ("p", "chosen")} for r in parts["routing"]])

        ce, tokens, held, routing = jax.lax.map(jax.checkpoint(row), ids)
        every = [{k: v.reshape((-1,) + v.shape[2:]) for k, v in layer.items()}
                 for layer in routing]
        loss = ce.mean() + float(src.get("router_aux_loss_coef") or 0.0) * (
            ref.balancing_loss(every, src))
        return loss, (tokens.sum(axis=0), held.sum(axis=0))

    def first(w, ids):
        (loss, (tokens, held)), grad = jax.value_and_grad(
            batch_loss, has_aux=True)(w, ids)
        return loss, tokens, held, from_source_names(grad, src)

    return jax.jit(first)


def reference_router(src: dict):
    """The same of the reference's ``choose`` (looked up when called: the band
    script swaps it)."""
    from chipbench import reference_smallthinker as ref

    def router(logits, _):
        _, chosen, weight = ref.choose(logits, src)
        return chosen, weight

    return router


def mixer_layers(src: dict) -> dict:
    """{"swa": the first window layer's index, "full": the first full layer's}."""
    kinds = layer_kinds(src)
    return {"swa": kinds.index(("swa", "moe")), "full": kinds.index(("attn", "moe"))}


def mixer_inputs(params: dict, src: dict, which: str, seed: int, batch: int,
                 seq: int, score_gain: float, dtype):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for one mixer alone, from
    ``seed`` (``train_steps_swa.mixer_inputs``'s, by this stack's places)."""
    import jax
    import jax.numpy as jnp

    kind, at = layer_places(src)[mixer_layers(src)[which]]
    leaves = params["layers"] if kind is None else params["layers"][kind]
    keys = jax.random.split(jax.random.PRNGKey(seed + (3 if which == "swa" else 4)), 2)
    lw = {name: leaves[name][at] for name in MIXER_LEAVES}
    lw["wq"] = lw["wq"] * score_gain
    x = jax.random.normal(keys[0], (batch, seq, src["hidden_size"]), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def reference_mixer(src: dict, which: str, dtype=None):
    """The same of the reference's ``attention`` of that layer (looked up when
    called: the band script swaps its pieces), one row at a time, in float32 at
    highest precision; ``dtype``: in that one instead."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_smallthinker as ref

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


def edge_gaps(mixer, lw, x, window: int, seed: int) -> dict:
    """The window's edge through ``mixer`` (leaves, x) -> y: input row j =
    ``EDGE_ROW`` replaced by 8 x a fresh normal row. {"inside": how far output
    row j + window - 1 moves, over its norm (its window holds key j: it must
    move), "outside": the same of row j + window (the first whose window has
    left key j: exactly 0 where the mask is right)}. None where the sequence
    does not reach past the window."""
    import jax
    import jax.numpy as jnp

    j = min(EDGE_ROW, x.shape[1] - window - 1)
    if j < 0:
        return None
    row = 8.0 * jax.random.normal(jax.random.PRNGKey(seed + 5), x.shape[:1] + x.shape[2:],
                                  jnp.float32)

    def both(lw, x):
        f = lambda x: mixer(lw, x).astype(jnp.float32)
        y, moved = f(x), f(x.at[:, j].set(row.astype(x.dtype)))
        far = lambda t: jnp.linalg.norm(moved[:, t] - y[:, t]) / jnp.linalg.norm(y[:, t])
        return far(j + window - 1), far(j + window)

    inside, outside = jax.device_get(jax.jit(both)(lw, x))
    return {"inside": float(inside), "outside": float(outside)}


def rotated_layers(model, src: dict) -> int:
    """Layers of the stack that the program hands a RoPE table
    (``Transformer.rope_for`` by each layer's mixer)."""
    return sum(model.rope_for(mixer, 8)[0] is not None for mixer, _ in layer_kinds(src))


ROTATIONS = ("swa_rope", "rope_yarn")     # the scopes a rotation's instructions open


def step_scopes(names=("pre_router", "moe_router", "nope_core", "swa_rope")):
    """{scope: the COMPILED train step's instructions under it}, read off the
    program the engine registered with the tracer, and ``rope_under_nope``:
    those of a rotation (``ROTATIONS``) inside an unrotated layer's ``nope_*``
    scope. None where no step is registered."""
    from shuffle_exchange_tpu.profiling import trace

    ops = trace.registered_ops("train_step")
    if ops is None:
        return None
    paths = [op.scope.split("/") for op in ops.values()]
    out = {name: sum(1 for path in paths if name in path) for name in names}
    out["rope_under_nope"] = sum(
        1 for path in paths if any(part in ROTATIONS for part in path)
        and any(part.startswith("nope_") for part in path))
    return out


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct): ``train_steps_swa.failed_checks`` on the readings
    it knows, then this architecture's own (module docstring): ``edge_gaps``
    (None: nothing to read), ``router_input`` = (what the program says, what
    the source says), ``rotated`` = (layers the program hands a table, layers
    the source rotates, layers) and ``scopes`` (``step_scopes``; None: no
    compiled step to read, as in the band script)."""
    failed = train_steps_swa.failed_checks(got, traffic)
    edge = got.get("edge_gaps")
    if edge is not None:
        inside, outside = float(traffic["edge_min"]), float(traffic["edge_outside_tol"])
        if not edge["inside"] >= inside:
            failed.append(f"the window's edge: replacing one input row moved the last "
                          f"output row whose window holds it by {edge['inside']:.3g} of "
                          f"its norm: under {inside} (a window one key short)")
        if not edge["outside"] <= outside:
            failed.append(f"the window's edge: replacing one input row moved the first "
                          f"output row whose window has left it by {edge['outside']:.3g} "
                          f"of its norm: over {outside} (a window one key long, or none)")
    scopes = got.get("scopes")
    says, wants = got.get("router_input", ("block", "block"))
    if says != wants or (scopes is not None and wants == "block"
                         and not scopes.get("pre_router")):
        failed.append(f"the router's input: the program says {says!r} and its compiled "
                      f"step holds {(scopes or {}).get('pre_router')} instructions under "
                      f"pre_router; the source's router reads the {wants} input")
    have, want, layers = got.get("rotated", (0, 0, 0))
    if have != want:
        failed.append(f"the rotation by kind: the program hands {have} layers a RoPE "
                      f"table, the source's rope_layout {want}")
    if scopes is not None:
        found = {k: scopes.get(k) for k in ("nope_core", "swa_rope", "rope_under_nope")}
        if ((want < layers and not found["nope_core"]) or (want and not found["swa_rope"])
                or found["rope_under_nope"]):
            failed.append(f"the rotation by kind: the compiled step holds {found} "
                          f"instructions; the source's rope_layout rotates {want} of "
                          f"{layers} layers (nope_core where one is unrotated, swa_rope "
                          f"where one is rotated, no rotation under nope_*)")
    return failed


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
        raise harness.BenchError("train_steps_prerouter holds the whole state on "
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
    mixers = {which: program_mixer(model, which, seq) for which in inputs}
    mix_gaps = both_mixer_gaps(
        mixers, inputs, {which: mixer_answers(reference_mixer(src, which), *inputs[which])
                         for which in inputs})
    edge = edge_gaps(mixers["swa"], *inputs["swa"][:2], mcfg.swa_window, seed)
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
    scopes = step_scopes()
    router_input = (getattr(mcfg, "moe_router_input", "ffn"), "block")
    rotated = (rotated_layers(model, src),
               int(sum(src["rope_layout"][:src["num_hidden_layers"]])),
               int(src["num_hidden_layers"]))

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
                 moe_router_input=router_input[0], rope_layers_rotated=rotated[0],
                 step_scopes=scopes, remat=[mcfg.remat, mcfg.remat_policy],
                 reference_loss=reference["loss"], first_loss=float(losses[0]),
                 router_gaps=route_gaps, mixer_gaps=mix_gaps, edge_gaps=edge,
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
    hi = lo + int(src.get("num_experts_held") or src["moe_num_primary_experts"])
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
         "window_route": routes["swa_core"], "visit_share": visits,
         "edge_gaps": edge, "router_input": router_input, "rotated": rotated,
         "scopes": scopes},
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
                 mixer_gaps=mix_gaps, edge_gaps=edge, swa_block_visit_share=visits,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped,
                 moe_held_row_share=held_share, traced_steps=traced_steps,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    counters = {"compiles_in_window": in_win["programs_compiled"], "steps": steps,
                "moe_router_input": router_input[0], "rope_layers_rotated": rotated[0],
                "pre_router_ops": (scopes or {}).get("pre_router")}
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
