"""Training cells of a sparse-expert (MoE) model: ``train_steps``'s window
(``sxt.initialize(...).train_batch`` on a new seeded batch every step, steps
chained on the donated state, two in flight untraced, one at a time traced)
held to the benchmark's own plain float32 reference of the architecture
(``chipbench/reference_olmoe.py``) instead of the program's own model with jnp
attention, which for an MoE model would check the grouped GEMM against itself.

Before the trainer exists (the reference's backward and the trainer's state do
not fit the chip together) the reference computes, from the SAME initial
weights relaid under the source's names, one row at a time: the first batch's
loss (cross-entropy + balancing loss over all rows' tokens), the token-choices
every expert of every layer receives and, by ``jax.grad`` of that loss, the
gradient, which waits on the host. The trainer's own first gradient is read
back out of its optimizer: after the first AdamW update the first moment is
(1 - beta1) x the gradient, exactly. ``correct`` = every loss finite, the loss
fell, the first loss within ``loss_tol`` of the reference's, the first step's
expert counts within ``route_tol`` of the reference's (share of token-choices
that differ), every leaf's gradient within ``grad_tol`` of the reference's
(norm of the difference over the reference's norm), and no token-choice
dropped. The gradient is
what holds the BACKWARD to the reference: six of the nine grouped GEMMs of a
step, the dispatch's hand-written transpose. A loss that merely falls does not.

Weights: ``Transformer.init`` from ``--seed``, except that the q/k norm gains
are drawn uniformly from [0.5, 1.5): at init they are 1 and the projections
they normalise have unit variance, so leaving the norm out would change
almost nothing and no comparison could show it.

Traffic parameters (``chipbench/traffic/<name>.json``), beside ``train_steps``'s
``seq``, ``batch_per_chip``, ``warmup_steps``, ``trace_steps``, ``loss_tol``:
  route_tol   largest share of the first step's token-choices that may differ
              from the reference's
  grad_tol    largest |g - g_ref| / |g_ref| of any leaf of the first gradient
``chipbench/olmoe_band.py`` measures the band the three are set from.
The program hands out ``moe_expert_tokens`` [L, E] of the last step
(``engine.last_step_stats()``); the counters ``moe_expert_load_max_over_mean``
and ``moe_dropped_token_share`` are derived from it here.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_moe, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them: the cell's
    configuration file (a rehearsal brings a tiny one). The balancing
    coefficient the file leaves out is the modelling code's default."""
    src = dict(rehearsal.get("source_config") or cell["config"])
    src.setdefault("router_aux_loss_coef", 0.01)
    return src


# the program's leaves under the source's names: per layer, and per expert
_PER_LAYER = {"ln1_w": "input_layernorm", "ln2_w": "post_attention_layernorm",
              "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
              "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
              "q_norm_w": "self_attn.q_norm", "k_norm_w": "self_attn.k_norm",
              "moe_gate": "mlp.gate"}
_PER_EXPERT = {"moe_w_gate": "gate_proj", "moe_w_up": "up_proj",
               "moe_w_down": "down_proj"}


def source_names(src: dict) -> list:
    """[(leaf of the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model."""
    out = [("embed", (), "model.embed_tokens.weight"),
           ("ln_f_w", (), "model.norm.weight"),
           ("unembed", (), "lm_head.weight")]
    for i in range(src["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += [(leaf, (i,), f"{p}{theirs}.weight")
                for leaf, theirs in _PER_LAYER.items()]
        out += [(leaf, (i, e), f"{p}mlp.experts.{e}.{theirs}.weight")
                for e in range(src["num_experts"])
                for leaf, theirs in _PER_EXPERT.items()]
    return out


def to_source_names(params: dict, src: dict) -> dict:
    """The program's stacked tree -> a flat dict under the source's names,
    each matrix as torch's nn.Linear stores it ([out, in]; the embedding is
    [V, D] on both sides). Stays on the device; float32 as the master is."""
    flat = {**params, **params["layers"]}
    return {name: flat[leaf][index] if leaf == "embed" else flat[leaf][index].T
            for leaf, index, name in source_names(src)}


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat: {leaf: the program's stacked array}."""
    import jax.numpy as jnp

    cells = {}
    for leaf, index, name in source_names(src):
        cells.setdefault(leaf, {})[index] = (named[name] if leaf == "embed"
                                             else named[name].T)
    layers, experts = range(src["num_hidden_layers"]), range(src["num_experts"])
    out = {}
    for leaf, at in cells.items():
        if () in at:
            out[leaf] = at[()]
        elif (0,) in at:
            out[leaf] = jnp.stack([at[i,] for i in layers])
        else:
            out[leaf] = jnp.stack([jnp.stack([at[i, e] for e in experts])
                                   for i in layers])
    return out


def reference_program(src: dict):
    """The reference on a whole batch as ONE jitted program that takes one
    row at a time (``lax.map``: whole [H, T, T] scores are kept, so a row is
    what fits; each row is computed again in the backward): (weights, ids
    [B, T + 1]) -> loss, ce, aux, expert_tokens [L, E], d loss / d weights in
    the program's layout ({leaf: stacked array}). ``loss`` = mean
    cross-entropy + coefficient x the reference's own balancing loss over ALL
    rows' tokens (it concatenates over tokens)."""
    import jax

    from chipbench import reference_olmoe as ref

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None])
            return parts["ce"], parts["expert_tokens"], parts["routing"]

        ce, tokens, routing = jax.lax.map(jax.checkpoint(row), ids)
        # all rows' tokens of a layer together, layer by layer: what the
        # source's concatenation over layers and tokens sees
        every = [{k: v.reshape((-1,) + v.shape[2:]) for k, v in layer.items()}
                 for layer in routing]
        aux = ref.balancing_loss(every, src)
        loss = ce.mean() + src["router_aux_loss_coef"] * aux
        return loss, (ce.mean(), aux, tokens.sum(axis=0))

    def first(w, ids):
        (loss, (ce, aux, tokens)), grad = jax.value_and_grad(
            batch_loss, has_aux=True)(w, ids)
        return loss, ce, aux, tokens, from_source_names(grad, src)

    return jax.jit(first)


def reference_first_step(program, weights: dict, ids) -> dict:
    """``reference_program``'s answer, on the HOST (the gradient is as large
    as the weights, and the trainer's step leaves no room for it): ``loss``,
    ``ce``, ``aux``, ``expert_tokens`` [L, E] summed over rows, ``grads``."""
    import jax

    loss, ce, aux, tokens, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "ce": float(ce), "aux": float(aux),
            "expert_tokens": tokens, "grads": grads}


def first_moment(opt_state):
    """{leaf: array}: Adam's first moment out of the trainer's optimizer
    state, flat as ``from_source_names`` lays a gradient; None where the
    optimizer keeps none. It starts at 0, so after ONE update it is
    (1 - beta1) x that step's gradient."""
    import jax

    has = lambda s: hasattr(s, "mu")
    found = [s.mu for s in jax.tree.leaves(opt_state, is_leaf=has) if has(s)]
    if not found:
        return None
    return {**{k: v for k, v in found[0].items() if k != "layers"},
            **found[0]["layers"]}


def grad_gaps(ours: dict, theirs: dict, scale: float = 1.0) -> dict:
    """{leaf: |scale x ours - theirs| / |theirs|} over ``theirs``' leaves
    (host arrays go to the device for it)."""
    import jax
    import jax.numpy as jnp

    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
    gaps = jax.jit(lambda a, b: {k: norm(scale * a[k] - b[k]) / norm(b[k])
                                 for k in b})(
        {k: ours[k] for k in theirs}, jax.device_put(theirs))
    return {k: float(v) for k, v in jax.device_get(gaps).items()}


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed``, the q/k norm gains drawn from [0.5, 1.5)
    (the module's docstring says why)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    gains = jax.random.split(jax.random.PRNGKey(seed + 1))
    for name, key in zip(("q_norm_w", "k_norm_w"), gains):
        params["layers"][name] = jax.random.uniform(
            key, params["layers"][name].shape, jnp.float32, 0.5, 1.5)
    return params


def route_gap(a, b) -> float:
    """Share of token-choices that went to another expert: half the summed
    absolute difference of two [L, E] count tables over their total."""
    import numpy as np

    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    return float(np.abs(a - b).sum() / 2.0 / max(int(b.sum()), 1))


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled

    cell, meter, spans = ctx["cell"], ctx["meter"], ctx["spans"]
    rehearsal = ctx.get("rehearsal") or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_moe holds the whole state on one "
                                 "chip for its reference; the cell asks for "
                                 f"{chips}")
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
    mcfg = harness.model_config(cell, rehearsal)
    src = source_config(cell, rehearsal)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9)

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chip: its backward at the published
    # widths peaks at 14.0 GB, and the trainer's state is 7.5 GB more. The
    # weights are drawn again for the trainer: the same seed, the same weights
    weights = to_source_names(initial_params(model, seed), src)
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    engine = sxt.initialize(model=model, params=initial_params(model, seed),
                            config=config, seed=seed)[0]
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)

    stats_of = getattr(engine, "last_step_stats", dict)
    losses = [engine.train_batch(first)]
    first_counts = np.asarray(stats_of().get("moe_expert_tokens", ()))
    # that read waited for the step: its memory is free again, and the next
    # step has not replaced the moment yet
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
                 routes={"fused_adamw": "pallas" if pallas_enabled() else "xla",
                         "grouped_gemm": "megablox" if pallas_enabled()
                         else "ragged_dot"},
                 reference_loss=reference["loss"], reference_ce=reference["ce"],
                 reference_aux=reference["aux"], first_loss=float(losses[0]),
                 compiled_step_bytes=step_bytes,
                 peak_memory_in_bytes=peak_bytes, **warm)

    # -- the window (train_steps's) -------------------------------------------
    traced = bool(ctx["trace"])
    trace_steps = int(traffic.get("trace_steps", 4))
    in_window = meter.mark()
    window_losses = []
    tracing, trace_at, traced_steps = False, None, 0
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
    loss_tol, route_tol, grad_tol = (float(traffic[k]) for k in
                                     ("loss_tol", "route_tol", "grad_tol"))
    tail = vals[-max(1, min(20, steps)):]
    choices = batch * seq * mcfg.moe_top_k * mcfg.n_layers
    last_counts = np.asarray(stats_of().get("moe_expert_tokens", ()))
    have = first_counts.size > 0 and last_counts.size > 0
    first_gap = route_gap(first_counts, reference["expert_tokens"]) if have else 1.0
    dropped = (100.0 * (choices - int(last_counts.sum())) / choices
               if have else None)
    first_dropped = choices - int(first_counts.sum()) if have else None
    load = (float((last_counts.max(axis=1) / last_counts.mean(axis=1)).max())
            if have else None)
    worst = max(first_gaps, key=first_gaps.get)
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - reference["loss"]) <= loss_tol,
         f"first loss {vals[0]} vs the float32 reference {reference['loss']}: off "
         f"by more than {loss_tol}"),
        (sum(tail) / len(tail) < vals[0],
         f"loss did not fall: first {vals[0]}, mean of the last {len(tail)} "
         f"{sum(tail) / len(tail)}"),
        (have, "the program handed out no moe_expert_tokens"),
        (first_gap <= route_tol,
         f"first step's expert counts differ from the reference's in "
         f"{first_gap:.5f} of the token-choices: more than {route_tol}"),
        (first_gaps[worst] <= grad_tol,
         f"first step's gradient of {worst} differs from the reference's by "
         f"{first_gaps[worst]:.5f} of its norm: more than {grad_tol} (1 = the "
         f"optimizer's state held no first moment to read it from)"),
        (have and dropped == 0.0 and first_dropped == 0,
         f"token-choices dropped: {first_dropped} in the first step, "
         f"{dropped}% in the last"),
    ]
    correct = all(c for c, _ in checks)
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_abs_err=abs(vals[0] - reference["loss"]),
                 first_step_route_gap=first_gap,
                 first_step_grad_gap=first_gaps[worst],
                 first_step_grad_gaps=first_gaps,
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped, traced_steps=traced_steps,
                 failed_checks=[m for c, m in checks if not c], **in_win)
    counters = {"compiles_in_window": in_win["programs_compiled"],
                "steps": steps}
    if have:
        counters.update(moe_expert_load_max_over_mean=load,
                        moe_dropped_token_share=dropped)
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
                  "flops_per_token": arith_moe.train_flops_per_token(mcfg, seq)},
    }
