"""Training cells of a hybrid stack (Qwen3-Next: Gated DeltaNet and gated
full-attention layers, one expert-parallel rank's share of the routed experts
plus a shared expert): ``train_steps_moe``'s window
(``sxt.initialize(...).train_batch`` on a new seeded batch every step, steps
chained on the donated state, two in flight untraced, one at a time traced)
held to the benchmark's own plain float32 reference of the architecture
(``chipbench/reference_qwen3next.py``: the delta rule one token at a time,
whole attention scores, a loop over the held experts).

As in ``train_steps_moe`` the reference runs FIRST and alone on the chip, from
the same initial weights relaid under the source's names, one row at a time:
the first batch's loss (cross-entropy + balancing loss over all rows' tokens
of all layers), the token-choices every one of the router's experts receives
in every layer, the rows that fall on the held experts, and by ``jax.grad`` the
gradient, which waits on the host. The trainer's first gradient is read out of
Adam's first moment after one update ((1 - beta1) x the gradient). ``correct``
= every loss finite, the loss fell, the first loss within ``loss_tol``, the
first step's expert counts over ALL the router's experts of all layers within
``route_tol`` (share of token-choices that differ), every leaf's gradient
within ``grad_tol`` of the reference's norm (``grad_tol_routed`` for the routed
experts' matrices and the routers, whose gradients move with every token-choice
that a rounding flips), the program's held-row counter
equal to its own expert counts summed over the held range (no row lost on the
way) and within ``route_tol`` of the reference's held rows, and no row dropped
(``moe_overflow_rows`` 0 in the first and the last step), and the rule's own
distance from the recurrence within ``state_tol`` (below).

The state's precision. Every reading above goes through the whole model, where
a token-choice that a rounding flips moves every gradient by a tenth of its
norm, and at the init's decays (``A_log`` = log U(0, 16): a state forgotten
within a token) no arithmetic on the state can show at all. So one reading
takes the rule alone, at the decays a trained model has: the function the
program's DeltaNet mixer calls (``ops/gated_delta.gated_delta_chunked``) on
seeded q, k, v, g and beta of the cell's own shape and the trainer's compute
dtype, each head's memory (1 / mean -g) drawn log-uniformly from seq / 128 to
seq / 2 tokens, against the reference's token-by-token ``delta_rule`` in
float32 on the same numbers: the output and the five gradients under a seeded
cotangent, each as a share of the reference's norm (``state_gaps``). A rule
that carries S in bf16 reads three to five times the program's distance there
(``chipbench/qwen3next_band.py``, variant ``bf16_state``); ``state_tol`` sits
between the two.

Weights: ``Transformer.init`` from ``--seed``, except that every zero-centred
gain (block norms, final norm, q/k norm) is drawn from [-0.5, 0.5) and the
DeltaNet output gain from [0.5, 1.5): at their initial 0 and 1 a model that
leaves the gain's form out computes the same function and no comparison
could show it. ``A_log`` = log U(0, 16) and ``dt_bias`` = 1 are the init's own.

Traffic parameters: ``train_steps_moe``'s, ``grad_tol_routed`` and
``state_tol``. ``chipbench/qwen3next_band.py`` measures the band the five are
set from, and runs every wrong model and lower precision through
``failed_checks`` below, in the program's place. Counters derived here
from ``engine.last_step_stats()`` (``moe_expert_tokens`` [L, E],
``moe_held_rows`` [L], ``moe_overflow_rows`` [L]): ``moe_expert_load_max_over_mean``
(over all the router's experts), ``moe_dropped_token_share`` (overflow rows
over all token-choices), ``moe_held_row_share`` (held rows over tokens x k,
worst layer), all three of the window's last step; and the fact
``held_rows_per_step`` (held rows summed over the layers) of the last TRACED
step in a traced run, else of the last step: what the reducers lay beside the
traced kernels' time.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_hybrid, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap

# the program's leaves under the source's names, by the kind that has them
_BLOCK = {"ln1_w": "input_layernorm.weight",
          "ln2_w": "post_attention_layernorm.weight",
          "moe_gate": "mlp.gate.weight",
          "moe_shared_w_gate": "mlp.shared_expert.gate_proj.weight",
          "moe_shared_w_up": "mlp.shared_expert.up_proj.weight",
          "moe_shared_w_down": "mlp.shared_expert.down_proj.weight",
          "moe_shared_gate": "mlp.shared_expert_gate.weight"}
_MIXER = {
    "gated_attn": {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
                   "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
                   "q_norm_w": "self_attn.q_norm.weight",
                   "k_norm_w": "self_attn.k_norm.weight"},
    "gdn": {"w_qkvz": "linear_attn.in_proj_qkvz.weight",
            "w_ba": "linear_attn.in_proj_ba.weight",
            "conv_w": "linear_attn.conv1d.weight", "A_log": "linear_attn.A_log",
            "dt_bias": "linear_attn.dt_bias", "gdn_norm_w": "linear_attn.norm.weight",
            "w_out": "linear_attn.out_proj.weight"}}
_PER_EXPERT = {"moe_w_gate": "gate_proj.weight", "moe_w_up": "up_proj.weight",
               "moe_w_down": "down_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one). The balancing coefficient the catalog's row leaves out is
    the modelling code's default."""
    src = dict(rehearsal.get("source_config") or cell["config"])
    src.setdefault("router_aux_loss_coef", 0.001)
    return src


def period_of(src: dict) -> list:
    """[(kind's name in the program's tree, index among that kind's layers of
    a period, mixer)] for the layers of one period, as the program stacks
    them (``Transformer.slots``; written out here so that the mapping does
    not move with the program)."""
    interval = int(src.get("full_attention_interval", 4))
    return ([("gdn_moe", j, "gdn") for j in range(interval - 1)]
            + [("gated_attn_moe", 0, "gated_attn")])


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model held here."""
    out = [((leaf,), (), name) for leaf, name in _TOP.items()]
    period = period_of(src)
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["num_experts"])
    for i in range(src["num_hidden_layers"]):
        kind, j, mixer = period[i % len(period)]
        at = (i // len(period), j)
        p = f"model.layers.{i}."
        out += [(("layers", kind, leaf), at, p + theirs)
                for leaf, theirs in {**_BLOCK, **_MIXER[mixer]}.items()]
        out += [(("layers", kind, leaf), at + (e,),
                 f"{p}mlp.experts.{first + e}.{theirs}")
                for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


def _relaid(path, x):
    """One tensor between the program's layout and torch's: a matrix is
    [in, out] here and [out, in] there (the embedding [V, D] on both sides),
    the convolution [K, C] here and [C, 1, K] there. Its own inverse but for
    the convolution."""
    if path[-1] == "conv_w":
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
    array}."""
    import jax.numpy as jnp

    cells = {}
    for path, index, name in source_names(src):
        cells.setdefault(path, {})[index] = _relaid(path, named[name])

    def stacked(at, depth, prefix=()):
        if depth == 0:
            return at[prefix]
        n = 1 + max(index[len(prefix)] for index in at
                    if index[:len(prefix)] == prefix)
        return jnp.stack([stacked(at, depth - 1, prefix + (i,)) for i in range(n)])

    return {"/".join(path): stacked(at, len(next(iter(at))))
            for path, at in cells.items()}


def flat_tree(tree: dict) -> dict:
    """{"a/b/c": leaf} of a nested dict, as ``from_source_names`` names them."""
    out = {}
    for key, value in tree.items():
        if isinstance(value, dict):
            out.update({f"{key}/{k}": v for k, v in flat_tree(value).items()})
        else:
            out[key] = value
    return out


def reference_program(src: dict):
    """The reference on a whole batch as ONE jitted program that takes one
    row at a time (``lax.map``; each row, and inside it each layer, each
    expert and each 64 steps of the recurrence, is computed again in the
    backward): (weights, ids [B, T + 1]) -> loss, ce, aux, expert_tokens
    [L, E], held_rows [L], d loss / d weights in the program's layout."""
    import jax

    from chipbench import reference_qwen3next as ref

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None], remat=True)
            return (parts["ce"], parts["expert_tokens"], parts["held_rows"],
                    parts["routing"])

        ce, tokens, held, routing = jax.lax.map(jax.checkpoint(row), ids)
        every = [{k: v.reshape((-1,) + v.shape[2:]) for k, v in layer.items()}
                 for layer in routing]
        aux = ref.balancing_loss(every, src)
        loss = ce.mean() + src["router_aux_loss_coef"] * aux
        return loss, (ce.mean(), aux, tokens.sum(axis=0), held.sum(axis=0))

    def first(w, ids):
        (loss, (ce, aux, tokens, held)), grad = jax.value_and_grad(
            batch_loss, has_aux=True)(w, ids)
        return loss, ce, aux, tokens, held, from_source_names(grad, src)

    return jax.jit(first)


def reference_first_step(program, weights: dict, ids) -> dict:
    """``reference_program``'s answer, on the HOST."""
    import jax

    loss, ce, aux, tokens, held, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "ce": float(ce), "aux": float(aux),
            "expert_tokens": tokens, "held_rows": held, "grads": grads}


def first_moment(opt_state):
    """{"a/b": array}: Adam's first moment out of the trainer's optimizer
    state, flat as ``from_source_names`` lays a gradient; None where the
    optimizer keeps none."""
    import jax

    has = lambda s: hasattr(s, "mu")
    found = [s.mu for s in jax.tree.leaves(opt_state, is_leaf=has) if has(s)]
    return flat_tree(found[0]) if found else None


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed`` with the gains redrawn (the module's
    docstring says why)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    draw = lambda x, lo, hi: jax.random.uniform(next(keys), x.shape, jnp.float32, lo, hi)
    params["ln_f_w"] = draw(params["ln_f_w"], -0.5, 0.5)
    for kind in sorted(params["layers"]):
        leaves = params["layers"][kind]
        for name in sorted(leaves):
            if name in ("ln1_w", "ln2_w", "q_norm_w", "k_norm_w"):
                leaves[name] = draw(leaves[name], -0.5, 0.5)
            elif name == "gdn_norm_w":
                leaves[name] = draw(leaves[name], 0.5, 1.5)
    return params


def rule_inputs(seed: int, batch: int, seq: int, mcfg, dtype):
    """((q, k, v, g, beta), cotangent) for the rule alone, from ``seed``:
    q, k [B, T, Hv, dk] l2-normalised (q scaled by dk^-0.5) and v [B, T, Hv,
    dv] = silu of a normal draw, all three rounded to ``dtype`` as the mixer
    hands them over; beta = sigmoid of a normal draw; g = -softplus(a + 1) /
    softplus(1) / memory, a normal, with each head's ``memory`` log-uniform
    between seq / 128 and seq / 2 tokens; g, beta and the cotangent [B, T, Hv,
    dv] float32."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_qwen3next as ref

    H, dk, dv = mcfg.gdn_value_heads, mcfg.gdn_key_dim, mcfg.gdn_value_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    q = ref.l2norm(normal(keys[0], batch, seq, H, dk)) * dk ** -0.5
    k = ref.l2norm(normal(keys[1], batch, seq, H, dk))
    v = jax.nn.silu(normal(keys[2], batch, seq, H, dv))
    beta = jax.nn.sigmoid(normal(keys[3], batch, seq, H))
    memory = jnp.exp(jax.random.uniform(keys[4], (H,), jnp.float32,
                                        math.log(seq / 128), math.log(seq / 2)))
    g = -jax.nn.softplus(normal(keys[5], batch, seq, H) + 1.0) / (
        math.log1p(math.e) * memory)
    rounded = lambda x: x.astype(dtype)
    return (rounded(q), rounded(k), rounded(v), g, beta), normal(keys[6], batch, seq, H, dv)


RULE_PARTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


def rule_answers(rule, args, cotangent) -> tuple:
    """(o, dq, dk, dv, dg, dbeta) of ``rule(q, k, v, g, beta)`` under the
    cotangent, as one jitted program; float32."""
    import jax
    import jax.numpy as jnp

    def both(args, cotangent):
        o, back = jax.vjp(lambda *a: rule(*a).astype(jnp.float32), *args)
        return tuple(x.astype(jnp.float32) for x in (o,) + back(cotangent))

    return jax.jit(both)(args, cotangent)


def reference_rule(*args):
    """The reference's recurrence in float32 at highest precision, every 64th
    state kept for the backward."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_qwen3next as ref

    q, k, v, g, beta = args
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), g, beta, remat=True)


def state_gaps(rule, inputs, exact=None) -> dict:
    """{"o": ..., "dq": ..., ...}: ``rule``'s distance from the reference's
    recurrence on ``inputs`` (``rule_inputs``), each as a share of the
    reference's norm. ``exact``: the recurrence's answers where the caller
    has them already."""
    if exact is None:
        exact = rule_answers(reference_rule, *inputs)
    return grad_gaps(dict(zip(RULE_PARTS, rule_answers(rule, *inputs))),
                     dict(zip(RULE_PARTS, exact)))


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``reference_loss``, ``route_gap`` and ``held_gap`` (None: the
    program handed out no counters), ``counters_add_up``, ``overflow`` [first
    step, last step], ``grad_gaps`` {leaf: share of the reference's norm},
    ``state_gaps`` (``state_gaps`` above). The band script hands it a wrong
    model's or a lower precision's answers in the program's place."""
    vals = got["losses"]
    loss_tol, route_tol, grad_tol = (float(traffic[k]) for k in
                                     ("loss_tol", "route_tol", "grad_tol"))
    routed_tol = float(traffic.get("grad_tol_routed", grad_tol))
    state_tol = float(traffic["state_tol"])
    over = {leaf: gap / (routed_tol if is_routed(leaf) else grad_tol)
            for leaf, gap in got["grad_gaps"].items()}
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    worst = max(over, key=nan_last(over))         # a NaN gap is the worst of all
    part = max(got["state_gaps"], key=nan_last(got["state_gaps"]))
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    have = got["route_gap"] is not None
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - got["reference_loss"]) <= loss_tol,
         f"first loss {vals[0]} vs the float32 reference {got['reference_loss']}: "
         f"off by more than {loss_tol}"),
        (len(vals) == 1 or sum(tail) / len(tail) < vals[0],
         f"loss did not fall: first {vals[0]}, mean of the last {len(tail)} "
         f"{sum(tail) / len(tail)}"),
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
         "to tokens x k"),
        (over[worst] <= 1.0,
         f"first step's gradient of {worst} differs from the reference's by "
         f"{got['grad_gaps'][worst]:.5f} of its norm: more than "
         f"{routed_tol if is_routed(worst) else grad_tol} (1 = the optimizer's "
         f"state held no first moment to read it from)"),
        (have and got["overflow"] == [0, 0],
         f"held rows dropped (did not fit the buffer): {got['overflow'][0]} in "
         f"the first step, {got['overflow'][1]} in the last"),
        (got["state_gaps"][part] <= state_tol,
         f"the rule alone, at a memory of hundreds of tokens: {part} differs "
         f"from the float32 recurrence's by {got['state_gaps'][part]:.5f} of its "
         f"norm: more than {state_tol} (a state carried below float32 reads so)"),
    ]
    return [message for ok, message in checks if not ok]


def is_routed(leaf: str) -> bool:
    """A routed expert's matrix or a router: the leaves ``grad_tol_routed``
    is for."""
    return "/moe_w_" in leaf or leaf.endswith("/moe_gate")


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

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_hybrid holds the whole state on "
                                 f"one chip for its reference; the cell asks for {chips}")
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
    src = source_config(cell, rehearsal)
    # (a rehearsal may replace sections: float32 at a size where bf16 noise
    # drowns a gradient of a hundred tokens)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chip; the weights are drawn again for
    # the trainer: the same seed, the same weights
    weights = to_source_names(initial_params(model, seed), src)
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    # the rule alone, at a long memory: what shows the state's precision
    from shuffle_exchange_tpu.ops.gated_delta import gated_delta_chunked

    rule_gaps = state_gaps(gated_delta_chunked, rule_inputs(
        seed, batch, seq, mcfg,
        jnp.bfloat16 if config.get("bf16", {}).get("enabled") else jnp.float32))
    engine = sxt.initialize(model=model, params=initial_params(model, seed),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows")
                if k in got}

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
                 routes={"grouped_gemm": "megablox" if pallas_enabled()
                         else "ragged_dot",
                         "attention": "pallas" if pallas_enabled() else "xla",
                         "gated_delta": "xla (chunked, lax.scan)"},
                 remat=[mcfg.remat, mcfg.remat_policy],
                 reference_loss=reference["loss"], reference_ce=reference["ce"],
                 reference_aux=reference["aux"], first_loss=float(losses[0]),
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
    last_stats = stats_now()
    have = len(first_stats) == 3 and len(last_stats) == 3
    lo = int(src.get("expert_first", 0))
    hi = lo + int(src.get("num_experts_held") or src["num_experts"])
    first_gap = held_gap = load = dropped = held_share = held_rows_step = None
    overflow, counters_add_up = [None, None], False
    if have:
        first_gap = route_gap(first_stats["moe_expert_tokens"],
                              reference["expert_tokens"])
        held_gap = route_gap(first_stats["moe_held_rows"], reference["held_rows"])
        # every held token-choice is computed or counted as dropped, and the
        # router's counts come to tokens x k
        counters_add_up = all(
            np.array_equal(s["moe_held_rows"] + s["moe_overflow_rows"],
                           s["moe_expert_tokens"][:, lo:hi].sum(axis=1))
            and int(s["moe_expert_tokens"].sum()) == per_layer * mcfg.n_layers
            for s in (first_stats, last_stats))
        overflow = [int(s["moe_overflow_rows"].sum()) for s in (first_stats, last_stats)]
        counts = last_stats["moe_expert_tokens"]
        load = float((counts.max(axis=1) / counts.mean(axis=1)).max())
        dropped = 100.0 * overflow[1] / (per_layer * mcfg.n_layers)
        held_share = 100.0 * float(last_stats["moe_held_rows"].max()) / per_layer
        held_rows_step = float(traced_stats.get(
            "moe_held_rows", last_stats["moe_held_rows"]).sum())
    failed = failed_checks(
        {"losses": vals, "reference_loss": reference["loss"], "route_gap": first_gap,
         "held_gap": held_gap, "counters_add_up": counters_add_up,
         "overflow": overflow, "grad_gaps": first_gaps, "state_gaps": rule_gaps},
        traffic)
    worst = max(first_gaps, key=lambda leaf: first_gaps[leaf]
                if first_gaps[leaf] == first_gaps[leaf] else math.inf)
    correct = not failed
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_abs_err=abs(vals[0] - reference["loss"]),
                 first_step_route_gap=first_gap, first_step_held_gap=held_gap,
                 first_step_grad_gap=first_gaps[worst],
                 first_step_grad_gap_leaf=worst,
                 first_step_grad_gap_routed=max(
                     (g for leaf, g in first_gaps.items() if is_routed(leaf)), default=None),
                 first_step_grad_gap_others=max(
                     (g for leaf, g in first_gaps.items() if not is_routed(leaf)), default=None),
                 first_step_grad_gaps=first_gaps,
                 state_gap=max(rule_gaps.values()), state_gaps=rule_gaps,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped,
                 moe_held_row_share=held_share, traced_steps=traced_steps,
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
                  "flops_per_token": None if held_rows_step is None else
                  arith_hybrid.train_flops_per_token(
                      mcfg, seq, held_rows_step / (batch * seq))},
    }
