"""Training cells of a LOOPED dense stack (Ouro shaped: sandwich-normed blocks
of plain multi-head attention and a gated MLP, the stack run ``total_ut_steps``
times over the same weights with the final norm inside the loop, an exit gate,
the loss taken at every exit under the gate's distribution):
``train_steps_ssm_dense``'s window (``sxt.initialize(...).train_batch`` on a new
seeded batch every step, steps chained on the donated state, two in flight
untraced, one at a time traced) held to the benchmark's own plain float32
reference of the architecture (``chipbench/reference_ouro.py``: a Python loop
over steps and layers, a block of queries at a time, full logits an exit).

The reference runs FIRST and alone on the chip, from the same initial weights
relaid under the source's names (each visit, each query block and each block
of an exit's logits computed again in the backward): the first batch's loss, each
exit's mean CE and mean mass, and by ``jax.grad`` the gradient, which waits on
the host. The trainer's first gradient is read out of Adam's first moment after
one update ((1 - beta1) x the gradient) and compared leaf by leaf of the
program's tree. ``correct`` = every loss finite, the loss fell, the first loss
within ``loss_tol``, every leaf's gradient within ``grad_tol`` of the
reference's norm, and ALONE where the whole model hides them:

  the gate's two leaves (``gate_tol``)  2,049 numbers of 667 M: their own gaps,
      on a limit of their own (a gate that reads another stream, a ``p_T`` that
      uses ``lam_T`` and a detached weighting all move THEM first).
  each exit's mean CE (``exit_tol``, absolute) and mean mass (``pdf_tol``,
      absolute) against the reference's, from the program's own counters
      ``loop_exit_ce`` / ``loop_exit_mass`` of the first step.
  the exit block (``alone_tol``)  ``Transformer.exit_distribution``, the
      function the step's loss calls, on seeded streams of the cell's shape in
      the compute dtype (a normed stream: unit rows times a gain from [0.5,
      1.5)) and a seeded gate, against the reference's ``gate``,
      ``exit_distribution`` and ``entropy`` on the same numbers in float32:
      the WORST token's |p_t - p_t'| and |H - H'| (``train_steps_ssm_dense``'s
      reading of its gated norm alone). A gate logit, a distribution or an
      entropy formed in bf16 is a few thousandths off at some token, and
      averages out of an exit's mean mass over 8,192 of them.
  the update (``update_tol``)  every leaf of the MASTER after the second
      update against the seed's weights moved by Adam's step as written here
      (``adam_step``), from the trainer's own two moments: |change - step| /
      |step|. The first update runs at the schedule's learning rate 0 (its
      count starts at 0) and moves nothing; the second, at peak / warm-up
      steps (1.5e-6 here), moves a weight near 0.02 by less than a hundredth
      of a bf16 spacing: a master kept in bf16 and an update that is lost both
      read 1. (Leaves of fewer than 16 numbers, the gate's bias, are not read:
      one number's step can vanish, ``update_gaps``.) The gradient the moments hold is the one ``grad_tol`` holds to
      the reference's.
  ``loop_layer_visits`` == ``total_ut_steps`` x ``num_hidden_layers`` and
      ``loss_rows`` == ``total_ut_steps`` x the step's tokens (0 or None: not
      correct): the head read every exit.

Weights: ``Transformer.init`` from ``--seed``, except that every gain (the four
block norms, the final norm) is drawn from [0.5, 1.5), the gate's weight normal
/ sqrt(D) (its logit on a normed stream then varies over tokens with a
deviation near 1) and its bias from [-0.5, 0.5]: at their neutral values a
model that leaves one out computes the same function.

Traffic parameters: ``train_steps``', ``loss_tol``, ``grad_tol``, ``gate_tol``,
``exit_tol``, ``pdf_tol``, ``alone_tol``, ``update_tol``. ``chipbench/ouro_band.py`` measures the band they are
set from and runs every wrong model and lower precision through
``failed_checks`` below, in the program's place.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_loop, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_gdn import host_gaps
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree

_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight", "exit_gate_w": "model.early_exit_gate.weight",
        "exit_gate_b": "model.early_exit_gate.bias"}
_BLOCK = {"ln1_w": "input_layernorm.weight", "ln1_post_w": "input_layernorm_2.weight",
          "ln2_w": "post_attention_layernorm.weight",
          "ln2_post_w": "post_attention_layernorm_2.weight",
          "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
          "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
          "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
          "w_down": "mlp.down_proj.weight"}
GAINS = ("ln1_w", "ln1_post_w", "ln2_w", "ln2_post_w")
GATE = ("exit_gate_w", "exit_gate_b")


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def _relaid(leaf: str, x):
    """One tensor between the program's layout and torch's: a matrix is
    [in, out] here and [out, in] there (the embedding [V, D] on both sides);
    the gate a Linear(D, 1): weight [D] here, [1, D] there; bias [] / [1]."""
    if leaf == "exit_gate_w":
        return x[None, :] if x.ndim == 1 else x[0]
    if leaf == "exit_gate_b":
        return x.reshape(1) if x.ndim == 0 else x.reshape(())
    return x.T if x.ndim == 2 and leaf != "embed" else x


def to_source_names(params: dict, src: dict) -> dict:
    """The program's tree -> a flat dict under the source's names, each tensor
    as torch stores it. Stays on the device; float32 as the master is."""
    out = {name: _relaid(leaf, params[leaf]) for leaf, name in _TOP.items()}
    for i in range(int(src["num_hidden_layers"])):
        for leaf, name in _BLOCK.items():
            out[f"model.layers.{i}.{name}"] = _relaid(leaf, params["layers"][leaf][i])
    return out


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat and on the HOST (numpy):
    {"/".join(path): the program's stacked array}."""
    import numpy as np

    named = {k: np.asarray(v) for k, v in named.items()}
    out = {leaf: _relaid(leaf, named[name]) for leaf, name in _TOP.items()}
    for leaf, name in _BLOCK.items():
        out["layers/" + leaf] = np.stack([
            _relaid(leaf, named[f"model.layers.{i}.{name}"])
            for i in range(int(src["num_hidden_layers"]))])
    return out


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed`` with the gains and the gate redrawn (the
    module's docstring says why)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    draw = lambda x, lo=0.5, hi=1.5: jax.random.uniform(next(keys), x.shape, jnp.float32,
                                                        lo, hi)
    params["ln_f_w"] = draw(params["ln_f_w"])
    for name in GAINS:
        params["layers"][name] = draw(params["layers"][name])
    w = params["exit_gate_w"]
    params["exit_gate_w"] = jax.random.normal(next(keys), w.shape, jnp.float32) / math.sqrt(
        w.shape[0])
    params["exit_gate_b"] = draw(params["exit_gate_b"], -0.5, 0.5)
    return params


def reference_program(src: dict, dtype=None):
    """The reference on a whole batch as ONE jitted program: (weights, ids
    [B, S + 1]) -> ((loss, {each exit's mean CE, mean mass, the mean entropy
    and expected steps}), d loss / d weights under the source's names). The
    cell's ONE row goes straight through (the reference's own checkpoints a
    visit are all that is replayed: the forward runs twice, not three times);
    more rows go one at a time (``lax.map`` of a checkpointed row, so that one
    row's kept inputs live at a time). ``dtype``: the band's lower precision."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_ouro as ref

    dtype = dtype or jnp.float32
    keep = ("loss", "exit_ce", "exit_mass", "entropy", "expected_steps")

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None], dtype, True)
            return {k: parts[k] for k in keep}

        if ids.shape[0] == 1:
            parts = row(ids[0])
        else:
            parts = jax.tree.map(lambda a: a.mean(axis=0),
                                 jax.lax.map(jax.checkpoint(row), ids))
        return parts.pop("loss"), parts

    return jax.jit(jax.value_and_grad(batch_loss, has_aux=True))


def reference_first_step(program, weights: dict, ids, src: dict) -> dict:
    """``reference_program``'s answer on the HOST, the gradient in the
    program's layout."""
    import jax
    import numpy as np

    (loss, parts), grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "grads": from_source_names(grads, src),
            **{k: np.asarray(v, np.float64).tolist() for k, v in parts.items()}}


# -- the mechanisms alone -------------------------------------------------------

def exit_inputs(seed: int, steps: int, rows: int, seq: int, width: int, dtype):
    """(streams [T, B, S, D], the gate's weight [D] and bias []) for the exit
    block alone, from ``seed`` and rounded to ``dtype`` (what an engine hands
    the model): each stream a NORMED one (normal rows, root mean square 1,
    times a gain from [0.5, 1.5)), the gate drawn as ``initial_params`` draws
    it, so that its logit varies over tokens with a deviation near 1."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
    gain = jax.random.uniform(keys[0], (width,), jnp.float32, 0.5, 1.5)
    streams = jax.random.normal(keys[1], (steps, rows, seq, width), jnp.float32) * gain
    weight = jax.random.normal(keys[2], (width,), jnp.float32) / math.sqrt(width)
    bias = jax.random.uniform(keys[3], (), jnp.float32, -0.5, 0.5)
    return tuple(a.astype(dtype) for a in (streams, weight, bias))


def program_exit_block(model):
    """The program's exit block as a function of ``exit_inputs``' three:
    ``Transformer.exit_distribution`` -> (p [T, B, S], H [B, S])."""
    return lambda streams, weight, bias: model.exit_distribution(
        {"exit_gate_w": weight, "exit_gate_b": bias}, streams)


def reference_exit_block(streams, weight, bias):
    """The same of the reference's own pieces, float32 at highest precision."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_ouro as ref

    f32 = jnp.float32
    named = {"model.early_exit_gate.weight": weight.astype(f32)[None, :],
             "model.early_exit_gate.bias": bias.astype(f32).reshape(1)}
    with jax.default_matmul_precision("highest"):
        p = ref.exit_distribution([ref.gate(named, h.astype(f32)) for h in streams])
    return p, ref.entropy(p)


def exit_alone_gaps(block, inputs) -> dict:
    """{"exit/p", "exit/H"}: the largest distance over the TOKENS of
    ``block``'s distribution (over its exits too) and entropy from
    ``reference_exit_block``'s on the same ``inputs``; inf for a NaN or
    another shape."""
    import jax
    import jax.numpy as jnp

    def far(ours, theirs):
        if ours.shape != theirs.shape:
            return math.inf
        gap = float(jnp.max(jnp.abs(ours.astype(jnp.float32) - theirs)))
        return gap if gap == gap else math.inf

    (p, h), (want_p, want_h) = jax.jit(block)(*inputs), jax.jit(reference_exit_block)(*inputs)
    return {"exit/p": far(p, want_p), "exit/H": far(h, want_h)}


def moments(opt_state):
    """({"a/b": Adam's first moment}, {"a/b": its second}) out of the
    trainer's optimizer state; None where the optimizer keeps none."""
    import jax

    has = lambda s: hasattr(s, "mu") and hasattr(s, "nu")
    found = [s for s in jax.tree.leaves(opt_state, is_leaf=has) if has(s)]
    return (flat_tree(found[0].mu), flat_tree(found[0].nu)) if found else None


def adam_step(before, mu, nu, update: int, lr: float, betas, eps: float, decay: float):
    """The ``update``-th step of Adam with decoupled weight decay on one
    leaf, as the configuration states the optimizer (FusedAdam: AdamW), from
    the moments AFTER that update and the weights before it, in float32:
    -lr x (mu^ / (sqrt(nu^) + eps) + decay x w), mu^ = mu / (1 - beta1^n)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    m_hat = mu.astype(f32) / (1.0 - betas[0] ** update)
    v_hat = nu.astype(f32) / (1.0 - betas[1] ** update)
    return -lr * (m_hat / (jnp.sqrt(v_hat) + eps) + decay * before.astype(f32))


def update_gaps(after: dict, before: dict, both, update: int, lr: float, optimizer: dict,
                least: int = 16) -> dict:
    """{leaf: |(after - before) - step| / |step|} over the flat trees
    ``after`` and ``before`` (the master after ``update`` updates of which
    only the last ran at a learning rate ``lr`` other than 0, and the seed's
    weights), ``step`` = ``adam_step`` of ``both`` moments; on the device, a
    leaf at a time. 1 = the leaf did not move (every leaf 1 where there are no
    moments); 0 for a leaf that has no gradient and stayed; inf for a NaN. A
    leaf of fewer than ``least`` numbers is NOT read: where two batches'
    gradients nearly cancel in the first moment ONE number's step falls under
    its float32 spacing (the gate's bias read 0.123 on seed 2147484413, where
    every other seed read 1e-3: my chip run, PR 64) and a handful can read
    anything up to 1; over a leaf's thousands they cannot."""
    import jax
    import jax.numpy as jnp

    if both is None:
        return {leaf: 1.0 for leaf, new in after.items() if new.size >= least}
    betas = tuple(optimizer.get("betas", (0.9, 0.999)))
    eps, decay = float(optimizer.get("eps", 1e-8)), float(optimizer.get("weight_decay", 0.0))

    @jax.jit
    def sums(new, old, mu, nu):
        step = adam_step(old, mu, nu, update, lr, betas, eps, decay)
        moved = new.astype(jnp.float32) - old.astype(jnp.float32)
        return jnp.sum(jnp.square(moved - step)), jnp.sum(jnp.square(step))

    out = {}
    for leaf, new in after.items():
        if new.size < least:
            continue
        off, norm = (float(x) for x in sums(new, before[leaf], both[0][leaf], both[1][leaf]))
        gap = math.sqrt(off / norm) if norm else (0.0 if off == 0.0 else math.inf)
        out[leaf] = gap if gap == gap else math.inf
    return out


def warmup_lr(scheduler: dict, peak: float, update: int) -> float:
    """The learning rate of the ``update``-th update (1 = the first) under
    the cell's schedule, WarmupCosineLR in its linear warm-up from 0: the
    count starts at 0, so the first update runs at 0."""
    params = scheduler["params"]
    assert scheduler["type"] == "WarmupCosineLR" and not params.get("warmup_min_ratio")
    warm = max(2, int(params["warmup_num_steps"]))
    assert update <= warm, "past the warm-up: the cosine is not written here"
    return peak * (update - 1) / warm


def step_counters(stats: dict) -> dict:
    """The looped stack's counters of one step (``Engine.last_step_stats``)
    on the host: scalars as numbers, per-exit rows as lists; a program without
    them gives None for each."""
    import numpy as np

    names = ("loop_layer_visits", "loss_rows", "loop_exit_mass", "loop_exit_ce",
             "loop_exit_entropy", "loop_expected_steps")
    host = lambda a: None if a is None else np.asarray(a, np.float64).tolist()
    return {name: host(stats.get(name)) for name in names}


def is_gate(leaf: str) -> bool:
    """The exit gate's two leaves: what ``gate_tol`` is for."""
    return leaf in GATE


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``reference`` (``loss``, ``exit_ce`` [T], ``exit_mass`` [T]),
    ``grad_gaps`` {leaf: share of the reference's norm}, ``counters``
    (``step_counters`` of the first step), ``visits_expected``,
    ``rows_expected``, ``exit_alone_gaps`` (``exit_alone_gaps``),
    ``update_gaps`` (``update_gaps``); either of the last two empty or
    missing: not correct. The band script hands it a wrong model's or a lower
    precision's answers in the program's place."""
    vals, ref, counters = got["losses"], got["reference"], got["counters"]
    loss_tol, grad_tol, gate_tol, exit_tol, pdf_tol, alone_tol, update_tol = (
        float(traffic[k]) for k in ("loss_tol", "grad_tol", "gate_tol", "exit_tol", "pdf_tol",
                                    "alone_tol", "update_tol"))
    alone = got.get("exit_alone_gaps") or {"exit/p": math.inf}
    moved = got.get("update_gaps") or {"no leaf": math.inf}
    apart = max(alone, key=alone.get)
    stuck = max(moved, key=moved.get)
    limit = lambda leaf: gate_tol if is_gate(leaf) else grad_tol
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    over = {leaf: gap / limit(leaf) for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))          # a NaN gap is the worst of all

    def off(name, theirs):
        """The largest |program's counter - reference's| over the exits; inf
        where the program reported none or another number of exits."""
        ours = counters.get(name)
        if ours is None or len(ours) != len(theirs):
            return math.inf
        gap = max(abs(a - b) for a, b in zip(ours, theirs))
        return gap if gap == gap else math.inf

    ce_off, mass_off = off("loop_exit_ce", ref["exit_ce"]), off("loop_exit_mass",
                                                                ref["exit_mass"])
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - ref["loss"]) <= loss_tol,
         f"first loss {vals[0]} vs the float32 reference {ref['loss']}: "
         f"off by more than {loss_tol}"),
        (len(vals) == 1 or sum(tail) / len(tail) < vals[0],
         f"loss did not fall: first {vals[0]}, mean of the last {len(tail)} "
         f"{sum(tail) / len(tail)}"),
        (over[worst] <= 1.0,
         f"first step's gradient of {worst} differs from the reference's by "
         f"{got['grad_gaps'][worst]:.5f} of its norm: more than {limit(worst)} (1 = "
         f"the optimizer's state held no first moment to read it from)"),
        (ce_off <= exit_tol,
         f"an exit's mean CE {counters.get('loop_exit_ce')} differs from the "
         f"reference's {ref['exit_ce']} by {ce_off:.2e}: more than {exit_tol}"),
        (mass_off <= pdf_tol,
         f"an exit's mean mass {counters.get('loop_exit_mass')} differs from the "
         f"reference's {ref['exit_mass']} by {mass_off:.2e}: more than {pdf_tol}"),
        (alone[apart] <= alone_tol,
         f"the exit block alone: {apart} of some token differs from the float32 "
         f"reference's by {alone[apart]:.2e}: more than {alone_tol}"),
        (moved[stuck] <= update_tol,
         f"the master's change of {stuck} over the first updates differs from Adam's "
         f"step by {moved[stuck]:.4f} of it: more than {update_tol} (1 = the leaf did "
         f"not move: a master too coarse for the step, or an update lost)"),
        (bool(counters.get("loop_layer_visits"))
         and counters["loop_layer_visits"] == got["visits_expected"],
         f"the program's loop_layer_visits counter reads "
         f"{counters.get('loop_layer_visits')!r}, not total_ut_steps x "
         f"num_hidden_layers = {got['visits_expected']}"),
        (bool(counters.get("loss_rows")) and counters["loss_rows"] == got["rows_expected"],
         f"the program's loss_rows counter reads {counters.get('loss_rows')!r}, not "
         f"total_ut_steps x the step's tokens = {got['rows_expected']}: the head "
         f"did not read every exit"),
    ]
    return [message for ok, message in checks if not ok]


def run(ctx: dict) -> dict:
    cell = ctx["cell"]
    rehearsal = ctx.get("rehearsal") or {}
    # first: a program that cannot build the configuration says so at once
    mcfg = harness.model_config(cell, rehearsal)

    import jax
    import jax.numpy as jnp

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.flash_attention import attention_route

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    chips = len(ctx["devices"])
    seq, per_chip = int(traffic["seq"]), int(traffic["batch_per_chip"])
    batch = per_chip * chips
    src = source_config(cell, rehearsal)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))
    dtype = jnp.bfloat16 if config.get("bf16", {}).get("enabled") else jnp.float32

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chip: its weights are drawn under the
    # source's names (and again for the trainer: the same seed, the same
    # weights), so that the chip holds them once beside its gradient
    weights = jax.jit(lambda: to_source_names(initial_params(model, seed), src))()
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]), src)
    del weights
    # the exit block alone, at one chip's rows of the cell's own shape
    steps_T, layers = int(src["total_ut_steps"]), int(src["num_hidden_layers"])
    alone_gaps = exit_alone_gaps(program_exit_block(model), exit_inputs(
        seed, steps_T, per_chip, seq, mcfg.d_model, dtype))
    params = initial_params(model, seed)
    engine = sxt.initialize(model=model, params=params, config=config, seed=seed)[0]
    del params
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(a.size) for a in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)

    losses = [engine.train_batch(first)]
    counters = step_counters(engine.last_step_stats())
    optimizer = config["optimizer"]["params"]
    beta1 = optimizer.get("betas", (0.9,))[0]
    moment = first_moment(engine.state.opt_state)
    first_gaps = ({leaf: 1.0 for leaf in reference["grads"]} if moment is None
                  else host_gaps(moment, reference.pop("grads"), 1.0 / (1.0 - beta1)))
    del moment
    # the second update is the first that moves the master (the first runs at
    # the schedule's 0): its change against Adam's step from the seed's weights
    losses.append(engine.train_batch(next(data)))
    params = flat_tree(initial_params(model, seed))
    moved_gaps = update_gaps(flat_tree(engine.state.master), params,
                             moments(engine.state.opt_state), 2,
                             warmup_lr(config["scheduler"], float(optimizer["lr"]), 2), optimizer)
    del params
    for _ in range(int(traffic["warmup_steps"]) - 2):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    heads = jax.ShapeDtypeStruct((per_chip, seq, mcfg.n_heads, mcfg.head_dim), dtype)
    routes = {"attn_core": attention_route(heads, heads, heads, impl=mcfg.attention_impl)}
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 routes=routes, loop_steps=getattr(mcfg, "loop_steps", None),
                 norm_order=mcfg.norm_order, counters=counters,
                 remat=[mcfg.remat, mcfg.remat_policy],
                 reference_loss=reference["loss"], first_loss=float(losses[0]),
                 reference_exit_ce=reference["exit_ce"],
                 reference_exit_mass=reference["exit_mass"],
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
    last = step_counters(engine.last_step_stats())

    # -- correct, outside the window ------------------------------------------
    vals = [float(v) for v in losses + window_losses]
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    failed = failed_checks(
        {"losses": vals, "reference": reference, "grad_gaps": first_gaps,
         "counters": counters, "visits_expected": steps_T * layers,
         "rows_expected": steps_T * batch * seq,
         "exit_alone_gaps": alone_gaps, "update_gaps": moved_gaps},
        traffic)
    nan_last = lambda gaps: lambda k: gaps[k] if gaps[k] == gaps[k] else math.inf
    worst = max(first_gaps, key=nan_last(first_gaps))
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_abs_err=abs(vals[0] - reference["loss"]),
                 first_step_grad_gap=first_gaps[worst],
                 first_step_grad_gap_leaf=worst,
                 first_step_grad_gap_gate=max(
                     (g for leaf, g in first_gaps.items() if is_gate(leaf)), default=None),
                 first_step_grad_gap_others=max(
                     (g for leaf, g in first_gaps.items() if not is_gate(leaf)),
                     default=None),
                 first_step_grad_gaps=first_gaps,
                 exit_alone_gaps=alone_gaps, update_gaps=moved_gaps,
                 update_gap=max(moved_gaps.values()),
                 first_step_counters=counters, last_step_counters=last,
                 traced_steps=traced_steps,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    return {
        "correct": not failed, "attempted": steps,
        "failed": sum(1 for v in vals[len(losses):] if not math.isfinite(v)),
        "end_to_end": {"train_tokens_per_s_chip": per_chip_rate},
        # arguments + temporaries, as every training driver reports it (it
        # counts buffers that never live together and reads past the chip's
        # memory here; XLA's own peak is in the setup line)
        "window_s": window_s, "program_bytes": step_bytes,
        "counters": {"compiles_in_window": in_win["programs_compiled"],
                     "steps": steps,
                     "loop_layer_visits": counters["loop_layer_visits"],
                     # the FIRST step's: the seed's gate on the seed's batch,
                     # the same in every run of a seed (the last step's depends
                     # on how many steps the window held)
                     "loop_expected_steps": counters["loop_expected_steps"]},
        "facts": {"model_cfg": mcfg, "seq": seq, "batch": batch,
                  "chips": chips, "tokens_per_step": batch * seq,
                  "step_s": [b - a for a, b in spans.named("train_step")],
                  "traced_steps": traced_steps,
                  "loop_steps": steps_T,
                  "loop_flops_per_token":
                      arith_loop.train_flops_per_token(mcfg, seq, steps_T)},
    }
