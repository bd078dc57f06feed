"""Training cells of a state-space / attention hybrid sparse stack (Nemotron-H
shaped: Mamba-2 layers beside grouped-query attention that rotates nothing,
layers that are a mixer or a feed-forward part alone, ungated squared-ReLU
experts under a sigmoid router with a selection bias plus a shared expert of
its own width, one expert-parallel rank's share of the routed experts, an
untied head): ``train_steps_sconv``'s window
(``sxt.initialize(...).train_batch`` on a new seeded batch every step, steps
chained on the donated state, two in flight untraced, one at a time traced)
held to the benchmark's own plain float32 reference of the architecture
(``chipbench/reference_nemotron3.py``).

As in the other sparse drivers the reference runs FIRST and alone on the chip,
from the same initial weights relaid under the source's names, one row at a
time: the first batch's loss, the token-choices every one of the router's
experts receives in every ROUTED layer with their summed weights, the rows
that fall on the held experts, and by ``jax.grad`` the gradient, which waits on
the host. The trainer's first gradient is read out of Adam's first moment after
one update. ``correct`` = ``train_steps_sconv``'s list (finite losses, the
first batch's loss fell, first loss within ``loss_tol``, expert counts and held
rows within ``route_tol``, every leaf's gradient within ``grad_tol`` /
``grad_tol_routed``, the counters add up, nothing dropped, no gradient on the
selection bias and the buffer after one step the aux-free update of the one
before, the timed step's own weights within ``weight_tol``, the router alone
within ``router_tol``) and, below, the new mechanism ALONE, twice.

The state-space mixer alone (``mixer_tol``): ``Transformer._ssm`` (the
projections, the convolution, the gates, the scan on the route the timed step
runs, the gated grouped norm) on the seed's first state-space layer's leaves,
a seeded normed input and a seeded cotangent of the cell's own shape in the
trainer's compute dtype, against the reference's ``mamba`` in float32 on the
same numbers: the output, the input's gradient and every leaf's, each as a
share of the reference's norm (``mixer_gaps``, keys ``ssm/...``).

The scan alone (``state_tol``, ``decay_tol``): ``ops.ssd.ssd_chunked`` on the
x, B, C, step and decay the reference's own pieces make of that input (x, B and
C rounded to the compute dtype, as the mixer hands them over), against the
reference's ``scan``, the recurrence token by token in float32: the output and
the gradients of x, B, C and the step (``scan_gaps``, keys ``scan/...``). A
state kept in bf16 reads past ``state_tol``. A decay whose exponent is formed
in bf16 does NOT: it moves the scan by less than the rounding of the products'
operands to bf16 does (the band: 0.0019 against the program's 0.004). So the
scan runs a second time on the SAME numbers held as float32 (keys
``scan32/...``): its products are then float32 too, what is left is the
state's and the decay's own arithmetic, which is the same code at either
operand dtype, and ``decay_tol`` lies below both lower precisions.

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm, the gated norm's) and the skip ``D`` are drawn from
[0.5, 1.5) and the selection bias from a normal of ``select_bias_std``: at
their initial 1 and 0 a model that leaves them out computes the same function
(the convolution's bias is drawn by ``init`` itself, torch's default).

Traffic parameters: ``train_steps_sconv``'s, ``state_tol`` beside
``mixer_tol``. ``chipbench/nemotron3_band.py`` measures the band the
tolerances are set from, and runs every wrong model and lower precision
through ``failed_checks`` below, in the program's place. ``routes`` in the
``setup`` line is what the program says it runs (``ops.ssd.ssd_route``,
``ops.flash_attention.attention_route``) and the scan's chunk count, not a
restatement; the fact ``ssd_route`` carries the first to the roofline's
reducer. ``correct`` holds the scan's: the stand-alone scan that ``state_tol``
and ``decay_tol`` read ran the route the program states for the cell's shapes
(``ssd_alone``), and the COMPILED step bears that route out
(``ssd_step_kernels``: the backward kernel's launch is among its instructions
where the route is not "xla", and is not where it is).
"""

from __future__ import annotations

import math
import time

from chipbench import arith_ssm, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_mla import (is_routed, mixer_answers, program_router,
                                               router_gaps, router_inputs)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap

# the program's leaves under the source's names
_MIXER = {"ssm": {"ssm_w_in": "in_proj.weight", "ssm_conv_w": "conv1d.weight",
                  "ssm_conv_b": "conv1d.bias", "ssm_dt_bias": "dt_bias",
                  "ssm_A_log": "A_log", "ssm_D": "D", "ssm_norm_w": "norm.weight",
                  "ssm_w_out": "out_proj.weight"},
          "attn": {"wq": "q_proj.weight", "wk": "k_proj.weight", "wv": "v_proj.weight",
                   "wo": "o_proj.weight"}}
_ROUTED = {"moe_gate": "gate.weight", "moe_select_bias": "gate.e_score_correction_bias",
           "moe_shared_w_up": "shared_experts.up_proj.weight",
           "moe_shared_w_down": "shared_experts.down_proj.weight"}
_PER_EXPERT = {"moe_w_up": "up_proj.weight", "moe_w_down": "down_proj.weight"}
_TOP = {"embed": "backbone.embeddings.weight", "ln_f_w": "backbone.norm_f.weight",
        "unembed": "lm_head.weight"}
GAINS = ("ln1_w", "ln2_w", "ssm_norm_w", "ssm_D")


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def letters(src: dict) -> str:
    """The letters of the layers held here."""
    return str(src["hybrid_override_pattern"])[:int(src["num_hidden_layers"])]


def blocks(src: dict) -> list:
    """[((mixer, ffn), the mixer's own index in the source, the feed-forward
    part's or None)] of the (mixer, ffn) blocks the layers held here pair
    into: a mixer and the ``E`` right after it, or a mixer alone."""
    out, i, text = [], 0, letters(src)
    while i < len(text):
        mixer = {"M": "ssm", "*": "attn"}[text[i]]
        if text[i + 1:i + 2] == "E":
            out.append(((mixer, "moe"), i, i + 1))
            i += 2
        else:
            out.append(((mixer, "none"), i, None))
            i += 1
    return out


def layer_places(src: dict) -> list:
    """[(kind's name, index into that kind's stacked leaves)] a block held
    here: under ``layers/<mixer>_<ffn>`` at [period, index among the kind's
    blocks of the period] (written out here so that the mapping does not move
    with the program; the model has no leading layers)."""
    kinds = [kind for kind, _, _ in blocks(src)]
    period = next(p for p in range(1, len(kinds) + 1) if len(kinds) % p == 0
                  and kinds[:p] * (len(kinds) // p) == kinds)
    return [("_".join(kind), (j // period,
                              sum(1 for k in kinds[j - j % period:j] if k == kind)))
            for j, kind in enumerate(kinds)]


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model held here; a layer's name
    carries the source's OWN index (a block is two of the source's layers, or
    one)."""
    out = [((leaf,), (), name) for leaf, name in _TOP.items()]
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["n_routed_experts"])
    for ((mixer, ffn), i, j), (kind, at) in zip(blocks(src), layer_places(src)):
        path = ("layers", kind)
        p = f"backbone.layers.{i}."
        out.append((path + ("ln1_w",), at, p + "norm.weight"))
        out += [(path + (leaf,), at, p + "mixer." + theirs)
                for leaf, theirs in _MIXER[mixer].items()]
        if ffn != "moe":
            continue
        p = f"backbone.layers.{j}."
        out.append((path + ("ln2_w",), at, p + "norm.weight"))
        out += [(path + (leaf,), at, p + "mixer." + theirs) for leaf, theirs in _ROUTED.items()]
        out += [(path + (leaf,), at + (e,), f"{p}mixer.experts.{first + e}.{theirs}")
                for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


def _relaid(path, x):
    """One tensor between the program's layout and torch's: a matrix is
    [in, out] here and [out, in] there (the embedding [V, D] on both sides);
    the taps are [K, C] here and [C, 1, K] there."""
    if path[-1] == "ssm_conv_w":
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
    row at a time (``lax.map``; each row, and inside it each layer, each head,
    each expert and each block of the scan, is computed again in the
    backward): (weights, ids [B, T + 1]) -> loss, expert_tokens and
    expert_weight [routed layers, E], held_rows [routed layers], d loss / d
    weights in the program's layout."""
    import jax

    from chipbench import reference_nemotron3 as ref

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
    """``model.init`` from ``seed`` with the gains, the skip and the selection
    bias redrawn (the module's docstring says why)."""
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

    redraw(params["layers"])
    return params


def reference_router(src: dict):
    """(logits, bias) -> (chosen, weight) of the reference's ``choose``
    (looked up when called: the band script swaps it)."""
    from chipbench import reference_nemotron3 as ref

    def router(logits, bias):
        _, chosen, weight = ref.choose(logits, bias, src)
        return chosen, weight

    return router


def mixer_inputs(params: dict, src: dict, seed: int, batch: int, seq: int, dtype):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for the state-space mixer
    alone, from ``seed``: the first state-space layer's mixer leaves of the
    seed's weights, a standard normal x as a normed residual is; leaves and x
    rounded to ``dtype`` as the trainer hands them over, the cotangent
    float32."""
    import jax
    import jax.numpy as jnp

    at = next(j for j, (kind, _, _) in enumerate(blocks(src)) if kind[0] == "ssm")
    kind, index = layer_places(src)[at]
    keys = jax.random.split(jax.random.PRNGKey(seed + 3), 2)
    lw = {name: params["layers"][kind][name][index] for name in _MIXER["ssm"]}
    x = jax.random.normal(keys[0], (batch, seq, src["hidden_size"]), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def program_mixer(model):
    """(leaves, x) -> the program's own state-space mixer, on the route the
    timed step runs."""
    return lambda lw, x: model._ssm(lw, x, None)


def _named(lw: dict) -> dict:
    """One mixer's leaves under the source's names, float32, prefix ``a.``."""
    import jax.numpy as jnp

    return {"a." + _MIXER["ssm"][k]: _relaid((k,), v.astype(jnp.float32))
            for k, v in lw.items()}


def reference_mixer(src: dict, dtype=None):
    """The same of the reference's ``mamba`` (looked up when called: the band
    script swaps its pieces), one row at a time, in float32 at highest
    precision; ``dtype``: in that one instead (the band's lower precisions)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_nemotron3 as ref

    def mixer(lw, x):
        named = _named(lw)
        row = lambda one: ref.mamba(named, "a.", one[None].astype(dtype or jnp.float32), src)[0]
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(jax.checkpoint(row), x)

    return mixer


def scan_inputs(lw: dict, x, src: dict, seed: int, dtype):
    """((x, dt, A, B, C, D), cotangent) for the scan alone: what the
    reference's own pieces make of the mixer's input before the scan (the
    projection, the taps with their bias and SiLU, the step's softplus and the
    decay), x [B, T, H, P], B and C [B, T, G, N] rounded to ``dtype`` as the
    mixer hands them to the scan, the step [B, T, H], A and D [H] float32; a
    seeded float32 cotangent of the output's shape."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_nemotron3 as ref

    f32 = jnp.float32
    H, P = src["mamba_num_heads"], src["mamba_head_dim"]
    G, N = src["n_groups"], src["ssm_state_size"]
    inner = H * P

    def pieces(lw, x):
        named = _named(lw)
        with jax.default_matmul_precision("highest"):
            _, xbc, dt = ref.mamba_split(ref.linear(x.astype(f32), named["a.in_proj.weight"]), src)
        xbc = jax.nn.silu(ref.taps_sum(xbc, named["a.conv1d.weight"], named["a.conv1d.bias"]))
        step, A = ref.step_and_decay(dt, named["a.dt_bias"], named["a.A_log"])
        B_, T = x.shape[:2]
        return (xbc[..., :inner].reshape(B_, T, H, P).astype(dtype), step, A,
                xbc[..., inner:inner + G * N].reshape(B_, T, G, N).astype(dtype),
                xbc[..., inner + G * N:].reshape(B_, T, G, N).astype(dtype),
                named["a.D"])

    args = jax.jit(pieces)(lw, x)
    cot = jax.random.normal(jax.random.PRNGKey(seed + 5), args[0].shape, f32)
    return args, cot


def scan_answers(scan, args, cotangent) -> dict:
    """{"y", "dx", "ddt", "dB", "dC"} of ``scan(x, dt, A, B, C, D)``
    [B, T, H, P] under the cotangent, as one jitted program; float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def both(args, cotangent):
        x, dt, A, B, C, D = args
        y, back = jax.vjp(lambda x, dt, B, C: scan(x, dt, A, B, C, D).astype(f32),
                          x, dt, B, C)
        dx, ddt, dB, dC = back(cotangent)
        return {"y": y, "dx": dx, "ddt": ddt, "dB": dB, "dC": dC}

    return jax.tree.map(lambda a: a.astype(f32), jax.jit(both)(args, cotangent))


def as_float32(args):
    """The same numbers held as float32."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), args)


def exact_scan_answers(args, cotangent) -> dict:
    """``scan_answers`` of the reference's recurrence on ``args`` held as
    float32: gradients of bf16 operands would come back rounded to bf16, 0.0017
    of their norm, which is more than a decay formed in bf16 moves them."""
    return scan_answers(reference_scan(), as_float32(args), cotangent)


def scan_gaps_of(scan, args, cotangent, exact: dict) -> dict:
    """``scan``'s distance from ``exact`` (``exact_scan_answers`` of ``args``)
    on ``args`` as they are (keys ``scan/...``) and on the same numbers held
    as float32 (``scan32/...``)."""
    return {prefix + k: v
            for prefix, given in (("scan/", args), ("scan32/", as_float32(args)))
            for k, v in grad_gaps(scan_answers(scan, given, cotangent), exact).items()}


def program_scan():
    """(x, dt, A, B, C, D) -> the program's own chunked scan, as the mixer
    calls it."""
    from shuffle_exchange_tpu.ops.ssd import ssd_chunked

    return ssd_chunked


def reference_scan():
    """The same of the reference's ``scan`` (looked up when called: the band
    script swaps its pieces), one row at a time in float32."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_nemotron3 as ref

    f32 = jnp.float32

    def scan(x, dt, A, B, C, D):
        row = lambda one: ref.scan(one[0][None].astype(f32), one[1][None], A,
                                   one[2][None].astype(f32), one[3][None].astype(f32), D)[0]
        return jax.lax.map(jax.checkpoint(row), (x, dt, B, C))

    return scan


def weight_gap(got_weight, got_tokens, want_weight, want_tokens, min_tokens: int = 0):
    """``train_steps_mla.weight_gap`` (the mean weight of a token-choice, expert
    by expert of every routed layer, the difference's norm over the
    reference's) over the experts that received at least ``min_tokens``
    token-choices on BOTH sides -> (the gap, or None where no expert did; the
    experts left out). An expert that one token chose, or none, has a mean that
    is a single flip's doing: 128 sigmoid scores over Zipf ids at their initial
    draw leave some experts that bare, and one that received a choice on one
    side and none on the other reads 0 against 0.4, 0.043 of the norm alone
    (seed 2147488001, one run in 38: my chip runs, PR 46), where a weighed bias
    reads 0.020 over all of them."""
    import numpy as np

    got_n, want_n = (np.asarray(n, np.float64) for n in (got_tokens, want_tokens))
    mean = lambda w, n: np.asarray(w, np.float64) / np.maximum(n, 1.0)
    read = np.minimum(got_n, want_n) >= min_tokens
    want = mean(want_weight, want_n)[read]
    left_out = int(read.size - read.sum())
    if not want.size:
        return None, left_out
    return float(np.linalg.norm(mean(got_weight, got_n)[read] - want)
                 / max(np.linalg.norm(want), 1e-30)), left_out


def step_holds_scan_kernels():
    """Whether the COMPILED train step's scans are the kernels, read off the
    program the engine registered with the tracer (``engine.compile``): an
    instruction of it is the backward kernel's launch (``ssd_bwd`` in its
    ``op_name``; interpreted on a CPU, that too). None where no step is
    registered."""
    from shuffle_exchange_tpu.profiling import trace

    ops = trace.registered_ops("train_step")
    if ops is None:
        return None
    return any("ssd_bwd" in op.scope for op in ops.values())


def program_routes(mcfg, batch: int, seq: int, dtype) -> dict:
    """What the program says it runs at the cell's shapes (``ssd``,
    ``attn_core``, ``grouped_gemm``) and whether the compiled step's scans
    are the kernels (``ssd_step_kernels``)."""
    import jax

    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled
    from shuffle_exchange_tpu.ops.flash_attention import attention_route
    from shuffle_exchange_tpu.ops.ssd import ssd_chunks, ssd_route

    shape = lambda heads: jax.ShapeDtypeStruct((batch, seq, heads, mcfg.head_dim), dtype)
    return {"grouped_gemm": "megablox" if pallas_enabled() else "ragged_dot",
            "attn_core": attention_route(shape(mcfg.n_heads), shape(mcfg.kv_heads),
                                         shape(mcfg.kv_heads), impl=mcfg.attention_impl),
            "ssd": ssd_route(
                jax.ShapeDtypeStruct((batch, seq, mcfg.ssm_heads, mcfg.ssm_head_dim), dtype),
                jax.ShapeDtypeStruct((batch, seq, mcfg.ssm_groups, mcfg.ssm_state), dtype)),
            "ssd_step_kernels": step_holds_scan_kernels(),
            "ssd_chunks_a_sequence": ssd_chunks(seq)}


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``train_steps_mla.failed_checks``'s keys
    (``losses``, ``first_loss_again``, ``reference_loss``, ``route_gap``,
    ``held_gap``, ``counters_add_up``, ``overflow``, ``grad_gaps``,
    ``bias_grad``, ``bias_update_gap``, ``router_gaps``, ``weight_gap``),
    ``mixer_gaps`` (keys ``ssm/...``), ``scan_gaps`` (keys ``scan/...`` and
    ``scan32/...``) and ``routes`` (``ssd``, ``ssd_alone``, ``ssd_step_kernels``).
    The band script hands it a wrong model's or a lower precision's answers in
    the program's place."""
    vals = got["losses"]
    loss_tol, route_tol, grad_tol, router_tol, weight_tol, mixer_tol, state_tol, decay_tol = (
        float(traffic[k]) for k in ("loss_tol", "route_tol", "grad_tol", "router_tol",
                                    "weight_tol", "mixer_tol", "state_tol", "decay_tol"))
    routed_tol = float(traffic.get("grad_tol_routed", grad_tol))
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    tol_of = lambda leaf: routed_tol if is_routed(leaf) else grad_tol
    over = {leaf: gap / tol_of(leaf) for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))         # a NaN gap is the worst of all
    part = max(got["router_gaps"], key=nan_last(got["router_gaps"]))
    piece = max(got["mixer_gaps"], key=nan_last(got["mixer_gaps"]))
    scan_of = lambda prefix: {k: v for k, v in got["scan_gaps"].items()
                              if k.startswith(prefix)} or {prefix + "y": math.inf}
    rounded, wide = scan_of("scan/"), scan_of("scan32/")
    scanned = max(rounded, key=nan_last(rounded))
    decayed = max(wide, key=nan_last(wide))
    weighed = got["weight_gap"]
    routes = got["routes"]
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
         f"{weight_tol} (None: the program handed out no moe_expert_weight, or "
         f"no expert received weight_min_tokens token-choices)"),
        (got["mixer_gaps"][piece] <= mixer_tol,
         f"the state-space mixer alone: {piece} differs from the reference's by "
         f"{got['mixer_gaps'][piece]:.3g} of its norm: more than {mixer_tol} "
         f"(the gate after the norm, one norm over the whole width, B and C of "
         f"another group, a clamped step, a missing bias or skip read so)"),
        (rounded[scanned] <= state_tol,
         f"the scan alone: {scanned} differs from the recurrence's by "
         f"{rounded[scanned]:.3g} of its norm: more than {state_tol} "
         f"(a state kept below float32 reads so)"),
        (wide[decayed] <= decay_tol,
         f"the scan alone on float32 operands: {decayed} differs from the "
         f"recurrence's by {wide[decayed]:.3g} of its norm: more than "
         f"{decay_tol} (a decay's exponent formed below float32, a state kept "
         f"below float32 read so; inf: no such reading)"),
        (routes["ssd_alone"] == routes["ssd"]
         and routes["ssd_step_kernels"] == (routes["ssd"] != "xla"),
         f"the scan alone ran as {routes['ssd_alone']!r} and the compiled step "
         f"{'holds' if routes['ssd_step_kernels'] else 'does not hold'} the scan's "
         f"kernels where the program says {routes['ssd']!r} for the cell's shapes: "
         f"state_tol and decay_tol then read another form than the timed step runs"),
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
    from chipbench import reference_nemotron3 as ref
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops.ssd import ssd_route

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_ssm holds the whole state on "
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
    inputs = mixer_inputs(drawn, src, seed, batch, seq, dtype)
    weights = to_source_names(drawn, src)
    del drawn
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    # the router alone, in float32 on both sides
    route_gaps = router_gaps(program_router(mcfg),
                             router_inputs(seed, batch * seq, mcfg.n_experts, bias_std),
                             reference_router(src))
    # the state-space mixer alone, in the trainer's compute dtype against float32
    mix_gaps = {"ssm/" + k: v for k, v in grad_gaps(
        mixer_answers(program_mixer(model), *inputs),
        mixer_answers(reference_mixer(src), *inputs)).items()}
    # the scan alone, on what the reference's pieces hand it
    scan_args, scan_cot = scan_inputs(inputs[0], inputs[1], src, seed, dtype)
    del inputs
    scanned_gaps = scan_gaps_of(program_scan(), scan_args, scan_cot,
                                exact_scan_answers(scan_args, scan_cot))
    # the route that reading took, on its own operands
    scan_route = ssd_route(scan_args[0], scan_args[3])
    del scan_args, scan_cot
    engine = sxt.initialize(model=model, params=initial_params(model, seed, bias_std),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    routes = {**program_routes(mcfg, batch, seq, dtype), "ssd_alone": scan_route}

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows",
                 "moe_visited_rows", "moe_expert_weight", "ssm_scan_chunks") if k in got}

    def bias_now():
        """The selection bias of every routed layer, in the counters' order
        ([routed layers, E]): read through the driver's own mapping."""
        named = to_source_names(engine.state.master, src)
        return np.stack([np.asarray(
            named[f"backbone.layers.{j}.mixer.gate.e_score_correction_bias"])
            for _, _, j in blocks(src) if j is not None])

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
                 router_gaps=route_gaps, mixer_gaps=mix_gaps, scan_gaps=scanned_gaps,
                 ssm_scan_chunks=int(first_stats.get("ssm_scan_chunks", 0)),
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
    hi = lo + int(src.get("num_experts_held") or src["n_routed_experts"])
    first_gap = held_gap = load = first_load = dropped = held_share = None
    held_rows_step = weighed = weighed_all = left_out = None
    overflow, counters_add_up = [None, None], False
    if have:
        first_gap = route_gap(first_stats["moe_expert_tokens"],
                              reference["expert_tokens"])
        held_gap = route_gap(first_stats["moe_held_rows"], reference["held_rows"])
        if "moe_expert_weight" in first_stats:
            both_sides = (first_stats["moe_expert_weight"], first_stats["moe_expert_tokens"],
                          reference["expert_weight"], reference["expert_tokens"])
            weighed, left_out = weight_gap(*both_sides, int(traffic.get("weight_min_tokens", 0)))
            weighed_all = weight_gap(*both_sides)[0]    # every expert: printed, not held
        counters_add_up = all(
            s["moe_expert_tokens"].shape[0] == routed_layers
            and np.array_equal(s["moe_held_rows"] + s["moe_overflow_rows"],
                               s["moe_expert_tokens"][:, lo:hi].sum(axis=1))
            and np.array_equal(s["moe_expert_tokens"].sum(axis=1),
                               np.full(routed_layers, per_layer))
            for s in (first_stats, last_stats))
        overflow = [int(s["moe_overflow_rows"].sum()) for s in (first_stats, last_stats)]
        most = lambda counts: float((counts.max(axis=1) / counts.mean(axis=1)).max())
        load, first_load = (most(s["moe_expert_tokens"]) for s in (last_stats, first_stats))
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
         "weight_gap": weighed, "mixer_gaps": mix_gaps, "scan_gaps": scanned_gaps,
         "routes": routes},
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
                 first_step_weight_gap=weighed, first_step_weight_gap_all_experts=weighed_all,
                 first_step_weight_experts_left_out=left_out,
                 first_step_least_expert_tokens=(
                     int(first_stats["moe_expert_tokens"].min()) if have else None),
                 mixer_gaps=mix_gaps, scan_gaps=scanned_gaps,
                 first_step_bias_update_gap=bias_gap, first_step_bias_grad=bias_grad,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_visited_rows=[int(x) for x in last_stats.get("moe_visited_rows", ())],
                 moe_expert_load_max_over_mean=load,
                 first_step_load_max_over_mean=first_load,
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
                  "ssd_route": routes["ssd"],
                  "ssm_flops_per_token": None if held_rows_step is None else
                  arith_ssm.train_flops_per_token(
                      mcfg, seq, held_rows_step / (batch * seq))},
    }
