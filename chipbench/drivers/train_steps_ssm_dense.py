"""Training cells of a DENSE state-space / attention hybrid (Granite-4.0-H
shaped: Mamba-2 layers of one or a few groups beside grouped-query attention
that rotates nothing, a gated MLP in EVERY block, the family's four
multipliers, a tied head, no expert): ``train_steps_gdn``'s window
(``sxt.initialize(...).train_batch`` on a new seeded batch every step, steps
chained on the donated state, two in flight untraced, one at a time traced)
held to the benchmark's own plain float32 reference of the architecture
(``chipbench/reference_granite4h.py``: the recurrence token by token, whole
attention scores, the multipliers in float32).

The reference runs FIRST and alone on the chip, from the same initial weights
relaid under the source's names, one row at a time (each row, and inside it
each layer, each head and each block of the scan, is computed again in the
backward): the first batch's loss and by ``jax.grad`` the gradient, which
waits on the host. The trainer's first gradient is read out of Adam's first
moment after one update ((1 - beta1) x the gradient) and compared leaf by leaf
of the program's tree. ``correct`` = every loss finite, the loss fell, the
first loss within ``loss_tol``, every leaf's gradient within ``grad_tol`` of
the reference's norm (the TIED embedding, the sum of a lookup scaled by the
embedding's multiplier and a head divided by the logits', on ``grad_tol_embed``),
and the mechanisms ALONE where the whole model's band hides them:

  the scan (``state_tol``, ``decay_tol``)  ``train_steps_ssm``'s two readings
      at this cell's groups: ``ops.ssd.ssd_chunked`` on the x, B, C, step and
      decay the reference's own pieces make of a seeded input, against the
      recurrence in float32, as they are (``scan/...``: a state kept in bf16
      reads past ``state_tol``) and held as float32 (``scan32/...``: a decay
      formed in bf16 reads past ``decay_tol``).
  the gated norm (``stat_tol``)  ``ops.ssm_gate_norm.ssm_gate_norm`` on the
      route the step runs, on seeded o, x, z [rows, inner] in the compute
      dtype whose scale varies by token, against the same numbers gated and
      normed in float32: ``train_steps_gdn.stat_gap``'s reading, the root mean
      square over the tokens of ``<y, y_ref> / <y_ref, y_ref> - 1``. A rounded
      output averages out over a token's channels; a rounded STATISTIC scales
      the whole token.
  the attention layer (``attn_tol``)  ``Transformer._gqa`` at the
      configuration's scale on the seed's attention leaves, a seeded normed
      input and cotangent, against the reference's ``attention``: the output,
      the input's gradient and every leaf's (``attn/...``). 1 / sqrt(head
      size) for the multiplier reads here.

and the program's ``ssm_scan_chunks`` counter equal to chunks a sequence x
sequences x state-space layers (0 or None: not correct), the stand-alone scan
on the route the program states, and the COMPILED step bearing the routes out
(``trace.registered_ops``: ``ssd_bwd`` and ``ssm_conv_bwd`` among its
instructions where the route is not "xla", ``ssm_gate_norm_bwd`` likewise).

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm, the gated norm's) and the skip ``D`` are drawn from
[0.5, 1.5): at their initial 1 a model that leaves them out computes the same
function.

Traffic parameters: ``train_steps``', ``loss_tol``, ``grad_tol``,
``grad_tol_embed``, ``state_tol``, ``decay_tol``, ``stat_tol``, ``attn_tol``.
``chipbench/granite4h_band.py`` measures the band they are set from and runs
every wrong model and lower precision through ``failed_checks`` below, in the
program's place.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_granite4h, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_gdn import host_gaps
from chipbench.drivers.train_steps_mla import mixer_answers
from chipbench.drivers.train_steps_moe import grad_gaps
from chipbench.drivers.train_steps_ssm import (as_float32, program_scan, scan_answers,
                                               scan_gaps_of)

_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight"}
_BLOCK = {"ln1_w": "input_layernorm.weight", "ln2_w": "post_attention_layernorm.weight",
          "w_down": "shared_mlp.output_linear.weight"}
_MIXER = {"ssm": {"ssm_w_in": "mamba.in_proj.weight", "ssm_conv_w": "mamba.conv1d.weight",
                  "ssm_conv_b": "mamba.conv1d.bias", "ssm_dt_bias": "mamba.dt_bias",
                  "ssm_A_log": "mamba.A_log", "ssm_D": "mamba.D",
                  "ssm_norm_w": "mamba.norm.weight", "ssm_w_out": "mamba.out_proj.weight"},
          "attn": {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
                   "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight"}}
# the source's ONE input matrix of the gated MLP holds the program's two
_GATE_UP = "shared_mlp.input_linear.weight"
GAINS = ("ln1_w", "ln2_w", "ssm_norm_w", "ssm_D")
SCAN_KERNEL, CONV_KERNEL, NORM_KERNEL = "ssd_bwd", "ssm_conv_bwd", "ssm_gate_norm_bwd"


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def layer_types(src: dict) -> list:
    types = list(src["layer_types"])
    return [types[int(i)] for i in src.get("layers_held") or range(int(src["num_hidden_layers"]))]


def layer_places(src: dict) -> list:
    """[(mixer, kind's name, (period, index among the kind's layers of the
    period))] a layer held here, as the program stacks them
    (``Transformer.slots``; written out here so that the mapping does not move
    with the program)."""
    mixers = [{"mamba": "ssm", "attention": "attn"}[t] for t in layer_types(src)]
    period = next(p for p in range(1, len(mixers) + 1) if len(mixers) % p == 0
                  and mixers[:p] * (len(mixers) // p) == mixers)
    return [(m, m + "_mlp", (j // period, sum(1 for k in mixers[j - j % period:j] if k == m)))
            for j, m in enumerate(mixers)]


def _relaid(leaf: str, x):
    """One tensor between the program's layout and torch's: a matrix is
    [in, out] here and [out, in] there (the embedding [V, D] on both sides);
    the taps are [K, C] here and [C, 1, K] there."""
    if leaf == "ssm_conv_w":
        return x.T[:, None, :] if x.ndim == 2 else x[:, 0, :].T
    return x.T if x.ndim == 2 and leaf != "embed" else x


def to_source_names(params: dict, src: dict) -> dict:
    """The program's tree -> a flat dict under the source's names, each tensor
    as torch stores it. Stays on the device; float32 as the master is."""
    import jax.numpy as jnp

    out = {name: params[leaf] for leaf, name in _TOP.items()}
    for i, (mixer, kind, at) in enumerate(layer_places(src)):
        leaves = {k: v[at] for k, v in params["layers"][kind].items()}
        p = f"model.layers.{i}."
        for leaf, name in {**_BLOCK, **_MIXER[mixer]}.items():
            out[p + name] = _relaid(leaf, leaves[leaf])
        out[p + _GATE_UP] = jnp.concatenate([leaves["w_gate"].T, leaves["w_up"].T], axis=0)
    return out


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat and on the HOST (numpy):
    {"/".join(path): the program's stacked array}."""
    import numpy as np

    named = {k: np.asarray(v) for k, v in named.items()}
    out = {leaf: named[name] for leaf, name in _TOP.items()}
    cells = {}
    for i, (mixer, kind, at) in enumerate(layer_places(src)):
        p = f"model.layers.{i}."
        leaves = {leaf: _relaid(leaf, named[p + name])
                  for leaf, name in {**_BLOCK, **_MIXER[mixer]}.items()}
        gate_up = named[p + _GATE_UP]
        F = gate_up.shape[0] // 2
        leaves["w_gate"], leaves["w_up"] = gate_up[:F].T, gate_up[F:].T
        for leaf, x in leaves.items():
            cells.setdefault(f"layers/{kind}/{leaf}", {})[at] = x
    for path, at in cells.items():
        periods = 1 + max(p for p, _ in at)
        each = 1 + max(j for _, j in at)
        out[path] = np.stack([np.stack([at[p, j] for j in range(each)])
                              for p in range(periods)])
    return out


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed`` with the gains and the skip redrawn (the
    module's docstring says why)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    draw = lambda x: jax.random.uniform(next(keys), x.shape, jnp.float32, 0.5, 1.5)
    params["ln_f_w"] = draw(params["ln_f_w"])
    for kind in sorted(params["layers"]):
        leaves = params["layers"][kind]
        for name in sorted(leaves):
            if name in GAINS:
                leaves[name] = draw(leaves[name])
    return params


def reference_program(src: dict, dtype=None):
    """The reference on a whole batch as ONE jitted program that takes one
    row at a time (``lax.map``): (weights, ids [B, T + 1]) -> (loss, d loss /
    d weights under the source's names). ``dtype``: the band's lower
    precision."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_granite4h as ref

    dtype = dtype or jnp.float32

    def batch_loss(w, ids):
        row = lambda one: ref.loss(w, src, one[None], dtype, True)
        return jax.lax.map(jax.checkpoint(row), ids).mean()

    return jax.jit(jax.value_and_grad(batch_loss))


def reference_first_step(program, weights: dict, ids, src: dict) -> dict:
    """``reference_program``'s answer on the HOST, the gradient in the
    program's layout."""
    import jax

    loss, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "grads": from_source_names(grads, src)}


# -- the mechanisms alone -------------------------------------------------------


def first_leaves(params: dict, src: dict, mixer: str, dtype) -> dict:
    """The mixer leaves of the seed's first layer whose mixer is ``mixer``,
    rounded to ``dtype`` as the trainer hands them over."""
    _, kind, at = next(p for p in layer_places(src) if p[0] == mixer)
    return {name: params["layers"][kind][name][at].astype(dtype) for name in _MIXER[mixer]}


def _named(lw: dict, mixer: str) -> dict:
    """One mixer's leaves under the source's names, float32, prefix ``a.``."""
    import jax.numpy as jnp

    return {"a." + _MIXER[mixer][k]: _relaid(k, v.astype(jnp.float32)) for k, v in lw.items()}


def mixer_input(seed: int, batch: int, seq: int, width: int, dtype):
    """(x [B, T, D] a standard normal as a normed residual is, rounded to
    ``dtype``; a float32 cotangent of its shape), from ``seed``."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed + 3), 2)
    x = jax.random.normal(keys[0], (batch, seq, width), jnp.float32)
    return x.astype(dtype), jax.random.normal(keys[1], x.shape, jnp.float32)


def scan_inputs(lw: dict, x, src: dict, seed: int, dtype):
    """``train_steps_ssm.scan_inputs`` under this family's keys: ((x, dt, A,
    B, C, D), cotangent) for the scan alone, what the reference's own pieces
    make of the mixer's input before the scan."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_granite4h as ref

    f32 = jnp.float32
    H, P = src["mamba_n_heads"], src["mamba_d_head"]
    G, N = src["mamba_n_groups"], src["mamba_d_state"]
    inner = H * P

    def pieces(lw, x):
        named = _named(lw, "ssm")
        with jax.default_matmul_precision("highest"):
            _, xbc, dt = ref.mamba_split(
                ref.linear(x.astype(f32), named["a.mamba.in_proj.weight"]), src)
        xbc = jax.nn.silu(ref.taps_sum(xbc, named["a.mamba.conv1d.weight"],
                                       named["a.mamba.conv1d.bias"]))
        step, A = ref.step_and_decay(dt, named["a.mamba.dt_bias"], named["a.mamba.A_log"])
        B_, T = x.shape[:2]
        return (xbc[..., :inner].reshape(B_, T, H, P).astype(dtype), step, A,
                xbc[..., inner:inner + G * N].reshape(B_, T, G, N).astype(dtype),
                xbc[..., inner + G * N:].reshape(B_, T, G, N).astype(dtype),
                named["a.mamba.D"])

    args = jax.jit(pieces)(lw, x)
    return args, jax.random.normal(jax.random.PRNGKey(seed + 5), args[0].shape, f32)


def reference_scan():
    """The reference's ``scan`` (looked up when called: the band script swaps
    its pieces), one row at a time in float32."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_granite4h as ref

    f32 = jnp.float32

    def scan(x, dt, A, B, C, D):
        row = lambda one: ref.scan(one[0][None].astype(f32), one[1][None], A,
                                   one[2][None].astype(f32), one[3][None].astype(f32), D)[0]
        return jax.lax.map(jax.checkpoint(row), (x, dt, B, C))

    return scan


def exact_scan_answers(args, cotangent) -> dict:
    """``scan_answers`` of the reference's recurrence on ``args`` held as
    float32 (``train_steps_ssm.exact_scan_answers``)."""
    return scan_answers(reference_scan(), as_float32(args), cotangent)


def norm_inputs(seed: int, rows: int, mcfg, dtype):
    """(o, x, z [1, rows, inner] in ``dtype``, D [heads], gain [inner]) for
    the gated norm alone, from ``seed``: normal draws whose scale varies by
    token (a log-uniform factor between 1/4 and 4), D and the gain from
    [0.5, 1.5)."""
    import jax
    import jax.numpy as jnp

    inner = mcfg.ssm_heads * mcfg.ssm_head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 6)
    scale = jnp.exp(jax.random.uniform(keys[0], (1, rows, 1), jnp.float32,
                                       -math.log(4.0), math.log(4.0)))
    draw = lambda key: (scale * jax.random.normal(key, (1, rows, inner), jnp.float32)
                        ).astype(dtype)
    gain = lambda key, n: jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5)
    return (draw(keys[1]), draw(keys[2]),
            jax.random.normal(keys[3], (1, rows, inner), jnp.float32).astype(dtype),
            gain(keys[4], mcfg.ssm_heads), gain(keys[5], inner))


def program_gate_norm(mcfg):
    """The epilogue as the program's state-space mixer calls it, on the route
    the step runs: (o, x, z, D, gain) -> o's dtype."""
    from shuffle_exchange_tpu.ops.ssm_gate_norm import ssm_gate_norm

    return lambda o, x, z, D, gain: ssm_gate_norm(o, x, z, D, gain, mcfg.ssm_groups,
                                                  mcfg.norm_eps)


def stat_gap(norm, inputs, groups: int, eps: float) -> float:
    """How far ``norm``'s per-token SCALE sits from the float32 gated norm's
    on the same numbers: rms over the tokens (and groups) of <y, y_ref> /
    <y_ref, y_ref> - 1."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def gap(o, x, z, D, gain):
        y = norm(o, x, z, D, gain).astype(f32)
        inner = o.shape[-1]
        u = (o.astype(f32) + jnp.repeat(D, inner // D.shape[0]) * x.astype(f32)
             ) * jax.nn.silu(z.astype(f32))
        g = u.reshape(u.shape[:2] + (groups, inner // groups))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        ref = (g.reshape(u.shape) * gain).reshape(g.shape)
        y = y.reshape(g.shape)
        scale = jnp.sum(y * ref, axis=-1) / jnp.sum(ref * ref, axis=-1) - 1.0
        return jnp.sqrt(jnp.mean(scale * scale))

    return float(jax.jit(gap)(*inputs))


def program_attention(model):
    """(leaves, x) -> the program's own attention mixer among several kinds
    (``Transformer._gqa``: projections, the configuration's scale, the route
    the timed step runs; nothing rotates)."""
    return lambda lw, x: model._gqa(lw, x, (None, None))


def reference_attention(src: dict, dtype=None):
    """The same of the reference's ``attention`` (looked up when called), one
    row at a time in float32 at highest precision; ``dtype``: in that one."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_granite4h as ref

    def mixer(lw, x):
        named = _named(lw, "attn")
        row = lambda one: ref.attention(named, "a.self_attn.",
                                        one[None].astype(dtype or jnp.float32), src, True)[0]
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(jax.checkpoint(row), x)

    return mixer


def attention_gaps(mixer, lw, x, cotangent, exact: dict) -> dict:
    """{"attn/y", "attn/dx", "attn/dwq", ...}: ``mixer``'s distance from
    ``exact`` (``mixer_answers`` of the reference), shares of its norms."""
    return {"attn/" + k: v
            for k, v in grad_gaps(mixer_answers(mixer, lw, x, cotangent), exact).items()}


# -- what the program says it runs ----------------------------------------------


def step_kernels() -> dict:
    """{kernel: whether its launch is among the COMPILED train step's
    instructions}, read off the program the engine registered with the tracer
    (``engine.compile``); None where no step is registered."""
    from shuffle_exchange_tpu.profiling import trace

    ops = trace.registered_ops("train_step")
    if ops is None:
        return None
    scopes = [op.scope for op in ops.values()]
    return {kernel: any(kernel in scope for scope in scopes)
            for kernel in (SCAN_KERNEL, CONV_KERNEL, NORM_KERNEL)}


def program_routes(mcfg, batch: int, seq: int, dtype) -> dict:
    """What the program's route functions say at the cell's shapes (``ssd``,
    ``ssm_conv``, ``ssm_gate_norm``, ``attn_core``) and which kernels the
    compiled step holds (``step_kernels``)."""
    import jax

    from shuffle_exchange_tpu.ops.flash_attention import attention_route
    from shuffle_exchange_tpu.ops.ssd import ssd_chunks, ssd_route
    from shuffle_exchange_tpu.ops.ssm_conv import ssm_conv_route
    from shuffle_exchange_tpu.ops.ssm_gate_norm import ssm_gate_norm_route

    H, P, G, N = mcfg.ssm_heads, mcfg.ssm_head_dim, mcfg.ssm_groups, mcfg.ssm_state
    inner = H * P
    shape = lambda *s: jax.ShapeDtypeStruct(s, dtype)
    heads = lambda n: shape(batch, seq, n, mcfg.head_dim)
    return {"ssd": ssd_route(shape(batch, seq, H, P), shape(batch, seq, G, N)),
            "ssm_conv": ssm_conv_route(shape(batch, seq, 2 * inner + 2 * G * N + H),
                                       shape(mcfg.ssm_conv_kernel, inner + 2 * G * N),
                                       inner, (inner, G * N, G * N)),
            "ssm_gate_norm": ssm_gate_norm_route(shape(batch, seq, inner), G),
            "attn_core": attention_route(heads(mcfg.n_heads), heads(mcfg.kv_heads),
                                         heads(mcfg.kv_heads), impl=mcfg.attention_impl),
            "step_kernels": step_kernels(),
            "ssd_chunks_a_sequence": ssd_chunks(seq)}


def is_embedding(leaf: str) -> bool:
    """The tied embedding: what ``grad_tol_embed`` is for."""
    return leaf == "embed"


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``reference_loss``, ``grad_gaps`` {leaf: share of the reference's
    norm}, ``scan_gaps`` (keys ``scan/...`` and ``scan32/...``), ``stat_gap``,
    ``attn_gaps`` (keys ``attn/...``), ``scan_chunks`` and
    ``scan_chunks_expected``, ``routes`` (``ssd``, ``ssm_conv``,
    ``ssm_gate_norm``, ``ssd_alone``, ``step_kernels``). The band script hands
    it a wrong model's or a lower precision's answers in the program's place."""
    vals = got["losses"]
    loss_tol, grad_tol, state_tol, decay_tol, stat_tol, attn_tol = (
        float(traffic[k]) for k in ("loss_tol", "grad_tol", "state_tol", "decay_tol",
                                    "stat_tol", "attn_tol"))
    embed_tol = float(traffic.get("grad_tol_embed", grad_tol))
    limit = lambda leaf: embed_tol if is_embedding(leaf) else grad_tol
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    over = {leaf: gap / limit(leaf) for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))          # a NaN gap is the worst of all
    scan_of = lambda prefix: {k: v for k, v in got["scan_gaps"].items()
                              if k.startswith(prefix)} or {prefix + "y": math.inf}
    rounded, wide = scan_of("scan/"), scan_of("scan32/")
    scanned = max(rounded, key=nan_last(rounded))
    decayed = max(wide, key=nan_last(wide))
    attended = max(got["attn_gaps"], key=nan_last(got["attn_gaps"]))
    routes = got["routes"]
    kernels = routes.get("step_kernels")
    borne_out = kernels is None or all(
        kernels[kernel] == (routes[route] != "xla")
        for route, kernel in (("ssd", SCAN_KERNEL), ("ssm_conv", CONV_KERNEL),
                              ("ssm_gate_norm", NORM_KERNEL)))
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - got["reference_loss"]) <= loss_tol,
         f"first loss {vals[0]} vs the float32 reference {got['reference_loss']}: "
         f"off by more than {loss_tol}"),
        (len(vals) == 1 or sum(tail) / len(tail) < vals[0],
         f"loss did not fall: first {vals[0]}, mean of the last {len(tail)} "
         f"{sum(tail) / len(tail)}"),
        (over[worst] <= 1.0,
         f"first step's gradient of {worst} differs from the reference's by "
         f"{got['grad_gaps'][worst]:.5f} of its norm: more than {limit(worst)} (1 = "
         f"the optimizer's state held no first moment to read it from)"),
        (rounded[scanned] <= state_tol,
         f"the scan alone: {scanned} differs from the float32 recurrence's by "
         f"{rounded[scanned]:.5f} of its norm: more than {state_tol} (a state kept "
         f"below float32 reads so)"),
        (wide[decayed] <= decay_tol,
         f"the scan alone on float32 operands: {decayed} differs from the float32 "
         f"recurrence's by {wide[decayed]:.2e} of its norm: more than {decay_tol} (a "
         f"decay formed below float32 reads so)"),
        (got["stat_gap"] <= stat_tol,
         f"the gated norm alone: its per-token scale differs from the float32 "
         f"norm's by {got['stat_gap']:.2e} (rms over tokens): more than {stat_tol} "
         f"(a statistic formed below float32, or over other channels, reads so)"),
        (got["attn_gaps"][attended] <= attn_tol,
         f"the attention layer alone: {attended} differs from the reference's by "
         f"{got['attn_gaps'][attended]:.5f} of its norm: more than {attn_tol} (another "
         f"scale than the configuration's attention_multiplier reads so)"),
        (bool(got["scan_chunks"]) and got["scan_chunks"] == got["scan_chunks_expected"],
         f"the program's ssm_scan_chunks counter reads {got['scan_chunks']!r}, not "
         f"chunks a sequence x sequences x state-space layers = "
         f"{got['scan_chunks_expected']}"),
        (routes.get("ssd_alone") == routes["ssd"],
         f"the stand-alone scan ran route {routes.get('ssd_alone')!r}, the program "
         f"states {routes['ssd']!r} for the cell's shapes"),
        (borne_out,
         f"the compiled step's kernels {kernels} do not bear out the routes the "
         f"program states ({ {k: routes[k] for k in ('ssd', 'ssm_conv', 'ssm_gate_norm')} })"),
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
    from shuffle_exchange_tpu.ops.ssd import ssd_chunks, ssd_route

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
    params = initial_params(model, seed)
    # the mechanisms alone, at one chip's rows of the cell's own shape
    x, cot = mixer_input(seed, per_chip, seq, mcfg.d_model, dtype)
    scan_args, scan_cot = scan_inputs(first_leaves(params, src, "ssm", dtype), x, src,
                                      seed, dtype)
    scanned_gaps = scan_gaps_of(program_scan(), scan_args, scan_cot,
                                exact_scan_answers(scan_args, scan_cot))
    ssd_alone = ssd_route(scan_args[0], scan_args[3])
    del scan_args, scan_cot
    norm_gap = stat_gap(program_gate_norm(mcfg), norm_inputs(seed, seq, mcfg, dtype),
                        mcfg.ssm_groups, mcfg.norm_eps)
    attn_lw = first_leaves(params, src, "attn", dtype)
    attn_gaps = attention_gaps(
        program_attention(model), attn_lw, x, cot,
        mixer_answers(reference_attention(src), attn_lw, x, cot))
    del x, cot, attn_lw

    engine = sxt.initialize(model=model, params=params, config=config, seed=seed)[0]
    del params
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(a.size) for a in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)

    losses = [engine.train_batch(first)]
    chunks = engine.last_step_stats().get("ssm_scan_chunks")
    chunks = None if chunks is None else int(np.asarray(chunks))
    expected = ssd_chunks(seq) * batch * mcfg.ssm_layers
    beta1 = settings["train_config"]["optimizer"]["params"].get("betas", (0.9,))[0]
    moment = first_moment(engine.state.opt_state)
    first_gaps = ({leaf: 1.0 for leaf in reference["grads"]} if moment is None
                  else host_gaps(moment, reference.pop("grads"), 1.0 / (1.0 - beta1)))
    del moment
    for _ in range(max(1, int(traffic["warmup_steps"])) - 1):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    routes = {**program_routes(mcfg, per_chip, seq, dtype), "ssd_alone": ssd_alone}
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 routes=routes, ssm_scan_chunks=chunks,
                 ssm_scan_chunks_expected=expected,
                 multipliers={"embed_scale": mcfg.embed_scale,
                              "residual_scale": mcfg.residual_scale,
                              "attn_scale": mcfg.attn_scale,
                              "logit_divisor": mcfg.logit_divisor},
                 remat=[mcfg.remat, mcfg.remat_policy],
                 reference_loss=reference["loss"], first_loss=float(losses[0]),
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
    vals = [float(v) for v in losses + window_losses]
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    failed = failed_checks(
        {"losses": vals, "reference_loss": reference["loss"],
         "grad_gaps": first_gaps, "scan_gaps": scanned_gaps, "stat_gap": norm_gap,
         "attn_gaps": attn_gaps, "scan_chunks": chunks,
         "scan_chunks_expected": expected, "routes": routes},
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
                 first_step_grad_gap_embed=first_gaps.get("embed"),
                 first_step_grad_gap_others=max(
                     (g for leaf, g in first_gaps.items() if not is_embedding(leaf)),
                     default=None),
                 first_step_grad_gaps=first_gaps,
                 scan_gaps=scanned_gaps, stat_gap=norm_gap, attn_gaps=attn_gaps,
                 ssm_scan_chunks=chunks, traced_steps=traced_steps,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    return {
        "correct": not failed, "attempted": steps,
        "failed": sum(1 for v in vals[len(losses):] if not math.isfinite(v)),
        "end_to_end": {"train_tokens_per_s_chip": per_chip_rate},
        "window_s": window_s, "program_bytes": step_bytes,
        "counters": {"compiles_in_window": in_win["programs_compiled"],
                     "steps": steps, "ssm_scan_chunks": chunks},
        "facts": {"model_cfg": mcfg, "seq": seq, "batch": batch,
                  "chips": chips, "tokens_per_step": batch * seq,
                  "step_s": [b - a for a, b in spans.named("train_step")],
                  "traced_steps": traced_steps,
                  "ssd_route": routes["ssd"],
                  "ssm_gate_norm_route": routes["ssm_gate_norm"],
                  "granite4h_flops_per_token":
                      arith_granite4h.train_flops_per_token(mcfg, seq)},
    }
