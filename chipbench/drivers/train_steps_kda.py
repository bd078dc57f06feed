"""Training cells of a KDA / latent-attention sparse stack (Kimi Linear
shaped: mixers that run the delta rule with a decay a key channel, three to
one latent-attention mixer that rotates nothing, a leading dense layer, a
sigmoid router with a selection bias, an ungated shared expert, one
expert-parallel rank's share of the routed experts): ``train_steps_mla``'s
window (``sxt.initialize(...).train_batch`` on a new seeded batch every step,
steps chained on the donated state, two in flight untraced, one at a time
traced) held to the benchmark's own plain float32 reference of the
architecture (``chipbench/reference_kimilinear.py``: the rule one token at a
time, attention as a masked softmax a block of queries at a time, a loop over
the held experts).

As in ``train_steps_mla`` the reference runs FIRST and alone on the chip, from
the same initial weights relaid under the source's names, one row at a time,
and ``correct`` holds the timed path's own first step to it: every check of
``train_steps_mla.failed_checks`` (the first loss within ``loss_tol``, the
expert counts over ALL the router's experts and the held rows within
``route_tol``, every leaf's gradient out of Adam's first moment within
``grad_tol`` / ``grad_tol_routed`` of the reference's norm, the counters adding
up, nothing dropped, the selection bias a buffer that takes the aux-free
update and nothing of the optimizer's, the router ALONE within ``router_tol``,
the routed layers' mean weights within ``weight_tol``, the latent-attention
mixer ALONE within ``mixer_tol``), and beside them:

The rule alone. At the init's decays a state keeps a few dozen tokens and
through the whole model nothing of its arithmetic shows. So one reading takes
the rule alone, at the decays a trained model has: the function the program's
layer calls (``ops.kda.kda_chunked``, on the route of the timed step) on
seeded q, k, v of the cell's own shape in the trainer's compute dtype and a
log-decay g [B, T, H, dk] whose memory is log-uniform between 64 and 4,096
tokens A KEY CHANNEL (``rule_inputs``), against the reference's token-by-token
``delta_rule`` in float32: the output and the five gradients, dg per channel,
each as a share of the reference's norm (``rule_gaps``; ``rule_tol``). The
scalar rule in KDA's place (g's mean over the channels), a rule without decay
or without beta, a state or a cumulated decay kept in bf16 read several times
the program's distance there.

The KDA mixer alone. ``Transformer._kda`` (the projections, the three
convolutions, the l2 norms, beta, the decay's low-rank pair, the rule, the
per-head norm and the sigmoid gate's low-rank pair) on the seed's leading
layer's leaves, a seeded normed input and cotangent of the cell's shape in
the compute dtype, against the reference's ``kda`` in float32: the output, the
input's gradient and every leaf's (``kda_mixer_gaps``; ``kda_mixer_tol``). No
l2 norm, a SiLU for the gate's sigmoid, a missing convolution read there.

What the step says of itself: ``rope_layers_rotated`` has to read 0 (no layer
of this stack is handed a table) and ``kda_layers`` the configuration's count;
``kda_decay_mean`` / ``kda_decay_min`` (what a state's row keeps over a chunk
of 64 tokens in the timed steps) are reported, not judged.

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm, the latent's norm, the KDA output's) is drawn from [0.5,
1.5) and the selection bias from a normal of ``select_bias_std`` (traffic
file): at their initial 1 and 0 a model that leaves them out computes the
same function.

Traffic parameters: ``train_steps_mla``'s, ``rule_tol`` and ``kda_mixer_tol``.
``chipbench/kimilinear_band.py`` measures the band the tolerances are set
from, and runs every wrong model and lower precision through
``failed_checks`` below, in the program's place. ``routes`` in the ``setup``
line is what the program says it runs (``ops.kda.kernel_route``,
``ops.ssm_conv.ssm_conv_route``, ``ops.flash_attention.attention_route``), not
a restatement.
"""

from __future__ import annotations

import math
import time

from chipbench import arith_kda, harness
from chipbench.drivers import train_steps_mla as mla
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import (RULE_PARTS, first_moment, flat_tree,
                                                  rule_answers)
from chipbench.drivers.train_steps_mla import (is_routed, mixer_answers, program_router,  # noqa: F401 (the tests')
                                               router_gaps, router_inputs)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap

# the program's leaves under the source's names (a KDA layer's three
# projections and three convolutions are ONE leaf each here: ``_FUSED``)
_BLOCK = {"ln1_w": "input_layernorm.weight", "ln2_w": "post_attention_layernorm.weight"}
_KDA = {"kda_w_beta": "self_attn.b_proj.weight", "kda_w_fa": "self_attn.f_a_proj.weight",
        "kda_w_fb": "self_attn.f_b_proj.weight", "kda_w_ga": "self_attn.g_a_proj.weight",
        "kda_w_gb": "self_attn.g_b_proj.weight", "kda_A_log": "self_attn.A_log",
        "kda_dt_bias": "self_attn.dt_bias", "kda_norm_w": "self_attn.o_norm.weight",
        "kda_w_out": "self_attn.o_proj.weight"}
_FUSED = {"kda_w_qkv": "self_attn.{}_proj.weight", "kda_conv_w": "self_attn.{}_conv1d.weight"}
_MLA = {"mla_wq": "self_attn.q_proj.weight",
        "mla_wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
        "mla_kv_norm_w": "self_attn.kv_a_layernorm.weight",
        "mla_wkv_b": "self_attn.kv_b_proj.weight", "mla_wo": "self_attn.o_proj.weight"}
_DENSE = {"w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
          "w_down": "mlp.down_proj.weight"}
_ROUTED = {"moe_gate": "block_sparse_moe.gate.weight",
           "moe_select_bias": "block_sparse_moe.gate.e_score_correction_bias",
           "moe_shared_w_gate": "block_sparse_moe.shared_experts.gate_proj.weight",
           "moe_shared_w_up": "block_sparse_moe.shared_experts.up_proj.weight",
           "moe_shared_w_down": "block_sparse_moe.shared_experts.down_proj.weight"}
_PER_EXPERT = {"moe_w_gate": "w1.weight", "moe_w_up": "w3.weight", "moe_w_down": "w2.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}
GAINS = ("ln1_w", "ln2_w", "mla_kv_norm_w", "kda_norm_w")
MLA_LEAVES = tuple(_MLA)
KDA_LEAVES = tuple(_KDA) + tuple(_FUSED)


def source_config(cell: dict, rehearsal: dict) -> dict:
    """The source's own keys, as the reference reads them (a rehearsal brings
    a tiny one)."""
    return dict(rehearsal.get("source_config") or cell["config"])


def layer_kinds(src: dict) -> list:
    """[(mixer, ffn)] of layers 0 .. num_hidden_layers - 1, from the source's
    two lists (counted from 1) and ``first_k_dense_replace``."""
    kda = set(src["linear_attn_config"]["kda_layers"])
    lead = int(src.get("first_k_dense_replace", 0))
    return [("kda" if i + 1 in kda else "mla", "mlp" if i < lead else "moe")
            for i in range(src["num_hidden_layers"])]


def layer_slots(src: dict) -> list:
    """[(path of the layer's subtree in the program's tree, its index there)]
    layer by layer: the leading layers under ``lead`` [i], the others under
    ``layers/<mixer>_<ffn>`` [period, place among the period's layers of that
    kind], the period the shortest the routed layers repeat (written out here
    so that the mapping does not move with the program)."""
    kinds = layer_kinds(src)
    lead = int(src.get("first_k_dense_replace", 0))
    rest = kinds[lead:]
    period = next(n for n in range(1, len(rest) + 1)
                  if len(rest) % n == 0 and rest[:n] * (len(rest) // n) == rest)
    out = [(("lead",), (i,)) for i in range(lead)]
    one_kind = len(set(rest[:period])) == 1
    for j, kind in enumerate(rest):
        p, at = divmod(j, period)
        if one_kind:
            out.append((("layers",), (j,)))
        else:
            place = sum(1 for k in rest[p * period:p * period + at] if k == kind)
            out.append((("layers", "_".join(kind)), (p, place)))
    return out


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name, which of a fused leaf's three column blocks or None)] for
    every tensor of the model held here."""
    out = [((leaf,), (), name, None) for leaf, name in _TOP.items()]
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["num_experts"])
    for i, ((mixer, ffn), (top, at)) in enumerate(zip(layer_kinds(src), layer_slots(src))):
        p = f"model.layers.{i}."
        mine = {**_BLOCK, **(_KDA if mixer == "kda" else _MLA),
                **(_DENSE if ffn == "mlp" else _ROUTED)}
        out += [(top + (leaf,), at, p + theirs, None) for leaf, theirs in mine.items()]
        if mixer == "kda":
            out += [(top + (leaf,), at, p + theirs.format(n), j)
                    for leaf, theirs in _FUSED.items() for j, n in enumerate("qkv")]
        if ffn == "moe":
            out += [(top + (leaf,), at + (e,), f"{p}block_sparse_moe.experts.{first + e}.{theirs}",
                     None) for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


def _to_torch(path, x, part):
    """One tensor of the program's in torch's layout: a matrix [in, out] here
    is [out, in] there (the embedding [V, D] on both sides), the taps [K, C]
    here a depthwise Conv1d's [C, 1, K]; ``part``: the third of a fused
    leaf's columns."""
    if part is not None:
        n = x.shape[-1] // 3
        x = x[..., part * n:(part + 1) * n]
    if path[-1] == "kda_conv_w":
        return x.T[:, None, :]
    return x.T if x.ndim == 2 and path != ("embed",) else x


def _from_torch(path, x):
    if path[-1] == "kda_conv_w":
        return x[:, 0, :].T
    return x.T if x.ndim == 2 and path != ("embed",) else x


def to_source_names(params: dict, src: dict) -> dict:
    """The program's tree -> a flat dict under the source's names, each tensor
    as torch stores it. Stays on the device; float32 as the master is."""
    out = {}
    for path, index, name, part in source_names(src):
        leaf = params
        for key in path:
            leaf = leaf[key]
        out[name] = _to_torch(path, leaf[index], part)
    return out


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat: {"/".join(path): the program's stacked
    array}. A name ``named`` lacks (a wrong model without that tensor) counts
    as zeros of its neighbours' shape: a gradient that is not there."""
    import jax.numpy as jnp

    cells = {}
    for path, index, name, part in source_names(src):
        x = None if name not in named else _from_torch(path, named[name])
        at = cells.setdefault(path, {})
        if part is None:
            at[index] = x
        else:
            at.setdefault(index, [None] * 3)[part] = x
    for at in cells.values():
        for index, x in at.items():
            if isinstance(x, list):
                some = next((p for p in x if p is not None), None)
                at[index] = None if some is None else jnp.concatenate(
                    [jnp.zeros_like(some) if p is None else p for p in x], axis=-1)
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
    row at a time (``lax.map``; each row, and inside it each layer, each block
    of the rule's tokens, each head's block of scores and each expert, is
    computed again in the backward): (weights, ids [B, T + 1]) -> loss,
    expert_tokens [routed layers, E], held_rows [routed layers],
    expert_weight [routed layers, E], d loss / d weights in the program's
    layout."""
    import jax

    from chipbench import reference_kimilinear as ref

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


reference_first_step = mla.reference_first_step


def initial_params(model, seed: int, bias_std: float) -> dict:
    """``model.init`` from ``seed`` with the gains and the selection bias
    redrawn (the module's docstring says why)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))
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

    for top in ("lead", "layers"):
        redraw(params.get(top, {}))
    return params


# token-choices an expert needs, on both sides, for its mean weight to be read
LEAST_CHOICES = 128


def weight_gap(got_weight, got_tokens, want_weight, want_tokens) -> float:
    """``train_steps_mla.weight_gap`` over the experts that BOTH sides gave at
    least ``LEAST_CHOICES`` token-choices: the mean weight of a token-choice
    of every such expert of every routed layer (``moe_expert_weight`` /
    ``moe_expert_tokens``), the difference's norm over the reference's. With
    256 experts and 8 a token an untrained router leaves some experts a
    handful of choices a step, and ONE flipped choice then moves such an
    expert's mean by more than a weighed bias moves all of them: the plain
    reading swung from 8e-4 to 3e-2 by seed on the reference in bf16 itself
    (my chip runs, PR 67)."""
    import numpy as np

    n_got, n_want = np.asarray(got_tokens, np.float64), np.asarray(want_tokens, np.float64)
    read = np.minimum(n_got, n_want) >= LEAST_CHOICES
    mean = lambda w, n: np.where(read, np.asarray(w, np.float64) / np.maximum(n, 1.0), 0.0)
    want = mean(want_weight, n_want)
    return float(np.linalg.norm(mean(got_weight, n_got) - want)
                 / max(np.linalg.norm(want), 1e-30))


def reference_router(src: dict):
    """(logits, bias) -> (chosen, weight) of the reference's ``choose``
    (looked up when called: the band script swaps it)."""
    from chipbench import reference_kimilinear as ref

    def router(logits, bias):
        _, chosen, weight = ref.choose(logits, bias, src)
        return chosen, weight

    return router


# -- the rule alone -----------------------------------------------------------


def rule_inputs(seed: int, batch: int, seq: int, mcfg, dtype, memory=(64.0, 4096.0)):
    """((q, k, v, g, beta), cotangent) for the rule alone, from ``seed``:
    q, k [B, T, H, dk] l2-normalised (q scaled by dk^-0.5) and v [B, T, H, dv]
    = silu of a normal draw, all three rounded to ``dtype`` as the mixer hands
    them over; beta = sigmoid of a normal draw; g [B, T, H, dk] = -softplus(a
    + 1) / softplus(1) / memory, a normal, with EACH KEY CHANNEL's ``memory``
    log-uniform between ``memory``'s two numbers of tokens (64 to 4,096: what
    a trained layer's channels keep, spread by channel so that a rule with
    one decay a head computes another function); g, beta and the cotangent
    [B, T, H, dv] float32."""
    import jax
    import jax.numpy as jnp

    H, dk, dv = mcfg.kda_heads, mcfg.kda_key_dim, mcfg.kda_value_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = unit(normal(keys[0], batch, seq, H, dk)) * dk ** -0.5
    k = unit(normal(keys[1], batch, seq, H, dk))
    v = jax.nn.silu(normal(keys[2], batch, seq, H, dv))
    beta = jax.nn.sigmoid(normal(keys[3], batch, seq, H))
    keeps = jnp.exp(jax.random.uniform(keys[4], (H, dk), jnp.float32,
                                       math.log(memory[0]), math.log(memory[1])))
    g = -jax.nn.softplus(normal(keys[5], batch, seq, H, dk) + 1.0) / (
        math.log1p(math.e) * keeps)
    rounded = lambda x: x.astype(dtype)
    return (rounded(q), rounded(k), rounded(v), g, beta), normal(keys[6], batch, seq, H, dv)


def reference_rule(*args):
    """The reference's recurrence in float32 at highest precision, a state a
    block of tokens kept for the backward."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_kimilinear as ref

    q, k, v, g, beta = args
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), g, beta, remat=True)


def rule_gaps(rule, inputs, exact=None) -> dict:
    """{"o": ..., "dq": ..., "dg": ...}: ``rule``'s distance from the
    reference's recurrence on ``inputs`` (``rule_inputs``), each as a share of
    the reference's norm. ``exact``: the recurrence's answers where the
    caller has them already."""
    if exact is None:
        exact = rule_answers(reference_rule, *inputs)
    return grad_gaps(dict(zip(RULE_PARTS, rule_answers(rule, *inputs))),
                     dict(zip(RULE_PARTS, exact)))


def program_rule():
    """The rule the program's layer calls, on the route its shapes take."""
    from shuffle_exchange_tpu.ops.kda import kda_chunked

    return kda_chunked


# -- the mixers alone -----------------------------------------------------------


def mixer_inputs(params: dict, seed: int, batch: int, seq: int, mcfg, dtype,
                 which: str, score_gain: float = 1.0):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for a mixer alone, from
    ``seed``: ``which`` "kda": the first leading layer's KDA leaves of the
    seed's weights as they are; "mla": the period's latent-attention layer's
    five, the query projection times ``score_gain`` (at the init's scale the
    scores spread over 0.6 and every softmax is nearly flat: a trained head's
    spread over several units); a standard normal x as a normed residual is;
    leaves and x rounded to ``dtype`` as the trainer hands them over, the
    cotangent float32."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed + (3 if which == "mla" else 4)), 2)
    if which == "kda":
        tree = params["lead"] if "kda_w_qkv" in params.get("lead", {}) else next(
            t for t in params["layers"].values() if "kda_w_qkv" in t)
        lw = {name: tree[name][(0,) * (tree[name].ndim - _rank(name))] for name in KDA_LEAVES}
    else:
        tree = params["layers"]
        tree = tree if "mla_wq" in tree else next(
            t for t in tree.values() if "mla_wq" in t)
        lw = {name: tree[name][(0,) * (tree[name].ndim - _rank(name))] for name in MLA_LEAVES}
        lw["mla_wq"] = lw["mla_wq"] * score_gain
    x = jax.random.normal(keys[0], (batch, seq, mcfg.d_model), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def _rank(leaf: str) -> int:
    """Dimensions of ONE layer's leaf (what is left of a stacked one)."""
    return 1 if leaf in ("kda_A_log", "kda_dt_bias", "kda_norm_w", "mla_kv_norm_w") else 2


def program_mixer(model, which: str):
    """(leaves, x) -> the program's own mixer (``Transformer._kda`` /
    ``Transformer._mla`` under ``unrotated_mixers``: no table), in the dtype
    of what it is handed."""
    if which == "kda":
        return lambda lw, x: model._kda(lw, x, (None, None))[0]
    return lambda lw, x: model._mla(lw, x, model.rope_for("mla", x.shape[1]))


def reference_mixer(src: dict, which: str, dtype=None):
    """The same of the reference's ``kda`` / ``attention`` (looked up when
    called: the band script swaps their pieces), one row at a time, in float32
    at highest precision; ``dtype``: in that one instead."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_kimilinear as ref

    table = {**_KDA, **_MLA}

    def mixer(lw, x):
        named = {}
        for leaf, v in lw.items():
            v = v.astype(jnp.float32)
            if leaf in _FUSED:
                for j, n in enumerate("qkv"):
                    named["a." + _FUSED[leaf].format(n)] = _to_torch((leaf,), v, j)
            else:
                named["a." + table[leaf]] = _to_torch((leaf,), v, None)
        fn = ref.kda if which == "kda" else ref.attention
        row = lambda one: fn(named, "a.self_attn.", one[None].astype(dtype or jnp.float32),
                             src, remat=True)[0]
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(jax.checkpoint(row), x)

    return mixer


def mixer_gaps(mixer, inputs, exact: dict) -> dict:
    """{"y": ..., "dx": ..., "d<leaf>": ...}: ``mixer``'s distance from
    ``exact`` (``mixer_answers`` of ``reference_mixer`` in float32) on
    ``inputs`` (``mixer_inputs``), each as a share of the reference's norm."""
    return grad_gaps(mixer_answers(mixer, *inputs), exact)


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct): ``train_steps_mla.failed_checks``'s, on the same
    keys, and ``rule_gaps`` (``rule_gaps`` above), ``kda_mixer_gaps``
    (``mixer_gaps`` of the KDA mixer), ``rotated`` (the step's own
    ``rope_layers_rotated``; None: not handed out) and ``kda_layers`` (the
    step's, and the configuration's). The band script hands it a wrong
    model's or a lower precision's answers in the program's place."""
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    rule_tol, kda_tol = float(traffic["rule_tol"]), float(traffic["kda_mixer_tol"])
    part = max(got["rule_gaps"], key=nan_last(got["rule_gaps"]))
    piece = max(got["kda_mixer_gaps"], key=nan_last(got["kda_mixer_gaps"]))
    checks = [
        (got["rule_gaps"][part] <= rule_tol,
         f"the rule alone, at memories of 64 to 4,096 tokens a key channel: {part} "
         f"differs from the reference's recurrence by {got['rule_gaps'][part]:.3g} of "
         f"its norm: more than {rule_tol} (one decay a head in the channels' place, "
         f"no decay, no beta, a state or a cumulated decay below float32 read so)"),
        (got["kda_mixer_gaps"][piece] <= kda_tol,
         f"the KDA mixer alone: {piece} differs from the reference's by "
         f"{got['kda_mixer_gaps'][piece]:.3g} of its norm: more than {kda_tol} (no l2 "
         f"norm, a SiLU for the gate's sigmoid, a missing convolution read so)"),
        (got["rotated"] == 0,
         f"the step hands {got['rotated']} layer(s) a RoPE table "
         f"(rope_layers_rotated; None: no such counter): this stack rotates nothing"),
        (got["kda_layers"][0] == got["kda_layers"][1],
         f"the step walks {got['kda_layers'][0]} KDA rule(s) (kda_layers; None: no "
         f"such counter), the configuration has {got['kda_layers'][1]}"),
    ]
    return mla.failed_checks(got, traffic) + [
        message for ok, message in checks if not ok]


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
    from shuffle_exchange_tpu.ops import kda as kda_ops
    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled
    from shuffle_exchange_tpu.ops.flash_attention import attention_route
    from shuffle_exchange_tpu.ops.ssm_conv import ssm_conv_route

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_kda holds the whole state on "
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
    mla_in = mixer_inputs(drawn, seed, batch, seq, mcfg, dtype, "mla",
                          float(traffic["mixer_score_gain"]))
    kda_in = mixer_inputs(drawn, seed, batch, seq, mcfg, dtype, "kda")
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
    # the mixers alone, in the trainer's compute dtype against float32
    mix_gaps = mixer_gaps(program_mixer(model, "mla"), mla_in,
                          mixer_answers(reference_mixer(src, "mla"), *mla_in))
    del mla_in
    kda_gaps = mixer_gaps(program_mixer(model, "kda"), kda_in,
                          mixer_answers(reference_mixer(src, "kda"), *kda_in))
    del kda_in
    # the rule alone, at long memories a key channel
    state_gaps = rule_gaps(program_rule(), rule_inputs(seed, batch, seq, mcfg, dtype))
    engine = sxt.initialize(model=model, params=initial_params(model, seed, bias_std),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    H, Hk = mcfg.n_heads, mcfg.kda_heads
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    routes = {
        "grouped_gemm": "megablox" if pallas_enabled() else "ragged_dot",
        "mla_core": attention_route(
            shaped(batch, seq, H, mcfg.head_dim), shaped(batch, seq, H, mcfg.head_dim),
            shaped(batch, seq, H, mcfg.mla_v_dim), impl=mcfg.attention_impl),
        "kda_rule": kda_ops.kernel_route(
            shaped(batch, seq, Hk, mcfg.kda_key_dim), shaped(batch, seq, Hk, mcfg.kda_key_dim),
            shaped(batch, seq, Hk, mcfg.kda_value_dim)),
        "kda_conv": ssm_conv_route(
            shaped(batch, seq, Hk * (2 * mcfg.kda_key_dim + mcfg.kda_value_dim)),
            shaped(mcfg.kda_conv_kernel, Hk * (2 * mcfg.kda_key_dim + mcfg.kda_value_dim)),
            0, (Hk * mcfg.kda_key_dim, Hk * mcfg.kda_key_dim, Hk * mcfg.kda_value_dim)),
    }

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows",
                 "moe_expert_weight", "kda_layers", "kda_decay_mean", "kda_decay_min",
                 "rope_layers_rotated") if k in got}

    def biases():
        """The selection biases, a row a routed layer in the counters' order."""
        master = flat_tree(engine.state.master)
        return np.stack([np.asarray(master["/".join(path + ("moe_select_bias",))][at])
                         for (_, ffn), (path, at) in zip(layer_kinds(src), layer_slots(src))
                         if ffn == "moe"])

    bias_before = biases()
    losses = [engine.train_batch(first)]
    first_stats = stats_now()
    # the buffer after one step: the reference's aux-free update of the bias
    # it had, on the program's own counts, and nothing of the optimizer's
    bias_gap = None
    if "moe_expert_tokens" in first_stats:
        from chipbench import reference_kimilinear as ref

        bias_gap = float(np.abs(biases() - np.asarray(ref.bias_update(
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
                 kda_mixer_gaps=kda_gaps, rule_gaps=state_gaps,
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
                    # the rows the traced kernels had
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
    number = lambda key: (None if key not in last_stats
                          else float(np.asarray(last_stats[key])))
    rotated, walked = number("rope_layers_rotated"), number("kda_layers")
    failed = failed_checks(
        {"losses": vals, "first_loss_again": again,
         "reference_loss": reference["loss"], "route_gap": first_gap,
         "held_gap": held_gap, "counters_add_up": counters_add_up,
         "overflow": overflow, "grad_gaps": first_gaps, "bias_grad": bias_grad,
         "router_gaps": route_gaps, "weight_gap": weighed, "mixer_gaps": mix_gaps,
         "bias_update_gap": bias_gap, "rule_gaps": state_gaps,
         "kda_mixer_gaps": kda_gaps, "rotated": rotated,
         "kda_layers": [walked, float(mcfg.kda_layers)]},
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
                 kda_mixer_gaps=kda_gaps, rule_gaps=state_gaps,
                 first_step_bias_update_gap=bias_gap,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped,
                 moe_held_row_share=held_share, traced_steps=traced_steps,
                 kda_decay_mean=number("kda_decay_mean"),
                 kda_decay_min=number("kda_decay_min"),
                 kda_layers=walked, rope_layers_rotated=rotated,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    counters = {"compiles_in_window": in_win["programs_compiled"],
                "steps": steps}
    if have:
        counters.update(moe_expert_load_max_over_mean=load,
                        moe_dropped_token_share=dropped,
                        moe_held_row_share=held_share)
    for key in ("kda_decay_mean", "kda_decay_min", "kda_layers", "rope_layers_rotated"):
        if number(key) is not None:
            counters[key] = number(key)
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
                  "kda_flops_per_token": None if held_rows_step is None else
                  arith_kda.train_flops_per_token(
                      mcfg, seq, held_rows_step / (batch * seq))},
    }
