"""Training cells of a dense linear-attention hybrid whose state is sharded
(Olmo Hybrid: Gated DeltaNet layers with negative eigenvalues beside unrotated
full attention, every sublayer normed on its output, no expert):
``train_steps_hybrid``'s window and checks less everything routed, on
``train_steps``' mesh (``chipbench.mesh`` of the configuration file: ZeRO-3
over the cell's chips), held to the benchmark's own plain float32 reference
of the architecture (``chipbench/reference_olmohybrid.py``: the delta rule's
recurrence one token at a time, whole attention scores).

The reference runs FIRST and alone on the chips, data-parallel: every chip
holds the same initial weights in float32 under the source's names (drawn
again for the trainer from the same seed: the same weights) and takes its own
rows of the first batch ONE AT A TIME (each row, and inside it each layer, each
head and each 64 steps of the recurrence, is computed again in the backward);
a row's gradient is reduce-scattered over the chips as soon as it exists, so a
chip holds the weights, one row's gradient and a quarter of the sum. Its
arithmetic is the plain one; only the rows are dealt out. The trainer's first
gradient is read out of Adam's first moment after one update ((1 - beta1) x
the gradient) and compared on the host, leaf by leaf of the program's tree.
``correct`` = every loss finite, the loss fell, the first loss within
``loss_tol``, every leaf's gradient within ``grad_tol`` of the reference's
norm, the rule alone within ``state_tol`` of the float32 recurrence
(``train_steps_hybrid``'s reading, with the write strength drawn from (0, 2)
where the configuration allows negative eigenvalues), the q/k norm alone
within ``stat_tol`` (below), and the program's ``gdn_scan_chunks`` counter
(``engine.last_step_stats()``) above 0. The attention layer's leaves have a
limit of their own, ``grad_tol_attn``: through three DeltaNet layers' output
norms bf16 rounding moves a DeltaNet leaf's gradient by a fifth to a third of
its norm, an attention leaf's by under a tenth, and a wrong q/k norm shows on
the attention leaves alone.

The norm's statistic. A q/k norm whose mean of squares is formed in bf16 moves
no whole-model reading (the band's ``bf16_qk_stat`` reads what ``bf16`` reads
to three digits), so one reading takes the norm alone: the function the
program's attention mixer calls (``models/transformer._norm``) on a seeded
[seq, heads x head size] projection in the trainer's compute dtype against the
same numbers normed in float32. An output rounded to bf16 is off element by
element, which averages out over a token's 3,840 channels; a rounded statistic
scales the WHOLE token. ``stat_gap`` is the root mean square over the tokens of
``<y, y_ref> / <y_ref, y_ref> - 1``.

Weights: ``Transformer.init`` from ``--seed``, except that every gain (block
norms, final norm, q/k norm, the DeltaNet output norm) is drawn from
[0.5, 1.5): at their initial 1 a model that norms elsewhere differs less.
``A_log`` = log U(0, 16) and ``dt_bias`` = 1 are the init's own.

Traffic parameters: ``train_steps``', ``grad_tol``, ``grad_tol_attn``,
``state_tol`` and ``stat_tol``.
``chipbench/olmohybrid_band.py`` measures the band the three are set from and
runs every wrong model and lower precision through ``failed_checks`` below, in
the program's place.
"""

from __future__ import annotations

import math
import time

from chipbench import harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import (  # noqa: F401 (flat_tree: the tests')
    RULE_PARTS, first_moment, flat_tree, reference_rule, rule_answers)
from chipbench.drivers.train_steps_moe import grad_gaps

_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}
_BLOCK = {"ln1_w": "post_attention_layernorm.weight",
          "ln2_w": "post_feedforward_layernorm.weight",
          "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
          "w_down": "mlp.down_proj.weight"}
_ATTN = {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
         "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
         "q_norm_w": "self_attn.q_norm.weight", "k_norm_w": "self_attn.k_norm.weight"}
_GDN = {"A_log": "linear_attn.A_log", "dt_bias": "linear_attn.dt_bias",
        "gdn_norm_w": "linear_attn.o_norm.weight", "w_out": "linear_attn.o_proj.weight"}
# the program's fused leaves and the source's tensors they hold, in the order
# the program lays them side by side: ``w_qkvz`` a key head's [q | k | v | z]
# (one value head a key head here), ``w_ba`` a head's [b | a], ``conv_w`` all
# of q, all of k, all of v
_QKVZ = ("q_proj", "k_proj", "v_proj", "g_proj")
_BA = ("b_proj", "a_proj")
_CONV = ("q_conv1d", "k_conv1d", "v_conv1d")
GAINS = ("ln1_w", "ln2_w", "q_norm_w", "k_norm_w", "gdn_norm_w")


def slots_of(src: dict) -> list:
    """[(kind's name in the program's tree, period, index among that kind's
    layers of the period, is full attention)] layer by layer, as the program
    stacks them (``Transformer.slots``; written out here so that the mapping
    does not move with the program)."""
    types = list(src["layer_types"])[:src["num_hidden_layers"]]
    period = next(p for p in range(1, len(types) + 1)
                  if len(types) % p == 0 and types[:p] * (len(types) // p) == types)
    out = []
    for i, kind in enumerate(types):
        seen = sum(1 for t in types[i - i % period:i] if t == kind)
        full = kind == "full_attention"
        out.append(("attn_mlp" if full else "gdn_mlp", i // period, seen, full))
    return out


def _widths(src: dict):
    Hk, Hv = src["linear_num_key_heads"], src["linear_num_value_heads"]
    if Hk != Hv:
        raise harness.BenchError("train_steps_gdn maps one value head a key head "
                                 f"(the family's); the configuration has {Hk} / {Hv}")
    dk, dv = src["linear_key_head_dim"], src["linear_value_head_dim"]
    return Hk, (dk, dk, dv, dv)


def to_source_names(params: dict, src: dict) -> dict:
    """The program's tree -> a flat dict under the source's names, each tensor
    as torch stores it (a matrix [out, in], a convolution [C, 1, K]). Stays
    where the leaves are; float32 as the master is."""
    H, widths = _widths(src)
    dk, dv = widths[0], widths[2]
    out = {name: params[leaf] if leaf != "unembed" else params[leaf].T
           for leaf, name in _TOP.items()}
    for i, (kind, period, j, full) in enumerate(slots_of(src)):
        p = f"model.layers.{i}."
        leaves = {k: v[period, j] for k, v in params["layers"][kind].items()}
        named = {**_BLOCK, **(_ATTN if full else _GDN)}
        out.update({p + name: leaves[leaf].T if leaves[leaf].ndim == 2 else leaves[leaf]
                    for leaf, name in named.items()})
        if full:
            continue
        a = p + "linear_attn."
        D = leaves["w_qkvz"].shape[0]
        fused = leaves["w_qkvz"].reshape(D, H, sum(widths))
        at = 0
        for name, width in zip(_QKVZ, widths):
            out[a + name + ".weight"] = fused[:, :, at:at + width].reshape(D, H * width).T
            at += width
        ba = leaves["w_ba"].reshape(D, H, 2)
        for n, name in enumerate(_BA):
            out[a + name + ".weight"] = ba[:, :, n].T
        at = 0
        for name, width in zip(_CONV, (H * dk, H * dk, H * dv)):
            out[a + name + ".weight"] = leaves["conv_w"][:, at:at + width].T[:, None, :]
            at += width
    return out


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat and on the HOST (numpy):
    {"/".join(path): the program's stacked array}."""
    import numpy as np

    H, widths = _widths(src)
    named = {k: np.asarray(v) for k, v in named.items()}
    out = {leaf: named[name] if leaf != "unembed" else named[name].T
           for leaf, name in _TOP.items()}
    cells = {}
    for i, (kind, period, j, full) in enumerate(slots_of(src)):
        p = f"model.layers.{i}."
        leaves = {leaf: named[p + name].T if named[p + name].ndim == 2 else named[p + name]
                  for leaf, name in {**_BLOCK, **(_ATTN if full else _GDN)}.items()}
        if not full:
            a = p + "linear_attn."
            D = named[a + "q_proj.weight"].shape[1]
            leaves["w_qkvz"] = np.concatenate(
                [named[a + name + ".weight"].T.reshape(D, H, width)
                 for name, width in zip(_QKVZ, widths)], axis=2).reshape(D, -1)
            leaves["w_ba"] = np.stack([named[a + name + ".weight"].T for name in _BA],
                                      axis=2).reshape(D, 2 * H)
            leaves["conv_w"] = np.concatenate(
                [named[a + name + ".weight"][:, 0, :].T for name in _CONV], axis=1)
        for leaf, x in leaves.items():
            cells.setdefault(f"layers/{kind}/{leaf}", {})[(period, j)] = x
    for path, at in cells.items():
        periods = 1 + max(p for p, _ in at)
        each = 1 + max(j for _, j in at)
        out[path] = np.stack([np.stack([at[p, j] for j in range(each)])
                              for p in range(periods)])
    return out


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed`` with the gains redrawn (the module's
    docstring says why)."""
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


def reference_mesh(devices):
    """The reference's own mesh: the cell's devices in a row, axis ``rows``."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devices), ("rows",))


def reference_program(src: dict, devices, dtype=None):
    """The reference on a whole batch as ONE jitted program over ``devices``:
    (weights under the source's names, replicated; ids [B, T + 1], rows dealt
    out over the devices) -> (loss, d loss / d weights under the source's
    names). A device takes its rows one at a time (``lax.scan``; each row is
    computed again in the backward) and reduce-scatters a row's gradient at
    once: a matrix's sum comes back split over the devices along its first
    axis, a small tensor's whole. ``dtype``: the band's lower precision."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from chipbench import reference_olmohybrid as ref

    n = len(devices)
    mesh = reference_mesh(devices)
    dtype = dtype or jnp.float32
    split = lambda x: x.ndim >= 2 and x.shape[0] % n == 0 and x.size >= 1 << 16

    def row_loss(w, one):
        return ref.loss(w, src, one[None], dtype, True)

    def per_device(w, ids):
        def summed(g):
            return (jax.lax.psum_scatter(g, "rows", scatter_dimension=0, tiled=True)
                    if split(g) else jax.lax.psum(g, "rows"))

        def row(carry, one):
            loss, grad = jax.value_and_grad(row_loss)(w, one)
            total, acc = carry
            return (total + loss, jax.tree.map(
                lambda a, g: a + summed(g), acc, grad)), None

        if ids.shape[0] == 1:
            # one row a device: nothing to add up, no carry to hold
            total, grad = jax.value_and_grad(row_loss)(w, ids[0])
            acc = jax.tree.map(summed, grad)
        else:
            zero = jax.tree.map(
                lambda x: jnp.zeros((x.shape[0] // n,) + x.shape[1:] if split(x)
                                    else x.shape, jnp.float32), w)
            (total, acc), _ = jax.lax.scan(
                row, (jnp.zeros((), jnp.float32), zero), ids)
        rows = ids.shape[0] * n
        return (jax.lax.psum(total, "rows") / rows,
                jax.tree.map(lambda a: a / rows, acc))

    def program(w, ids):
        specs = jax.tree.map(lambda x: P("rows") if split(x) else P(), w)
        return jax.shard_map(per_device, mesh=mesh, in_specs=(P(), P("rows")),
                             out_specs=(P(), specs), check_vma=False)(w, ids)

    return jax.jit(program), mesh


def reference_weights(model, seed: int, src: dict, mesh) -> dict:
    """``initial_params`` under the source's names, on every device of
    ``mesh`` whole (each draws them itself: the same seed, the same weights)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def drawn():
        return to_source_names(initial_params(model, seed), src)

    return jax.jit(drawn, out_shardings=NamedSharding(mesh, P()))()


def reference_first_step(program, mesh, weights: dict, ids, src: dict) -> dict:
    """``reference_program``'s answer on the HOST, the gradient in the
    program's layout."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ids = jax.device_put(ids, NamedSharding(mesh, P("rows")))
    loss, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "grads": from_source_names(grads, src)}


def host_gaps(ours: dict, theirs: dict, scale: float = 1.0) -> dict:
    """{leaf: |scale x ours - theirs| / |theirs|} over ``theirs``' leaves, on
    the host one leaf at a time (``ours`` may be sharded over the chips)."""
    import numpy as np

    out = {}
    for leaf, b in theirs.items():
        a = np.asarray(ours[leaf], np.float32)
        b = np.asarray(b, np.float32)
        norm = float(np.sqrt(np.sum(np.square(b, dtype=np.float64))))
        out[leaf] = float(np.sqrt(np.sum(np.square(
            scale * a - b, dtype=np.float64)))) / norm if norm else math.inf
    return out


def rule_inputs(seed: int, batch: int, seq: int, mcfg, dtype):
    """``train_steps_hybrid.rule_inputs`` with the write strength in
    (0, ``gdn_beta_scale``): ((q, k, v, g, beta), cotangent) for the rule
    alone, from ``seed``: q, k [B, T, H, dk] l2-normalised (q scaled by
    dk^-0.5) and v [B, T, H, dv] = silu of a normal draw, rounded to
    ``dtype``; each head's memory (1 / mean -g) log-uniform between seq / 128
    and seq / 2 tokens; g, beta and the cotangent float32."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_olmohybrid as ref

    H, dk, dv = mcfg.gdn_value_heads, mcfg.gdn_key_dim, mcfg.gdn_value_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    q = ref.l2norm(normal(keys[0], batch, seq, H, dk)) * dk ** -0.5
    k = ref.l2norm(normal(keys[1], batch, seq, H, dk))
    v = jax.nn.silu(normal(keys[2], batch, seq, H, dv))
    beta = getattr(mcfg, "gdn_beta_scale", 1.0) * jax.nn.sigmoid(
        normal(keys[3], batch, seq, H))
    memory = jnp.exp(jax.random.uniform(keys[4], (H,), jnp.float32,
                                        math.log(seq / 128), math.log(seq / 2)))
    g = -jax.nn.softplus(normal(keys[5], batch, seq, H) + 1.0) / (
        math.log1p(math.e) * memory)
    rounded = lambda x: x.astype(dtype)
    return (rounded(q), rounded(k), rounded(v), g, beta), normal(keys[6], batch, seq, H, dv)


def state_gaps(rule, inputs, exact=None) -> dict:
    """{"o": ..., "dq": ..., ...}: ``rule``'s distance from the float32
    recurrence on ``inputs``, each as a share of the recurrence's norm."""
    if exact is None:
        exact = rule_answers(reference_rule, *inputs)
    return grad_gaps(dict(zip(RULE_PARTS, rule_answers(rule, *inputs))),
                     dict(zip(RULE_PARTS, exact)))


def stat_inputs(seed: int, rows: int, mcfg, dtype):
    """(q [rows, heads x head size] in ``dtype``, its gain) for the q/k norm
    alone, from ``seed``: a normal draw whose scale varies by token (a
    log-uniform factor between 1/4 and 4), a gain from [0.5, 1.5)."""
    import jax
    import jax.numpy as jnp

    width = mcfg.n_heads * mcfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    scale = jnp.exp(jax.random.uniform(keys[0], (rows, 1), jnp.float32,
                                       -math.log(4.0), math.log(4.0)))
    q = (scale * jax.random.normal(keys[1], (rows, width), jnp.float32)).astype(dtype)
    return q, jax.random.uniform(keys[2], (width,), jnp.float32, 0.5, 1.5)


def program_qk_norm(mcfg):
    """The whole-projection q/k norm as the program's attention mixer calls
    it: (x, gain) -> x's dtype."""
    from shuffle_exchange_tpu.models.transformer import _norm

    return lambda x, gain: _norm(x, gain, 0, "rmsnorm", eps=mcfg.norm_eps)


def stat_gap(norm, inputs, eps: float) -> float:
    """How far ``norm``'s per-token SCALE sits from the float32 norm's on the
    same numbers (the module's docstring): rms over the tokens of
    <y, y_ref> / <y_ref, y_ref> - 1."""
    import jax
    import jax.numpy as jnp

    def gap(x, gain):
        y = norm(x, gain).astype(jnp.float32)
        x32 = x.astype(jnp.float32)
        ref = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * gain
        scale = jnp.sum(y * ref, axis=-1) / jnp.sum(ref * ref, axis=-1) - 1.0
        return jnp.sqrt(jnp.mean(scale * scale))

    return float(jax.jit(gap)(*inputs))


def is_attention(leaf: str) -> bool:
    """A leaf of the full-attention layers: what ``grad_tol_attn`` is for."""
    return leaf.startswith("layers/attn_")


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``reference_loss``, ``grad_gaps`` {leaf: share of the reference's
    norm}, ``state_gaps``, ``stat_gap``, ``scan_chunks`` (the program's
    counter; None: it handed out none). The band script hands it a wrong
    model's or a lower precision's answers in the program's place."""
    vals = got["losses"]
    loss_tol, grad_tol, state_tol, stat_tol = (
        float(traffic[k]) for k in ("loss_tol", "grad_tol", "state_tol", "stat_tol"))
    attn_tol = float(traffic.get("grad_tol_attn", grad_tol))
    limit = lambda leaf: attn_tol if is_attention(leaf) else grad_tol
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    over = {leaf: gap / limit(leaf) for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))          # a NaN gap is the worst of all
    part = max(got["state_gaps"], key=nan_last(got["state_gaps"]))
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
        (got["state_gaps"][part] <= state_tol,
         f"the rule alone, at a memory of hundreds of tokens: {part} differs "
         f"from the float32 recurrence's by {got['state_gaps'][part]:.5f} of its "
         f"norm: more than {state_tol} (a state carried below float32 reads so)"),
        (got["stat_gap"] <= stat_tol,
         f"the q/k norm alone: its per-token scale differs from the float32 "
         f"norm's by {got['stat_gap']:.2e} (rms over tokens): more than {stat_tol} "
         f"(a statistic formed below float32 reads so)"),
        (bool(got["scan_chunks"]),
         f"the program's gdn_scan_chunks counter reads {got['scan_chunks']!r}: no "
         f"delta rule walked a chunk"),
    ]
    return [message for ok, message in checks if not ok]


def routes_at(mcfg, batch: int, seq: int, dtype) -> dict:
    """What the program's two route functions say at one chip's shapes."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops import gated_delta as gd

    H, Hk = mcfg.gdn_value_heads, mcfg.gdn_key_heads
    dk, dv = mcfg.gdn_key_dim, mcfg.gdn_value_dim
    shape = lambda *s: jax.ShapeDtypeStruct(s, dtype)
    q, v = shape(batch, seq, H, dk), shape(batch, seq, H, dv)
    qkvz = shape(batch, seq, 2 * Hk * dk + 2 * H * dv)
    conv_w = shape(mcfg.gdn_conv_kernel, 2 * Hk * dk + H * dv)
    return {"gated_delta": gd.kernel_route(q, q, v),
            "gdn_prologue": gd.prologue_route(qkvz, conv_w, dk, dv)}


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
    from shuffle_exchange_tpu.ops.gated_delta import gated_delta_chunked

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    seq, per_chip = int(traffic["seq"]), int(traffic["batch_per_chip"])
    batch = per_chip * chips
    src = dict(rehearsal.get("source_config") or cell["config"])
    # (a rehearsal may replace sections: float32 at a size where bf16 noise
    # drowns a gradient of a hundred tokens)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))
    if settings.get("mesh"):
        config["mesh"] = {k: (chips if v == "chips" else v)
                          for k, v in settings["mesh"].items()}
    dtype = jnp.bfloat16 if config.get("bf16", {}).get("enabled") else jnp.float32

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chips; the weights are drawn again
    # for the trainer: the same seed, the same weights
    program, ref_mesh = reference_program(src, devices)
    weights = reference_weights(model, seed, src, ref_mesh)
    reference = reference_first_step(program, ref_mesh, weights,
                                     jnp.asarray(first["input_ids"]), src)
    del weights, program
    # the rule alone, at a long memory and one chip's rows: what shows the
    # state's precision
    rule_gaps = state_gaps(gated_delta_chunked,
                           rule_inputs(seed, per_chip, seq, mcfg, dtype))
    # the q/k norm alone: what shows its statistic's precision
    norm_gap = stat_gap(program_qk_norm(mcfg), stat_inputs(seed, seq, mcfg, dtype),
                        mcfg.norm_eps)
    engine = sxt.initialize(model=model, params=initial_params(model, seed),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)

    losses = [engine.train_batch(first)]
    chunks = engine.last_step_stats().get("gdn_scan_chunks")
    chunks = None if chunks is None else int(np.asarray(chunks))
    beta1 = settings["train_config"]["optimizer"]["params"].get("betas", (0.9,))[0]
    moment = first_moment(engine.state.opt_state)
    first_gaps = ({leaf: 1.0 for leaf in reference["grads"]} if moment is None
                  else host_gaps(moment, reference.pop("grads"), 1.0 / (1.0 - beta1)))
    del moment
    for _ in range(max(1, int(traffic["warmup_steps"])) - 1):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 mesh={k: v for k, v in engine.topology.axis_sizes.items()
                       if v > 1},
                 routes={"attention": "pallas" if pallas_enabled() else "xla",
                         **routes_at(mcfg, per_chip, seq, dtype)},
                 gdn_scan_chunks=chunks,
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
    vals = [float(x) for x in losses + window_losses]
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    failed = failed_checks(
        {"losses": vals, "reference_loss": reference["loss"],
         "grad_gaps": first_gaps, "state_gaps": rule_gaps, "stat_gap": norm_gap,
         "scan_chunks": chunks},
        traffic)
    worst = max(first_gaps, key=lambda leaf: first_gaps[leaf]
                if first_gaps[leaf] == first_gaps[leaf] else math.inf)
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_abs_err=abs(vals[0] - reference["loss"]),
                 first_step_grad_gap=first_gaps[worst],
                 first_step_grad_gap_leaf=worst,
                 first_step_grad_gap_attn=max(
                     (g for leaf, g in first_gaps.items() if is_attention(leaf)), default=None),
                 first_step_grad_gap_others=max(
                     (g for leaf, g in first_gaps.items() if not is_attention(leaf)),
                     default=None),
                 first_step_grad_gaps=first_gaps,
                 state_gap=max(rule_gaps.values()), state_gaps=rule_gaps,
                 stat_gap=norm_gap,
                 gdn_scan_chunks=chunks, traced_steps=traced_steps,
                 failed_checks=failed, **in_win)
    return {
        "correct": not failed, "attempted": steps,
        "failed": sum(1 for v in vals[len(losses):] if not math.isfinite(v)),
        "end_to_end": {"train_tokens_per_s_chip": per_chip_rate},
        "window_s": window_s, "program_bytes": step_bytes,
        "counters": {"compiles_in_window": in_win["programs_compiled"],
                     "steps": steps, "gdn_scan_chunks": chunks},
        "facts": {"model_cfg": mcfg, "seq": seq, "batch": batch,
                  "chips": chips, "tokens_per_step": batch * seq,
                  "step_s": [b - a for a, b in spans.named("train_step")],
                  "traced_steps": traced_steps},
    }
