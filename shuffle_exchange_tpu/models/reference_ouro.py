"""A plain reference for ByteDance's Ouro-2.6B (``model_type: ouro``, a looped
language model): forward pass, the Stage-I loss over all exits and gradients
in ``jax.numpy``, float32, every matmul at ``jax.default_matmul_precision(
"highest")``; a Python ``for`` over loop steps and layers, no scan over either,
no kernel, no cache, nothing imported from ``shuffle_exchange_tpu``. This file
is in the repository TWICE, byte for byte:
``shuffle_exchange_tpu/models/reference_ouro.py`` (the program's tests hold the
program to it) and ``chipbench/reference_ouro.py`` (the cell ``ouro-train``'s
``correct`` is decided by it, and the benchmark imports nothing of the program
to judge it). ``tests/test_ouro.py`` holds the two together.
"""

# Written from the source's config.json (the catalog's row), the family's
# report ("Scaling Latent Reasoning via Looped Language Models",
# arXiv:2510.25741) and what ISSUE 64 recalls of the published modelling code
# (no network here: the configuration file's ``assumed`` lists each such
# item). Weights are a flat dict under the source's names, each matrix laid out
# as torch's nn.Linear stores it ([out, in]: y = x @ W.T); H =
# num_attention_heads = num_key_value_heads, Dh = head_dim, D = hidden_size,
# F = intermediate_size:
#
#   model.embed_tokens.weight                               [V, D]
#   model.layers.{i}.input_layernorm.weight                 [D]   N1
#   model.layers.{i}.input_layernorm_2.weight               [D]   N2
#   model.layers.{i}.post_attention_layernorm.weight        [D]   N3
#   model.layers.{i}.post_attention_layernorm_2.weight      [D]   N4
#   model.layers.{i}.self_attn.{q,k,v}_proj.weight          [H Dh, D]
#   model.layers.{i}.self_attn.o_proj.weight                [D, H Dh]
#   model.layers.{i}.mlp.{gate,up}_proj.weight              [F, D]
#   model.layers.{i}.mlp.down_proj.weight                   [D, F]
#   model.norm.weight                                       [D]   Nf
#   model.early_exit_gate.weight                            [1, D]
#   model.early_exit_gate.bias                              [1]
#   lm_head.weight                                          [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size, head_dim,
# num_attention_heads, num_hidden_layers, intermediate_size, rope_theta,
# rms_norm_eps, vocab_size, total_ut_steps) plus ``exit_entropy_beta`` (the
# report's Stage-I beta; no key of the source's; 0.1 without it).
#
# The equations, N(x; g) = x * rsqrt(mean(x^2) + eps) * g:
#   block i on h [S, D] (sandwich-normed, four gains):
#     q,k,v  = N(h; g1) Wq, Wk, Wv  [H x Dh]; q, k rotated, rotate-half pairs
#              (j, j + Dh/2) over all Dh dims, inverse frequencies
#              theta^(-2j/Dh), by the token's position: the SAME at every step
#     att    = softmax(q k^T / sqrt(Dh) + causal mask) v, in float32
#     a      = h + N(att Wo; g2)
#     h'     = a + N((silu(N(a; g3) Wg) * (N(a; g3) Wu)) Wd; g4)
#   the loop: h_0 = Embed(ids); for t = 1 .. T: h_t = N(Stack(h_{t-1}); g_f),
#     Stack = the same L blocks in order with the SAME weights at every t; the
#     final norm is INSIDE the loop: the normed stream is what the next step
#     takes in, what the head reads and what the gate reads
#   exits: logits_t = h_t W_head; lam_t = sigmoid(w_g . h_t + b_g) a token;
#     p_1 = lam_1, p_t = lam_t prod_{j<t} (1 - lam_j) for 1 < t < T,
#     p_T = prod_{j<T} (1 - lam_j) (what is left; lam_T is not used)
#   loss = mean over tokens of [ sum_t p_t CE_t - beta H(p) ],
#     H(p) = -sum_t p_t log p_t, gradients through p_t AND CE_t
#
# Departures from the source, each on purpose:
#   - the batch is [B, S + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - only the report's Stage I (pre-training) objective; its Stage II (the
#     gate fine-tuned on the exits' detached losses) is not written;
#   - ``early_exit_threshold`` is serving's and is not read;
#   - the masked softmax is computed a block of ``QUERY_BLOCK`` queries at a
#     time, every head at once, against the keys up to the block's last (a
#     dense mask row block, a Python loop over the blocks), so that 8,192
#     positions fit; the values are those of the whole [S, S] form. (No
#     ``lax.map`` over heads or blocks: on the chip every such loop's output
#     buffer is allocated at the top of the program, and 48 unrolled visits
#     of them do not fit);
#   - the logits of an exit are formed ``LOGIT_ROWS`` positions at a time;
#   - ``remat`` wraps each (step, layer) visit, each query block and each
#     block of an exit's logits in jax.checkpoint: the same values, computed
#     again in the backward; a visit's gradients are handed on together
#     (``one_at_a_time``), so that the chip's compiler finishes one visit's
#     backward before the next;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the gate, the exit distribution and the
#     cross-entropy stay float32.

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
LOGIT_ROWS = 2048
BETA = 0.1


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x [B, S, H, Dh]: every dim of each head rotated (rotate-half pairs
    (j, j + Dh / 2)), plain inverse frequencies, by the position in the
    sequence."""
    Dh = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [S, Dh]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return x * cos + rotate_half(x) * sin


def linear(x, w):
    return x @ w.astype(x.dtype).T


def attention(w, prefix, x, cfg, remat=False):
    """The mixer on its normed input x [B, S, D] -> [B, S, D]: plain
    multi-head causal softmax attention at 1 / sqrt(Dh), every head at once, a
    block of queries at a time (a Python loop) against the keys up to the
    block's last."""
    B, S, D = x.shape
    H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
    q = rope(linear(x, w[prefix + "q_proj.weight"]).reshape(B, S, H, Dh), cfg["rope_theta"])
    k = rope(linear(x, w[prefix + "k_proj.weight"]).reshape(B, S, H, Dh), cfg["rope_theta"])
    v = linear(x, w[prefix + "v_proj.weight"]).reshape(B, S, H, Dh)
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def rows(qb, kb, vb, start):
        """The queries [start, start + block) against the keys [0, start +
        block): scores [B, H, block, start + block] in float32."""
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                            preferred_element_type=jnp.float32) * Dh ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(start + block)[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1).astype(x.dtype), vb)

    one = jax.checkpoint(rows, static_argnums=(3,)) if remat else rows
    o = jnp.concatenate([one(q[:, s:s + block], k[:, :s + block], v[:, :s + block], s)
                         for s in range(0, S, block)], axis=1)
    return linear(o.reshape(B, S, H * Dh), w[prefix + "o_proj.weight"])


def mlp(w, prefix, y):
    """down(silu(gate y) * up y)."""
    return linear(jax.nn.silu(linear(y, w[prefix + "gate_proj.weight"]))
                  * linear(y, w[prefix + "up_proj.weight"]), w[prefix + "down_proj.weight"])


def out_norm(x, gain, eps):
    """A sublayer's OUTPUT norm (N2, N4): the band script leaves it out."""
    return rms_norm(x, gain, eps)


def layer(w, i, h, cfg, remat=False):
    """Block i, sandwich-normed: h [B, S, D] -> [B, S, D]."""
    eps, name = cfg["rms_norm_eps"], f"model.layers.{i}."
    att = attention(w, name + "self_attn.",
                    rms_norm(h, w[name + "input_layernorm.weight"], eps), cfg, remat)
    a = h + out_norm(att, w[name + "input_layernorm_2.weight"], eps)
    ff = mlp(w, name + "mlp.", rms_norm(a, w[name + "post_attention_layernorm.weight"], eps))
    return a + out_norm(ff, w[name + "post_attention_layernorm_2.weight"], eps)


def one_at_a_time(visit):
    """``visit(w, h) -> h`` computed again in the backward (jax.checkpoint),
    with its two gradients handed on TOGETHER (an optimization barrier: the
    same values). Without it the compiler puts off each visit's weight
    gradients, keeps the operands of dozens of them and runs out of memory."""
    visit = jax.checkpoint(visit)

    @jax.custom_vjp
    def tied(w, h):
        return visit(w, h)

    def forward(w, h):
        out, pull = jax.vjp(visit, w, h)
        return out, pull

    tied.defvjp(forward, lambda pull, g: jax.lax.optimization_barrier(pull(g)))
    return tied


def stack(w, h, cfg, remat=False):
    """The L blocks in order, a Python loop; block i gets its own weights."""
    for i in range(cfg["num_hidden_layers"]):
        own = {k: v for k, v in w.items() if k.startswith(f"model.layers.{i}.")}
        block = lambda own, h, i=i: layer(own, i, h, cfg, remat)
        h = (one_at_a_time(block) if remat else block)(own, h)
    return h


def next_input(raw, normed):
    """What step t + 1 takes in, of step t's stack output ``raw`` and its
    final norm ``normed``: the normed stream."""
    return normed


def gate_reads(raw, normed):
    """What the exit gate reads of the same two: the normed stream."""
    return normed


def exits(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, S] -> (the T normed streams, the T streams the gate
    reads), lists of [B, S, D]."""
    h = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
    normed, gated = [], []
    for _ in range(cfg["total_ut_steps"]):
        raw = stack(w, h, cfg, remat)
        n = rms_norm(raw, w["model.norm.weight"], cfg["rms_norm_eps"])
        normed.append(n)
        gated.append(gate_reads(raw, n))
        h = next_input(raw, n)
    return normed, gated


def gate(w, h):
    """lam [B, S] float32 = sigmoid(w_g . h + b_g): one Linear(D, 1) with bias."""
    z = h.astype(jnp.float32) @ w["model.early_exit_gate.weight"].astype(jnp.float32)[0]
    return jax.nn.sigmoid(z + w["model.early_exit_gate.bias"].astype(jnp.float32)[0])


def exit_distribution(lams):
    """lams: T arrays [B, S] -> p [T, B, S] float32: p_t = lam_t x what is
    left, the last exit takes what is left (its own lam is not used)."""
    left, p = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def entropy(p):
    """H(p) [B, S] = -sum_t p_t log p_t (0 log 0 = 0)."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)


def token_ce(w, h, labels):
    """One exit, some positions: the normed stream h [B, s, D] -> CE [B, s]
    float32 from the full logits [B, s, V]."""
    logits = linear(h, w["lm_head.weight"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def exit_ce(w, h, labels, remat=False):
    """One exit: CE [B, S], ``LOGIT_ROWS`` positions at a time (a Python
    loop; the values are those of the whole [B, S, V] form)."""
    S = h.shape[1]
    rows = LOGIT_ROWS if S % LOGIT_ROWS == 0 else S
    head = jax.checkpoint(token_ce) if remat else token_ce
    return jnp.concatenate([head(w, h[:, s:s + rows], labels[:, s:s + rows])
                            for s in range(0, S, rows)], axis=1)


def objective(p, ce, beta):
    """The Stage-I loss a token [B, S]: sum_t p_t CE_t - beta H(p)."""
    return jnp.sum(p * ce, axis=0) - beta * entropy(p)


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, S + 1] -> dict: ``loss``, ``exit_ce`` [T] (mean CE_t),
    ``exit_mass`` [T] (mean p_t), ``entropy`` (mean H), ``expected_steps``
    (mean of sum_t t p_t), ``p`` [T, B, S] and ``ce`` [T, B, S]."""
    labels = batch_ids[:, 1:]
    with jax.default_matmul_precision("highest"):
        normed, gated = exits(w, cfg, batch_ids[:, :-1], dtype, remat)
        ce = jnp.stack([exit_ce(w, h, labels, remat) for h in normed])
        p = exit_distribution([gate(w, h) for h in gated])
    beta = float(cfg.get("exit_entropy_beta", BETA))
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None, None]
    return {"loss": jnp.mean(objective(p, ce, beta)),
            "exit_ce": jnp.mean(ce, axis=(1, 2)), "exit_mass": jnp.mean(p, axis=(1, 2)),
            "entropy": jnp.mean(entropy(p)),
            "expected_steps": jnp.mean(jnp.sum(steps * p, axis=0)), "p": p, "ce": ce}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    HD = cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,), "model.early_exit_gate.weight": (1, D),
              "model.early_exit_gate.bias": (1,)}
    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        shapes.update({name + norm + ".weight": (D,) for norm in (
            "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
            "post_attention_layernorm_2")})
        shapes.update({name + f"self_attn.{x}_proj.weight": (HD, D) for x in "qkv"})
        shapes.update({name + "self_attn.o_proj.weight": (D, HD),
                       name + "mlp.gate_proj.weight": (F, D),
                       name + "mlp.up_proj.weight": (F, D),
                       name + "mlp.down_proj.weight": (D, F)})
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices; gains drawn
    from [0.5, 1.5), the gate's weight normal (its logit on a normed stream
    then varies over tokens with a deviation near 1) and its bias from
    [-0.5, 0.5], so that leaving one out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name == "model.early_exit_gate.bias":
            out[name] = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
        elif name == "model.early_exit_gate.weight":
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
