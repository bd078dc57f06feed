"""A plain reference for Qwen3-Next (``Qwen/Qwen3-Next-80B-A3B-Instruct``,
``model_type: qwen3_next``): the forward pass, the training loss and its
gradients in straightforward ``jax.numpy``, as the published architecture
describes them. ``tests/test_qwen3next.py`` holds the program to it at small
sizes; ``chipbench/reference_qwen3next.py`` is a byte-identical copy (below
this docstring) that decides the cell ``qwen3next-train``'s ``correct``.
"""

# Everything below is written from the source's modelling code
# (transformers' modeling_qwen3_next.py) and its config.json. float32
# throughout and every matmul at jax.default_matmul_precision("highest") (a
# TPU otherwise multiplies float32 in bf16 passes); no kernel, no chunked
# rule, no cache, no batching trick, nothing imported from
# shuffle_exchange_tpu. Weights are a flat dict under the source's own names,
# each matrix laid out as torch's nn.Linear stores it ([out, in]: y = x @ W.T):
#
#   model.embed_tokens.weight                              [V, D]
#   model.layers.{i}.input_layernorm.weight                [D]
#   model.layers.{i}.post_attention_layernorm.weight       [D]
#   every 4th layer (full attention):
#   model.layers.{i}.self_attn.q_proj.weight               [H * 2 Dh, D]
#   model.layers.{i}.self_attn.{k,v}_proj.weight           [KV * Dh, D]
#   model.layers.{i}.self_attn.o_proj.weight               [D, H * Dh]
#   model.layers.{i}.self_attn.{q,k}_norm.weight           [Dh]
#   the others (Gated DeltaNet):
#   model.layers.{i}.linear_attn.in_proj_qkvz.weight       [2 Hk dk + 2 Hv dv, D]
#   model.layers.{i}.linear_attn.in_proj_ba.weight         [2 Hv, D]
#   model.layers.{i}.linear_attn.conv1d.weight             [2 Hk dk + Hv dv, 1, K]
#   model.layers.{i}.linear_attn.{A_log,dt_bias}           [Hv]
#   model.layers.{i}.linear_attn.norm.weight               [dv]
#   model.layers.{i}.linear_attn.out_proj.weight           [D, Hv dv]
#   every layer:
#   model.layers.{i}.mlp.gate.weight                       [E, D]
#   model.layers.{i}.mlp.experts.{e}.{gate,up}_proj.weight [F, D]
#   model.layers.{i}.mlp.experts.{e}.down_proj.weight      [D, F]
#   model.layers.{i}.mlp.shared_expert.{gate,up}_proj.weight  [Fs, D]
#   model.layers.{i}.mlp.shared_expert.down_proj.weight    [D, Fs]
#   model.layers.{i}.mlp.shared_expert_gate.weight         [1, D]
#   model.norm.weight                                      [D]
#   lm_head.weight                                         [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size, head_dim,
# num_attention_heads, num_key_value_heads, num_hidden_layers,
# full_attention_interval, partial_rotary_factor, rope_theta, rms_norm_eps,
# linear_num_key_heads, linear_num_value_heads, linear_key_head_dim,
# linear_value_head_dim, linear_conv_kernel_dim, num_experts,
# num_experts_per_tok, norm_topk_prob, moe_intermediate_size,
# shared_expert_intermediate_size, router_aux_loss_coef) plus, for one
# expert-parallel rank's share, ``num_experts_held`` and ``expert_first``:
# the experts [expert_first, expert_first + num_experts_held) exist here, the
# router still scores all ``num_experts``.
#
# The equations (D = hidden_size):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w): zero-centred
#              gain. The block norms, the final norm, q_norm and k_norm.
#   block i    h = h + mixer_i(N(h));  h = h + moe(N(h)).  mixer_i is full
#              attention when (i + 1) % full_attention_interval == 0, else
#              Gated DeltaNet. Final N, untied head, mean token cross-entropy.
#   attention  [q | gate] = x Wq split PER HEAD into Dh + Dh; k = x Wk,
#              v = x Wv; q = N(q; w_q), k = N(k; w_k) per head over its Dh;
#              RoPE (rotate-half) on the first partial_rotary_factor * Dh
#              dims of each head; causal softmax(q k^T / sqrt(Dh)) v, each
#              KV head serving H / KV query heads;
#              y = (o * sigmoid(gate)) Wo
#   DeltaNet   x W_qkvz grouped per key head [q dk | k dk | v r dv | z r dv]
#              (r = Hv / Hk) and x W_ba per key head [b r | a r];
#              [all q | all k | all v] through a depthwise causal convolution
#              of width K (left pad K - 1, no bias), then SiLU;
#              beta = sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias);
#              q, k repeated r times to Hv heads, each x * rsqrt(sum(x^2) +
#              1e-6), q scaled by dk^-0.5; per head, S_0 = 0 [dk, dv]:
#                S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);
#                S <- S + k_t u_t^T;  o_t = S^T q_t
#              one token at a time; output norm per head over dv with a PLAIN
#              gain: w * o * rsqrt(mean(o^2) + eps) * silu(z);
#              y = o.reshape(T, Hv dv) W_out
#   experts    p = softmax(float32(x) Wr) over all E; the k largest p; weights
#              p_k / sum_k p (norm_topk_prob); routed = sum over the token's
#              choices THAT ARE HELD HERE of w_k E_{i_k}(x), as a loop over
#              the held experts with masks; E(x) = (silu(x Wg) * x Wu) Wd;
#              shared = sigmoid(x w_s) * E_shared(x); moe = routed + shared
#   aux        load_balancing_loss_func over all E experts: f[j, e] = mean
#              over the tokens of all layers of onehot(choice j)[e]; P[e] =
#              mean over the same tokens of p[e]; aux = E * sum_{j,e} f * P;
#              loss = CE + router_aux_loss_coef * aux
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the multi-token-prediction module is left out (the source's causal-LM
#     class does not load it either);
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: the source computes every expert; with
#     ``num_experts_held`` the routed sum runs over the held experts only and
#     what the absent ones would add is left out (another rank's part);
#   - ``remat`` wraps each layer, each expert and each 64 steps of the
#     recurrence in jax.checkpoint: the same values, computed again in the
#     backward, so that a row of 8192 tokens fits a 16 GB chip;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the router, g, beta, the state S and the
#     cross-entropy stay float32.

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    """Zero-centred gain: x / rms(x) * (1 + gain), over the last axis."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gain.astype(jnp.float32))).astype(x.dtype)


def l2norm(x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)
            ).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta, rotary):
    """x [B, T, H, Dh]: rotary position embedding on the first ``rotary``
    dims of each head; the rest pass through."""
    T = x.shape[1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, rotary]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    rot, rest = x[..., :rotary], x[..., rotary:]
    return jnp.concatenate([rot * cos + rotate_half(rot) * sin, rest], axis=-1)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def output_gate(o, gate):
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


def attention(w, prefix, x, cfg, remat=False):
    B, T, D = x.shape
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    rotary = int(Dh * cfg.get("partial_rotary_factor", 1.0))
    qg = linear(x, w[prefix + "q_proj.weight"]).reshape(B, T, H, 2 * Dh)
    q, gate = qg[..., :Dh], qg[..., Dh:]
    k = linear(x, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh)
    v = linear(x, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    q = rope(rms_norm(q, w[prefix + "q_norm.weight"], eps), float(cfg["rope_theta"]), rotary)
    k = rope(rms_norm(k, w[prefix + "k_norm.weight"], eps), float(cfg["rope_theta"]), rotary)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        """whole [T, T] scores of one query head: q [B, T, Dh], k, v."""
        qh, kh, vh = qkv
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh).astype(jnp.float32) / (Dh ** 0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1).astype(x.dtype), vh)

    per_kv = H // KV
    heads = jnp.arange(H)
    o = jax.lax.map(
        jax.checkpoint(lambda h: head((q[:, :, h], k[:, :, h // per_kv], v[:, :, h // per_kv])))
        if remat else
        (lambda h: head((q[:, :, h], k[:, :, h // per_kv], v[:, :, h // per_kv]))),
        heads)                                                   # [H, B, T, Dh]
    o = output_gate(jnp.moveaxis(o, 0, 2), gate)
    return linear(o.reshape(B, T, H * Dh), w[prefix + "o_proj.weight"])


def causal_conv(x, weight):
    """x [B, T, C]; weight [C, 1, K] as torch's depthwise Conv1d stores it,
    padding K - 1 on the left, no bias: y[t] = sum_j weight[c, 0, j] *
    x[t - (K - 1) + j]."""
    K, T = weight.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * weight[:, 0, j].astype(x.dtype) for j in range(K))


def write_strength(b):
    return jax.nn.sigmoid(b.astype(jnp.float32))


def log_decay(a, A_log, dt_bias):
    return -jnp.exp(A_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))


def delta_rule(q, k, v, g, beta, remat=False, state_bits=None):
    """The gated delta rule one token at a time. q, k [B, T, H, dk],
    v [B, T, H, dv], g, beta [B, T, H] float32 -> o [B, T, H, dv] float32.
    ``state_bits`` (exponent, mantissa): S is rounded to that after every
    token ((8, 7) is bf16; None: float32, not rounded). Only the band's
    measurement of a lower precision sets it."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    keep = (lambda S: S) if state_bits is None else (
        lambda S: jax.lax.reduce_precision(S, *state_bits))

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = keep(S + kt[..., :, None] * u[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, dv), f32)
    inner = 64
    if not remat or T % inner:
        return jnp.moveaxis(jax.lax.scan(step, S0, xs)[1], 0, 1)
    # a scan of scans: only every 64th state is kept for the backward
    blocks = tuple(a.reshape((T // inner, inner) + a.shape[1:]) for a in xs)
    _, o = jax.lax.scan(jax.checkpoint(lambda S, blk: jax.lax.scan(step, S, blk)),
                        S0, blocks)
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def delta_net(w, prefix, x, cfg, remat=False):
    B, T, D = x.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = Hv // Hk
    qkvz = linear(x, w[prefix + "in_proj_qkvz.weight"]).reshape(B, T, Hk, 2 * dk + 2 * r * dv)
    ba = linear(x, w[prefix + "in_proj_ba.weight"]).reshape(B, T, Hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(B, T, Hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(B, T, Hv, dv)
    b, a = ba[..., :r].reshape(B, T, Hv), ba[..., r:].reshape(B, T, Hv)
    mixed = jnp.concatenate([q.reshape(B, T, Hk * dk), k.reshape(B, T, Hk * dk),
                             v.reshape(B, T, Hv * dv)], axis=-1)
    mixed = jax.nn.silu(causal_conv(mixed, w[prefix + "conv1d.weight"]))
    q = mixed[..., :Hk * dk].reshape(B, T, Hk, dk)
    k = mixed[..., Hk * dk:2 * Hk * dk].reshape(B, T, Hk, dk)
    v = mixed[..., 2 * Hk * dk:].reshape(B, T, Hv, dv)
    beta = write_strength(b)
    g = log_decay(a, w[prefix + "A_log"], w[prefix + "dt_bias"])
    q = l2norm(jnp.repeat(q, r, axis=2)) * (dk ** -0.5)
    k = l2norm(jnp.repeat(k, r, axis=2))
    o = delta_rule(q, k, v, g, beta, remat=remat)                # float32
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = w[prefix + "norm.weight"].astype(jnp.float32) * o * jax.nn.silu(z.astype(jnp.float32))
    return linear(o.astype(x.dtype).reshape(B, T, Hv * dv), w[prefix + "out_proj.weight"])


def route(w, prefix, y, cfg):
    """y [N, D] -> (p [N, E] float32, chosen [N, k] int32, weight [N, k])."""
    k = cfg["num_experts_per_tok"]
    logits = y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T
    p = jax.nn.softmax(logits, axis=-1)
    weight, chosen = jax.lax.top_k(p, k)
    if cfg.get("norm_topk_prob", False):
        weight = weight / weight.sum(axis=-1, keepdims=True)
    return p, chosen.astype(jnp.int32), weight


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["num_experts"])


def mlp(w, name, y):
    return linear(jax.nn.silu(linear(y, w[name + "gate_proj.weight"]))
                  * linear(y, w[name + "up_proj.weight"]), w[name + "down_proj.weight"])


def shared_gate(w, prefix, y):
    return jax.nn.sigmoid(linear(y, w[prefix + "shared_expert_gate.weight"])
                          .astype(jnp.float32))


def experts(w, prefix, y, cfg, remat=False):
    """y [N, D] -> (out [N, D], p [N, E], chosen [N, k]): the held experts'
    part of the routed sum, plus the shared expert."""
    p, chosen, weight = route(w, prefix, y, cfg)
    first, end = held_range(cfg)
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    out = jnp.zeros(y.shape, jnp.float32)
    for e in range(first, end):
        # this expert's weight for every token: its (normalised) routing
        # probability where it is one of the token's k, else 0
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        out = out + mine[:, None] * one(w, f"{prefix}experts.{e}.", y).astype(jnp.float32)
    shared = one(w, prefix + "shared_expert.", y).astype(jnp.float32)
    out = out + shared_gate(w, prefix, y) * shared
    return out.astype(y.dtype), p, chosen


def is_full_attention(i, cfg):
    return (i + 1) % cfg.get("full_attention_interval", 4) == 0


def layer(w, i, x, cfg, remat=False):
    """Block i: x [B, T, D] -> (x, router probabilities, chosen experts)."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps)
    if is_full_attention(i, cfg):
        h = x + attention(w, name + "self_attn.", y, cfg, remat)
    else:
        h = x + delta_net(w, name + "linear_attn.", y, cfg, remat)
    y = rms_norm(h, w[name + "post_attention_layernorm.weight"], eps)
    out, p, chosen = experts(w, name + "mlp.", y.reshape(B * T, D), cfg, remat)
    return h + out.reshape(B, T, D), p, chosen


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per layer the router probabilities ``p`` [B*T, E] and the chosen
    experts ``chosen`` [B*T, k]."""
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        routing = []
        for i in range(cfg["num_hidden_layers"]):
            block = (jax.checkpoint(layer, static_argnums=(1, 3, 4))
                     if remat else layer)
            x, p, chosen = block(w, i, x, _Static(cfg), remat)
            routing.append({"p": p, "chosen": chosen})
        x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
        logits = linear(x, w["lm_head.weight"]).astype(jnp.float32)
    return logits, routing


class _Static(dict):
    """``cfg`` as a hashable static argument of jax.checkpoint."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def balancing_loss(routing, cfg):
    """The source's ``load_balancing_loss_func`` (no attention mask): all
    layers' tokens concatenated, over all the router's experts."""
    E = cfg["num_experts"]
    p = jnp.concatenate([r["p"] for r in routing], axis=0)               # [L*N, E]
    chosen = jnp.concatenate([r["chosen"] for r in routing], axis=0)     # [L*N, k]
    f = jnp.mean(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=0)   # [k, E]
    P = jnp.mean(p, axis=0)                                              # [E]
    return E * jnp.sum(f * P[None, :])


def expert_tokens(routing, cfg):
    """[L, E] int32: the token-choices each expert of each layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> dict: ``loss`` (CE + coef * aux), ``ce``,
    ``aux``, ``logits`` [B, T, V], ``expert_tokens`` [L, E], ``held_rows``
    [L] (the token-choices that fell on the held experts), ``routing``."""
    logits, routing = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    aux = balancing_loss(routing, cfg)
    tokens = expert_tokens(routing, cfg)
    first, end = held_range(cfg)
    return {"loss": ce + cfg.get("router_aux_loss_coef", 0.001) * aux,
            "ce": ce, "aux": aux, "logits": logits, "expert_tokens": tokens,
            "held_rows": tokens[:, first:end].sum(axis=1), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    F, Fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}
    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        shapes[name + "input_layernorm.weight"] = (D,)
        shapes[name + "post_attention_layernorm.weight"] = (D,)
        if is_full_attention(i, cfg):
            a = name + "self_attn."
            shapes.update({a + "q_proj.weight": (H * 2 * Dh, D),
                           a + "k_proj.weight": (KV * Dh, D),
                           a + "v_proj.weight": (KV * Dh, D),
                           a + "o_proj.weight": (D, H * Dh),
                           a + "q_norm.weight": (Dh,), a + "k_norm.weight": (Dh,)})
        else:
            a = name + "linear_attn."
            conv = 2 * Hk * dk + Hv * dv
            shapes.update({a + "in_proj_qkvz.weight": (2 * Hk * dk + 2 * Hv * dv, D),
                           a + "in_proj_ba.weight": (2 * Hv, D),
                           a + "conv1d.weight": (conv, 1, cfg["linear_conv_kernel_dim"]),
                           a + "A_log": (Hv,), a + "dt_bias": (Hv,),
                           a + "norm.weight": (dv,),
                           a + "out_proj.weight": (D, Hv * dv)})
        m = name + "mlp."
        shapes[m + "gate.weight"] = (cfg["num_experts"], D)
        for e in list(range(first, end)) + ["shared"]:
            ex = m + ("shared_expert." if e == "shared" else f"experts.{e}.")
            width = Fs if e == "shared" else F
            shapes.update({ex + "gate_proj.weight": (width, D),
                           ex + "up_proj.weight": (width, D),
                           ex + "down_proj.weight": (D, width)})
        shapes[m + "shared_expert_gate.weight"] = (1, D)
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices; zero-centred
    gains drawn from [-0.5, 0.5), the DeltaNet output gain from [0.5, 1.5),
    A_log = log U(0, 16), dt_bias around 1: so that leaving one out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("A_log"):
            out[name] = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))
        elif name.endswith("dt_bias"):
            out[name] = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
        elif name.endswith("linear_attn.norm.weight"):
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            fan_in = shape[-1] if len(shape) == 3 else shape[1]
            out[name] = jax.random.normal(key, shape, jnp.float32) / (fan_in ** 0.5)
    return out
