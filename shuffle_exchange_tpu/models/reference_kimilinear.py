"""A plain reference for Kimi-Linear-48B-A3B (``moonshotai/Kimi-Linear-48B-
A3B-Instruct``, ``model_type: kimi_linear``): the forward pass, the training
loss and its gradients in straightforward ``jax.numpy``, as the published
architecture describes them. ``tests/test_kimilinear.py`` holds the program to
it at small sizes; ``chipbench/reference_kimilinear.py`` is a byte-identical
copy (below this docstring) that decides the cell ``kimilinear-train``'s
``correct``.
"""

# Everything below is written from the family's report ("Kimi Linear: An
# Expressive, Efficient Attention Architecture", arXiv:2510.26692), the
# published config.json and the modelling code as ISSUE 67 recalls it
# (modeling_kimi.py, fla's KimiDeltaAttention). float32 throughout and every
# matmul at jax.default_matmul_precision("highest") (a TPU otherwise
# multiplies float32 in bf16 passes); no kernel, no cache, no chunked form of
# the rule, nothing imported from shuffle_exchange_tpu. Weights are a flat
# dict under the source's own names, each matrix laid out as torch's nn.Linear
# stores it ([out, in]: y = x @ W.T), a depthwise convolution as nn.Conv1d
# does ([C, 1, K]):
#
#   model.embed_tokens.weight                                     [V, D]
#   model.layers.{i}.input_layernorm.weight                       [D]
#   model.layers.{i}.post_attention_layernorm.weight              [D]
#   a KDA layer (i + 1 in linear_attn_config.kda_layers), Hk heads of dk:
#   model.layers.{i}.self_attn.{q,k,v}_proj.weight                [Hk dk, D]
#   model.layers.{i}.self_attn.{q,k,v}_conv1d.weight              [Hk dk, 1, K]
#   model.layers.{i}.self_attn.b_proj.weight                      [Hk, D]
#   model.layers.{i}.self_attn.f_a_proj.weight                    [dk, D]
#   model.layers.{i}.self_attn.f_b_proj.weight                    [Hk dk, dk]
#   model.layers.{i}.self_attn.A_log                              [Hk]
#   model.layers.{i}.self_attn.dt_bias                            [Hk dk]
#   model.layers.{i}.self_attn.g_a_proj.weight                    [dk, D]
#   model.layers.{i}.self_attn.g_b_proj.weight                    [Hk dk, dk]
#   model.layers.{i}.self_attn.o_norm.weight                      [dk]
#   model.layers.{i}.self_attn.o_proj.weight                      [D, Hk dk]
#   a latent-attention layer (i + 1 in full_attn_layers), H heads:
#   model.layers.{i}.self_attn.q_proj.weight                      [H (dc + dr), D]
#   model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight          [r + dr, D]
#   model.layers.{i}.self_attn.kv_a_layernorm.weight              [r]
#   model.layers.{i}.self_attn.kv_b_proj.weight                   [H (dc + dv), r]
#   model.layers.{i}.self_attn.o_proj.weight                      [D, H dv]
#   layers i < first_k_dense_replace (a dense SwiGLU of intermediate_size):
#   model.layers.{i}.mlp.{gate,up}_proj.weight                    [Fd, D]
#   model.layers.{i}.mlp.down_proj.weight                         [D, Fd]
#   the others (routed):
#   model.layers.{i}.block_sparse_moe.gate.weight                 [E, D]
#   model.layers.{i}.block_sparse_moe.gate.e_score_correction_bias [E]
#   model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight       [F, D]  (gate)
#   model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight       [F, D]  (up)
#   model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight       [D, F]  (down)
#   model.layers.{i}.block_sparse_moe.shared_experts.{gate,up}_proj.weight [n_shared F, D]
#   model.layers.{i}.block_sparse_moe.shared_experts.down_proj.weight      [D, n_shared F]
#   model.norm.weight                                             [D]
#   lm_head.weight                                                [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size,
# num_attention_heads, qk_nope_head_dim = dc, qk_rope_head_dim = dr,
# v_head_dim = dv, kv_lora_rank = r, rms_norm_eps, num_hidden_layers,
# linear_attn_config {kda_layers, full_attn_layers (both counted from 1),
# num_heads, head_dim, short_conv_kernel_size}, first_k_dense_replace,
# intermediate_size, moe_intermediate_size, num_experts,
# num_experts_per_token, num_shared_experts, moe_renormalize,
# routed_scaling_factor, vocab_size) plus, for one expert-parallel rank's
# share, ``num_experts_held`` and ``expert_first``: the experts [expert_first,
# expert_first + num_experts_held) exist here, the router still scores all
# ``num_experts``.
#
# The equations (D = hidden_size):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a plain gain: the
#              block norms, the final norm, the latent's and (per head) the
#              KDA output's.
#   block i    h = h + mixer_i(N(h));  h = h + ffn_i(N(h)).  Final N, untied
#              head, mean token cross-entropy.
#   KDA        q = l2(silu(conv(x Wq))) dk^-1/2, k = l2(silu(conv(x Wk))),
#              v = silu(conv(x Wv)): conv a causal depthwise convolution of K
#              taps without bias (zeros before position 0), l2 per head with
#              eps 1e-6 inside the root; beta = sigmoid(x Wb) a head;
#              g = -exp(A_log[h]) softplus(x Wfa Wfb + dt_bias) [Hk, dk], a
#              log-decay for EVERY KEY CHANNEL. Per head, S [dk, dv] from 0,
#              one token at a time:
#                  S <- diag(exp(g_t)) S;  u_t = beta_t (v_t - S^T k_t);
#                  S <- S + k_t u_t^T;     o_t = S^T q_t
#              y = (N(o; w_o) * sigmoid(x Wga Wgb)) Wo, N and the gate per head.
#   attention  q = x Wq per head [q_c dc | q_r dr] (no query compression);
#              [c | k_r] = x Wkv_a: the latent c (r wide) and ONE more key k_r
#              (dr) a token; c_n = N(c; g_kv); [k_c dc | v dv] = c_n Wkv_b per
#              head. NOTHING is rotated (mla_use_nope: the "rope" dims stay as
#              projected; the KDA layers carry the order); k = [k_c | k_r for
#              every head]; causal softmax(q k^T / sqrt(dc + dr)) v in
#              float32, a block of queries at a time; y = concat(o) Wo.
#   dense ffn  (silu(x Wg) * x Wu) Wd at intermediate_size: layers below
#              first_k_dense_replace.
#   routed ffn s = sigmoid(float32(x) Wr^T) over all E; the k largest of
#              s + b chosen (b = e_score_correction_bias, a buffer: no
#              gradient; one group: no group limit); weights w = s[chosen]
#              (WITHOUT b), w / (sum w + 1e-20) (moe_renormalize), times
#              routed_scaling_factor; routed = sum over the token's choices
#              THAT ARE HELD HERE of w_k E_{i_k}(x), as a loop over the held
#              experts with masks; shared = ONE SwiGLU of width
#              num_shared_experts * moe_intermediate_size, no gate;
#              ffn = routed + shared.
#   balance    the config states none. Assumed, as kanana-2's cell assumes it
#              of the same router form (DeepSeek-V3's recipe), both off unless
#              ``cfg`` has their keys: the sequence-wise balance loss
#              ``aux_loss_alpha`` x sum over the routed layers of the mean
#              over the sequences of sum_e f_e P_e (``balance_loss``), and the
#              aux-free update of the selection bias after each step
#              (``bias_update``; gamma = ``bias_update_speed``).
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: with ``num_experts_held`` the routed sum runs over the
#     held experts only and what the absent ones would add is left out;
#   - ``remat`` wraps each layer, each block of the rule's tokens, each head's
#     block of scores and each expert in jax.checkpoint: the same values,
#     computed again in the backward, so that a row of 16,384 tokens fits a
#     16 GB chip;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the decay, the rule's state, the router and the
#     cross-entropy stay float32.

import jax
import jax.numpy as jnp

# tokens a block of the rule's scan keeps nothing of under ``remat``, and
# queries a block of the attention's scores
RULE_BLOCK = 128
QUERY_BLOCK = 2048


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def latent_norm(c, gain, eps):
    """The norm on the latent (kv_a_layernorm)."""
    return rms_norm(c, gain, eps)


def l2norm(x):
    """x / sqrt(sum x^2 + 1e-6) over a head's channels, in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)
            ).astype(x.dtype)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def short_conv(x, w):
    """x [B, T, C], w [C, 1, K] (nn.Conv1d, groups = C, no bias): y[t, c] =
    sum_j w[c, 0, j] x[t - (K - 1) + j, c], zeros before position 0."""
    K, T = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[:, 0, j].astype(x.dtype) for j in range(K))


def log_decay(w, prefix, x, cfg):
    """g [B, T, Hk, dk] float32 <= 0: a log-decay for every key channel."""
    lin = cfg["linear_attn_config"]
    Hk, dk = lin["num_heads"], lin["head_dim"]
    B, T, _ = x.shape
    a = linear(linear(x, w[prefix + "f_a_proj.weight"]), w[prefix + "f_b_proj.weight"])
    a = a.astype(jnp.float32) + w[prefix + "dt_bias"].astype(jnp.float32)
    A = jnp.exp(w[prefix + "A_log"].astype(jnp.float32).reshape(Hk))
    return -A[:, None] * jax.nn.softplus(a.reshape(B, T, Hk, dk))


def write_strength(w, prefix, x):
    """beta [B, T, Hk] float32 in (0, 1)."""
    return jax.nn.sigmoid(linear(x, w[prefix + "b_proj.weight"]).astype(jnp.float32))


def output_gate(z):
    """The KDA output's gate: a sigmoid (not the scalar rule's SiLU)."""
    return jax.nn.sigmoid(z)


def rule_step(S, qt, kt, vt, gt, bt):
    """One token of the rule, all heads: S [B, H, dk, dv], qt, kt, gt
    [B, H, dk], vt [B, H, dv], bt [B, H] -> (S, o_t [B, H, dv])."""
    S = S * jnp.exp(gt)[..., None]
    u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
    S = S + kt[..., :, None] * u[..., None, :]
    return S, jnp.einsum("bhkv,bhk->bhv", S, qt)


def delta_rule(q, k, v, g, beta, remat=False):
    """The rule, one token at a time, float32: q, k, g [B, T, H, dk], v
    [B, T, H, dv], beta [B, T, H] -> o [B, T, H, dv]. Under ``remat`` the
    scan runs in blocks of ``RULE_BLOCK`` tokens that keep their first state
    alone."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))

    def step(S, x):
        return rule_step(S, *x)

    S0 = jnp.zeros((B, H, dk, dv), f32)
    n = RULE_BLOCK
    if not remat or T % n:
        o = jax.lax.scan(step, S0, xs)[1]
    else:
        block = jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x))
        o = jax.lax.scan(block, S0, tuple(
            a.reshape((T // n, n) + a.shape[1:]) for a in xs))[1]
        o = o.reshape((T,) + o.shape[2:])
    return jnp.moveaxis(o, 0, 1)


def kda(w, prefix, x, cfg, remat=False):
    """The KDA mixer on the normed block input x [B, T, D] -> [B, T, D]."""
    lin = cfg["linear_attn_config"]
    Hk, dk = lin["num_heads"], lin["head_dim"]
    B, T, _ = x.shape
    eps = cfg["rms_norm_eps"]
    branch = lambda n: jax.nn.silu(short_conv(
        linear(x, w[prefix + n + "_proj.weight"]), w[prefix + n + "_conv1d.weight"])
    ).reshape(B, T, Hk, dk)
    q = l2norm(branch("q")) * dk ** -0.5
    k = l2norm(branch("k"))
    v = branch("v")
    g = log_decay(w, prefix, x, cfg)
    beta = write_strength(w, prefix, x)
    o = delta_rule(q, k, v, g, beta, remat)
    z = linear(linear(x, w[prefix + "g_a_proj.weight"]), w[prefix + "g_b_proj.weight"])
    o = rms_norm(o, w[prefix + "o_norm.weight"], eps) * output_gate(
        z.astype(jnp.float32).reshape(B, T, Hk, dk))
    return linear(o.astype(x.dtype).reshape(B, T, Hk * dk), w[prefix + "o_proj.weight"])


def place_query(q_r):
    """What becomes of a query's ``dr`` extra dims: nothing (mla_use_nope)."""
    return q_r


def place_key(k_r):
    """And of the one extra key a token: nothing."""
    return k_r


def join(content, extra):
    """A head's query or key: [content | extra]."""
    return jnp.concatenate([content, extra], axis=-1)


def score_scale(cfg):
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def softmax_rows(scores):
    """Causal scores [.., Tq, T] float32 -> probabilities, float32."""
    return jax.nn.softmax(scores, axis=-1)


def attention(w, prefix, x, cfg, remat=False):
    B, T, D = x.shape
    H = cfg["num_attention_heads"]
    dc, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    r = cfg["kv_lora_rank"]
    q = linear(x, w[prefix + "q_proj.weight"]).reshape(B, T, H, dc + dr)
    q_c, q_r = q[..., :dc], q[..., dc:]
    down = linear(x, w[prefix + "kv_a_proj_with_mqa.weight"])      # [B, T, r + dr]
    c, k_r = down[..., :r], down[..., r:]
    c = latent_norm(c, w[prefix + "kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    kv = linear(c, w[prefix + "kv_b_proj.weight"]).reshape(B, T, H, dc + dv)
    k_c, v = kv[..., :dc], kv[..., dc:]
    q = join(q_c, place_query(q_r))
    k = join(k_c, jnp.broadcast_to(place_key(k_r[:, :, None, :]), (B, T, H, dr)))
    scale = score_scale(cfg)
    Tq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    keys = jnp.arange(T)

    def head(qkv):
        """one head, a block of Tq queries at a time against every key."""
        qh, kh, vh = qkv                                           # [B, T, d]

        def block(at):
            first, qb = at
            scores = jnp.einsum("bqd,bkd->bqk", qb, kh,
                                preferred_element_type=jnp.float32) * scale
            seen = (first + jnp.arange(Tq))[:, None] >= keys[None, :]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", softmax_rows(scores).astype(x.dtype), vh)

        blocks = jnp.moveaxis(qh.reshape(B, T // Tq, Tq, dc + dr), 1, 0)
        o = jax.lax.map(jax.checkpoint(block) if remat else block,
                        (jnp.arange(0, T, Tq), blocks))            # [T / Tq, B, Tq, dv]
        return jnp.moveaxis(o, 0, 1).reshape(B, T, dv)

    by_head = lambda a: jnp.moveaxis(a, 2, 0)
    o = jax.lax.map(jax.checkpoint(head) if remat else head,
                    (by_head(q), by_head(k), by_head(v)))          # [H, B, T, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(B, T, H * dv)
    return linear(o, w[prefix + "o_proj.weight"])


def router_logits(w, prefix, y):
    """y [N, D] -> [N, E] float32: the router multiplies in float32."""
    return y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T


def choose(logits, bias, cfg):
    """Sigmoid scores, the top k of score + bias in one group, on the router's
    logits [N, E] and its selection bias [E]: (s [N, E] float32 scores, chosen
    [N, k] int32, weight [N, k])."""
    k = cfg["num_experts_per_token"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("moe_renormalize", True):
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = weight * float(cfg.get("routed_scaling_factor", 1.0))
    return s, chosen.astype(jnp.int32), weight


def route(w, prefix, y, cfg):
    """y [N, D] -> ``choose`` of the layer's logits and bias."""
    return choose(router_logits(w, prefix, y),
                  w[prefix + "gate.e_score_correction_bias"], cfg)


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["num_experts"])


def swiglu(y, gate, up, down):
    return linear(jax.nn.silu(linear(y, gate)) * linear(y, up), down)


def mlp(w, name, y):
    return swiglu(y, w[name + "gate_proj.weight"], w[name + "up_proj.weight"],
                  w[name + "down_proj.weight"])


def expert(w, name, y):
    """One routed expert: w1 the gate, w3 the up, w2 the down projection."""
    return swiglu(y, w[name + "w1.weight"], w[name + "w3.weight"], w[name + "w2.weight"])


def shared(w, prefix, y, remat=False):
    """The shared experts: one SwiGLU of n_shared x the expert width, added
    as it is."""
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    return one(w, prefix + "shared_experts.", y).astype(jnp.float32)


def experts(w, prefix, y, cfg, remat=False):
    """y [N, D] -> (out [N, D], s [N, E], chosen [N, k], weight [N, k]): the
    held experts' part of the routed sum, plus the shared experts."""
    s, chosen, weight = route(w, prefix, y, cfg)
    first, end = held_range(cfg)
    one = jax.checkpoint(expert, static_argnums=(1,)) if remat else expert
    out = jnp.zeros(y.shape, jnp.float32)
    for e in range(first, end):
        # this expert's weight for every token: its scaled, normalised score
        # where it is one of the token's k, else 0
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        out = out + mine[:, None] * one(w, f"{prefix}experts.{e}.", y).astype(jnp.float32)
    out = out + shared(w, prefix, y, remat)
    return out.astype(y.dtype), s, chosen, weight


def is_dense(i, cfg):
    return i < int(cfg.get("first_k_dense_replace", 0))


def is_kda(i, cfg):
    """Layer i (from 0) runs the KDA mixer: the lists count from 1."""
    return (i + 1) in cfg["linear_attn_config"]["kda_layers"]


def layer(w, i, x, cfg, remat=False):
    """Block i: x [B, T, D] -> (x, router scores, chosen experts, their
    weights); the last three are None for a dense layer."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps)
    mixer = kda if is_kda(i, cfg) else attention
    h = x + mixer(w, name + "self_attn.", y, cfg, remat)
    y = rms_norm(h, w[name + "post_attention_layernorm.weight"], eps)
    if is_dense(i, cfg):
        return h + mlp(w, name + "mlp.", y), None, None, None
    out, s, chosen, weight = experts(w, name + "block_sparse_moe.",
                                     y.reshape(B * T, D), cfg, remat)
    return h + out.reshape(B, T, D), s, chosen, weight


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per ROUTED layer the router scores ``s`` [B*T, E], the chosen
    experts ``chosen`` [B*T, k] and their weights ``weight`` [B*T, k]."""
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        routing = []
        for i in range(cfg["num_hidden_layers"]):
            block = (jax.checkpoint(layer, static_argnums=(1, 3, 4))
                     if remat else layer)
            x, s, chosen, weight = block(w, i, x, _Static(cfg), remat)
            if chosen is not None:
                routing.append({"s": s, "chosen": chosen, "weight": weight})
        x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
        logits = linear(x, w["lm_head.weight"]).astype(jnp.float32)
    return logits, routing


class _Static(dict):
    """``cfg`` as a hashable static argument of jax.checkpoint."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def expert_tokens(routing, cfg):
    """[routed layers, E] int32: the token-choices each expert of each routed
    layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def expert_weight(routing, cfg):
    """[routed layers, E] float32: the sum of the weights of the token-choices
    each expert of each routed layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.float32)
                              * jax.lax.stop_gradient(r["weight"])[..., None],
                              axis=(0, 1)) for r in routing])


def balance_loss(routing, cfg, sequences):
    """The sequence-wise balance loss WITHOUT its alpha: the sum over the
    routed layers of the mean over the ``sequences`` of sum_e f_e P_e."""
    E, k = cfg["num_experts"], cfg["num_experts_per_token"]
    total = jnp.zeros((), jnp.float32)
    for r in routing:
        s = r["s"].reshape(sequences, -1, E)
        chosen = jax.nn.one_hot(r["chosen"], E, dtype=jnp.float32).sum(axis=-2)
        f = chosen.reshape(sequences, -1, E).mean(axis=1) * (E / k)
        p = (s / s.sum(axis=-1, keepdims=True)).mean(axis=1)
        total = total + jnp.mean(jnp.sum(f * p, axis=-1))
    return total


def bias_update(bias, tokens, gamma):
    """The aux-free update of one step: bias [routed layers, E], ``tokens``
    [routed layers, E] the token-choices of the step's batch; an expert with
    more than its layer's mean goes down by gamma, one with fewer up."""
    load = tokens.astype(jnp.float32)
    return bias + gamma * jnp.sign(load.mean(axis=-1, keepdims=True) - load)


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> dict: ``loss`` (the cross-entropy, plus
    ``aux_loss_alpha`` x ``balance_loss`` where ``cfg`` has it), ``logits``
    [B, T, V], ``expert_tokens`` [routed layers, E], ``expert_weight`` [routed
    layers, E], ``held_rows`` [routed layers] (the token-choices that fell on
    the held experts), ``routing``."""
    logits, routing = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    alpha = float(cfg.get("aux_loss_alpha") or 0.0)
    if alpha and cfg.get("seq_aux", True):
        ce = ce + alpha * balance_loss(routing, cfg, batch_ids.shape[0])
    tokens = expert_tokens(routing, cfg)
    first, end = held_range(cfg)
    return {"loss": ce, "logits": logits, "expert_tokens": tokens,
            "expert_weight": expert_weight(routing, cfg),
            "held_rows": tokens[:, first:end].sum(axis=1), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names (the selection bias is
    a buffer: its entry is zero)."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    dc, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    r, E = cfg["kv_lora_rank"], cfg["num_experts"]
    Fd, F = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = F * cfg["num_shared_experts"]
    lin = cfg["linear_attn_config"]
    Hk, dk, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}
    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        a = name + "self_attn."
        shapes.update({name + "input_layernorm.weight": (D,),
                       name + "post_attention_layernorm.weight": (D,)})
        if is_kda(i, cfg):
            for n in "qkv":
                shapes[a + n + "_proj.weight"] = (Hk * dk, D)
                shapes[a + n + "_conv1d.weight"] = (Hk * dk, 1, K)
            shapes.update({a + "b_proj.weight": (Hk, D), a + "f_a_proj.weight": (dk, D),
                           a + "f_b_proj.weight": (Hk * dk, dk), a + "A_log": (Hk,),
                           a + "dt_bias": (Hk * dk,), a + "g_a_proj.weight": (dk, D),
                           a + "g_b_proj.weight": (Hk * dk, dk), a + "o_norm.weight": (dk,),
                           a + "o_proj.weight": (D, Hk * dk)})
        else:
            shapes.update({a + "q_proj.weight": (H * (dc + dr), D),
                           a + "kv_a_proj_with_mqa.weight": (r + dr, D),
                           a + "kv_a_layernorm.weight": (r,),
                           a + "kv_b_proj.weight": (H * (dc + dv), r),
                           a + "o_proj.weight": (D, H * dv)})
        if is_dense(i, cfg):
            shapes.update({name + "mlp.gate_proj.weight": (Fd, D),
                           name + "mlp.up_proj.weight": (Fd, D),
                           name + "mlp.down_proj.weight": (D, Fd)})
            continue
        m = name + "block_sparse_moe."
        shapes[m + "gate.weight"] = (E, D)
        shapes[m + "gate.e_score_correction_bias"] = (E,)
        for e in range(first, end):
            shapes.update({f"{m}experts.{e}.w1.weight": (F, D),
                           f"{m}experts.{e}.w3.weight": (F, D),
                           f"{m}experts.{e}.w2.weight": (D, F)})
        shapes.update({m + "shared_experts.gate_proj.weight": (Fs, D),
                       m + "shared_experts.up_proj.weight": (Fs, D),
                       m + "shared_experts.down_proj.weight": (D, Fs)})
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices and taps; gains
    drawn from [0.5, 1.5) and the selection bias from a normal of 0.05, so
    that leaving one out shows; A = exp(A_log) from [1, 16) and the decay's
    bias from [-4, 0): decays that keep a few tokens to a few dozen."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("e_score_correction_bias"):
            out[name] = 0.05 * jax.random.normal(key, shape, jnp.float32)
        elif name.endswith("A_log"):
            out[name] = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            out[name] = jax.random.uniform(key, shape, jnp.float32, -4.0, 0.0)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[-1] ** 0.5)
    return out
