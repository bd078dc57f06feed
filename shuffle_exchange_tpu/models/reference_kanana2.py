"""A plain reference for kanana-2-30b-a3b (``kakaocorp/kanana-2-30b-a3b-
instruct-2601``, ``model_type: deepseek_v3``): the forward pass, the training
loss and its gradients in straightforward ``jax.numpy``, as the published
architecture describes them. ``tests/test_mla.py`` and ``tests/test_kanana2.py``
hold the program to it at small sizes; ``chipbench/reference_kanana2.py`` is a
byte-identical copy (below this docstring) that decides the cell
``kanana2-train``'s ``correct``.
"""

# Everything below is written from the source's modelling code
# (transformers' modeling_deepseek_v3.py) and its config.json. float32
# throughout and every matmul at jax.default_matmul_precision("highest") (a
# TPU otherwise multiplies float32 in bf16 passes); no kernel, no cache, no
# batching trick, nothing imported from shuffle_exchange_tpu. Weights are a
# flat dict under the source's own names, each matrix laid out as torch's
# nn.Linear stores it ([out, in]: y = x @ W.T):
#
#   model.embed_tokens.weight                                  [V, D]
#   model.layers.{i}.input_layernorm.weight                    [D]
#   model.layers.{i}.post_attention_layernorm.weight           [D]
#   model.layers.{i}.self_attn.q_proj.weight                   [H (dc + dr), D]
#   model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight       [r + dr, D]
#   model.layers.{i}.self_attn.kv_a_layernorm.weight           [r]
#   model.layers.{i}.self_attn.kv_b_proj.weight                [H (dc + dv), r]
#   model.layers.{i}.self_attn.o_proj.weight                   [D, H dv]
#   layers i < first_k_dense_replace (a dense SwiGLU of intermediate_size):
#   model.layers.{i}.mlp.{gate,up}_proj.weight                 [Fd, D]
#   model.layers.{i}.mlp.down_proj.weight                      [D, Fd]
#   the others (routed):
#   model.layers.{i}.mlp.gate.weight                           [E, D]
#   model.layers.{i}.mlp.gate.e_score_correction_bias          [E]
#   model.layers.{i}.mlp.experts.{e}.{gate,up}_proj.weight     [F, D]
#   model.layers.{i}.mlp.experts.{e}.down_proj.weight          [D, F]
#   model.layers.{i}.mlp.shared_experts.{gate,up}_proj.weight  [n_shared F, D]
#   model.layers.{i}.mlp.shared_experts.down_proj.weight       [D, n_shared F]
#   model.norm.weight                                          [D]
#   lm_head.weight                                             [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size,
# num_attention_heads, qk_nope_head_dim = dc, qk_rope_head_dim = dr,
# v_head_dim = dv, kv_lora_rank = r, rope_theta, rms_norm_eps,
# num_hidden_layers, first_k_dense_replace, intermediate_size,
# moe_intermediate_size, n_routed_experts, num_experts_per_tok,
# n_shared_experts, norm_topk_prob, routed_scaling_factor, vocab_size) plus,
# for one expert-parallel rank's share, ``num_experts_held`` and
# ``expert_first``: the experts [expert_first, expert_first +
# num_experts_held) exist here, the router still scores all
# ``n_routed_experts``.
#
# The equations (D = hidden_size, H heads):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a plain gain: the
#              block norms, the final norm and the latent's.
#   block i    h = h + attention(N(h));  h = h + ffn_i(N(h)).  Final N, untied
#              head, mean token cross-entropy.
#   attention  q = x Wq per head [q_c dc | q_r dr] (no query compression:
#              q_lora_rank null); [c | k_r] = x Wkv_a: the latent c (r wide)
#              and ONE rotary key k_r (dr) a token; c_n = N(c; g_kv);
#              [k_c dc | v dv] = c_n Wkv_b per head. RoPE (theta, dr dims, no
#              scaling) on q_r of every head and on k_r: the source stores the
#              rotary dims as adjacent pairs (rope_interleave) and moves the
#              even ones in front of the odd ones before its rotate-half;
#              k = [k_c | k_r for every head]; causal
#              softmax(q k^T / sqrt(dc + dr)) v in float32; y = concat(o) Wo.
#   dense ffn  (silu(x Wg) * x Wu) Wd at intermediate_size: layers below
#              first_k_dense_replace.
#   routed ffn s = sigmoid(float32(x) Wr^T) over all E; the k largest of
#              s + b chosen (b = e_score_correction_bias, a buffer: no
#              gradient; n_group = topk_group = 1: no group limit); weights
#              w = s[chosen] (WITHOUT b), w / (sum w + 1e-20)
#              (norm_topk_prob), times routed_scaling_factor; routed = sum
#              over the token's choices THAT ARE HELD HERE of w_k E_{i_k}(x),
#              as a loop over the held experts with masks; shared = ONE SwiGLU
#              of width n_shared_experts * moe_intermediate_size, no gate;
#              ffn = routed + shared.
#   balance    transformers' modelling code computes no balancing loss. The
#              family's published training recipe (the DeepSeek-V3 report,
#              section 2.1.2) has two pieces, both here, both off unless
#              ``cfg`` has their keys: the complementary sequence-wise balance
#              loss ``aux_loss_alpha`` x sum over the routed layers of the
#              mean over the sequences of sum_e f_e P_e, f_e = E / (k T) x the
#              sequence's token-choices of expert e, P_e the sequence's mean
#              of s_e / sum_j s_j (``balance_loss``); and the aux-free update
#              of the selection bias after each step, b_e += gamma x sign(mean
#              load - load_e) over the step's batch (``bias_update``; gamma =
#              ``bias_update_speed``).
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says (the source
#     does too);
#   - a rank's share: the source computes every expert; with
#     ``num_experts_held`` the routed sum runs over the held experts only and
#     what the absent ones would add is left out (another rank's part);
#   - ``remat`` wraps each layer, each head's scores and each expert in
#     jax.checkpoint: the same values, computed again in the backward, so that
#     a row of 8192 tokens fits a 16 GB chip;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the router and the cross-entropy stay float32.

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def latent_norm(c, gain, eps):
    """The norm on the latent (kv_a_layernorm)."""
    return rms_norm(c, gain, eps)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x [B, T, H, dr], stored as adjacent pairs: the source's
    ``apply_rotary_pos_emb_interleave``. The even dims move in front of the
    odd ones, then the rotate-half rotation over all dr dims."""
    B, T, H, dr = x.shape
    x = x.reshape(B, T, H, dr // 2, 2).swapaxes(-1, -2).reshape(B, T, H, dr)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, dr]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return x * cos + rotate_half(x) * sin


def rope_query(q_r, theta):
    return rope(q_r, theta)


def rope_key(k_r, theta):
    return rope(k_r, theta)


def join(content, rotary):
    """A head's query or key: [content | rotary]."""
    return jnp.concatenate([content, rotary], axis=-1)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def score_scale(cfg):
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def softmax_rows(scores):
    """Causal scores [.., T, T] float32 -> probabilities, float32."""
    return jax.nn.softmax(scores, axis=-1)


def attention(w, prefix, x, cfg, remat=False):
    B, T, D = x.shape
    H = cfg["num_attention_heads"]
    dc, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    r = cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    q = linear(x, w[prefix + "q_proj.weight"]).reshape(B, T, H, dc + dr)
    q_c, q_r = q[..., :dc], q[..., dc:]
    down = linear(x, w[prefix + "kv_a_proj_with_mqa.weight"])      # [B, T, r + dr]
    c, k_r = down[..., :r], down[..., r:]
    c = latent_norm(c, w[prefix + "kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    kv = linear(c, w[prefix + "kv_b_proj.weight"]).reshape(B, T, H, dc + dv)
    k_c, v = kv[..., :dc], kv[..., dc:]
    q_r = rope_query(q_r, theta)
    k_r = rope_key(k_r[:, :, None, :], theta)                       # one key a token
    q = join(q_c, q_r)
    k = join(k_c, jnp.broadcast_to(k_r, (B, T, H, dr)))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = score_scale(cfg)

    def head(h):
        """whole [T, T] scores of one head."""
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h],
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", softmax_rows(scores).astype(x.dtype), v[:, :, h])

    o = jax.lax.map(jax.checkpoint(head) if remat else head, jnp.arange(H))  # [H, B, T, dv]
    return linear(jnp.moveaxis(o, 0, 2).reshape(B, T, H * dv), w[prefix + "o_proj.weight"])


def router_logits(w, prefix, y):
    """y [N, D] -> [N, E] float32: the router multiplies in float32."""
    return y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T


def choose(logits, bias, cfg):
    """The source's ``noaux_tc`` with one group, on the router's logits
    [N, E] and its selection bias [E]: (s [N, E] float32 scores, chosen
    [N, k] int32, weight [N, k])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = weight * float(cfg.get("routed_scaling_factor", 1.0))
    return s, chosen.astype(jnp.int32), weight


def route(w, prefix, y, cfg):
    """y [N, D] -> ``choose`` of the layer's logits and bias."""
    return choose(router_logits(w, prefix, y),
                  w[prefix + "gate.e_score_correction_bias"], cfg)


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["n_routed_experts"])


def mlp(w, name, y):
    return linear(jax.nn.silu(linear(y, w[name + "gate_proj.weight"]))
                  * linear(y, w[name + "up_proj.weight"]), w[name + "down_proj.weight"])


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
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
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


def layer(w, i, x, cfg, remat=False):
    """Block i: x [B, T, D] -> (x, router scores, chosen experts, their
    weights); the last three are None for a dense layer."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps)
    h = x + attention(w, name + "self_attn.", y, cfg, remat)
    y = rms_norm(h, w[name + "post_attention_layernorm.weight"], eps)
    if is_dense(i, cfg):
        return h + mlp(w, name + "mlp.", y), None, None, None
    out, s, chosen, weight = experts(w, name + "mlp.", y.reshape(B * T, D), cfg, remat)
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
    E = cfg["n_routed_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def expert_weight(routing, cfg):
    """[routed layers, E] float32: the sum of the weights of the token-choices
    each expert of each routed layer received."""
    E = cfg["n_routed_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.float32)
                              * jax.lax.stop_gradient(r["weight"])[..., None],
                              axis=(0, 1)) for r in routing])


def balance_loss(routing, cfg, sequences):
    """The sequence-wise balance loss WITHOUT its alpha: the sum over the
    routed layers of the mean over the ``sequences`` of sum_e f_e P_e."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
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
    ``aux_loss_alpha`` x ``balance_loss`` where ``cfg`` has it), ``logits`` [B, T, V], ``expert_tokens`` [routed layers,
    E], ``expert_weight`` [routed layers, E], ``held_rows`` [routed layers]
    (the token-choices that fell on the held experts), ``routing``."""
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
    r, E = cfg["kv_lora_rank"], cfg["n_routed_experts"]
    Fd, F = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = F * cfg["n_shared_experts"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}

    def swiglu(name, width):
        return {name + "gate_proj.weight": (width, D),
                name + "up_proj.weight": (width, D),
                name + "down_proj.weight": (D, width)}

    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        a, m = name + "self_attn.", name + "mlp."
        shapes.update({name + "input_layernorm.weight": (D,),
                       name + "post_attention_layernorm.weight": (D,),
                       a + "q_proj.weight": (H * (dc + dr), D),
                       a + "kv_a_proj_with_mqa.weight": (r + dr, D),
                       a + "kv_a_layernorm.weight": (r,),
                       a + "kv_b_proj.weight": (H * (dc + dv), r),
                       a + "o_proj.weight": (D, H * dv)})
        if is_dense(i, cfg):
            shapes.update(swiglu(m, Fd))
            continue
        shapes[m + "gate.weight"] = (E, D)
        shapes[m + "gate.e_score_correction_bias"] = (E,)
        for e in range(first, end):
            shapes.update(swiglu(f"{m}experts.{e}.", F))
        shapes.update(swiglu(m + "shared_experts.", Fs))
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices; gains drawn
    from [0.5, 1.5) and the selection bias from a normal of 0.05, wide
    against the spread of the sigmoid scores of a random router: so that
    leaving one out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("e_score_correction_bias"):
            out[name] = 0.05 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
