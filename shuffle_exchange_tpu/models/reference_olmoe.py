"""The plain reference for OLMoE (``allenai/OLMoE-1B-7B-0125-Instruct``,
``model_type: olmoe``): forward, loss and, through ``jax.grad`` of the loss,
gradients, in straightforward ``jax.numpy``. This is the repository's copy;
``chipbench/reference_olmoe.py`` is the benchmark's own, byte-identical below
this docstring (``tests/test_olmoe.py`` compares them).
"""

# Everything below is written from the source's modelling code
# (transformers' modeling_olmoe.py) and its config.json. float32 throughout
# and every matmul at jax.default_matmul_precision("highest") (a TPU
# otherwise multiplies float32 in bf16 passes); no kernel, no scan, no cache,
# no batching trick, nothing imported from shuffle_exchange_tpu. Weights are a
# flat dict under the source's own names, each matrix laid out as torch's
# nn.Linear stores it ([out, in]: y = x @ W.T):
#
#   model.embed_tokens.weight                          [V, D]
#   model.layers.{i}.input_layernorm.weight            [D]
#   model.layers.{i}.self_attn.{q,k,v,o}_proj.weight   [D, D]
#   model.layers.{i}.self_attn.{q,k}_norm.weight       [D]
#   model.layers.{i}.post_attention_layernorm.weight   [D]
#   model.layers.{i}.mlp.gate.weight                   [E, D]
#   model.layers.{i}.mlp.experts.{e}.{gate,up}_proj.weight   [F, D]
#   model.layers.{i}.mlp.experts.{e}.down_proj.weight        [D, F]
#   model.norm.weight                                  [D]
#   lm_head.weight                                     [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size,
# num_attention_heads, num_hidden_layers, num_experts, num_experts_per_tok,
# rms_norm_eps, rope_theta, norm_topk_prob; router_aux_loss_coef defaults to
# the modelling code's 0.01).
#
# The equations:
#   block      h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));
#              final RMSNorm; untied head; mean token cross-entropy
#   RMSNorm    x / sqrt(mean(x^2) + eps) * g
#   attention  q = x Wq, k = x Wk, v = x Wv (no bias);
#              q = RMSNorm(q) * g_q, k = RMSNorm(k) * g_k over the WHOLE
#              projection (all heads together); split into heads; RoPE
#              (rotate-half over the whole head); causal
#              softmax(q k^T / sqrt(head_dim)) v; Wo
#   experts    p = softmax(float32(y) Wr); the k largest p; weights are those
#              p, NOT renormalised (norm_topk_prob false);
#              out = sum_i p_i * (silu(y Wg_i) * (y Wu_i)) Wd_i, as a loop
#              over the experts with masks: every token reaches all its k
#   aux        load_balancing_loss_func: f[j, e] = mean over the tokens of all
#              layers of onehot(choice j)[e]; P[e] = mean over the same tokens
#              of p[e]; aux = E * sum_{j,e} f[j, e] * P[e];
#              loss = CE + router_aux_loss_coef * aux
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model. The
#     source runs all T + 1 positions and shifts the logits, which scores the
#     same T predictions but lets the router's statistics see one more
#     position per row;
#   - the router multiplies in float32 whatever ``dtype`` says (the source
#     multiplies in the model's dtype and takes the softmax in float32);
#   - the source's pretraining also used a router z-loss that neither its
#     config.json nor its modelling code carries: left out;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it, norms, softmaxes, the router and the cross-entropy stay float32.

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x [B, T, H, Dh]: rotary position embedding over the whole head."""
    _, T, _, Dh = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, Dh]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return x * cos + rotate_half(x) * sin


def linear(x, w):
    return x @ w.astype(x.dtype).T


def attention(w, prefix, x, cfg):
    B, T, D = x.shape
    H = cfg["num_attention_heads"]
    Dh = D // H
    eps = cfg["rms_norm_eps"]
    q = linear(x, w[prefix + "q_proj.weight"])
    k = linear(x, w[prefix + "k_proj.weight"])
    v = linear(x, w[prefix + "v_proj.weight"])
    q = rms_norm(q, w[prefix + "q_norm.weight"], eps)
    k = rms_norm(k, w[prefix + "k_norm.weight"], eps)
    q = rope(q.reshape(B, T, H, Dh), float(cfg["rope_theta"]))
    k = rope(k.reshape(B, T, H, Dh), float(cfg["rope_theta"]))
    v = v.reshape(B, T, H, Dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / (Dh ** 0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, D)
    return linear(out, w[prefix + "o_proj.weight"])


def route(w, prefix, y, cfg):
    """y [N, D] -> (p [N, E] float32, chosen [N, k] int32, weight [N, k])."""
    k = cfg["num_experts_per_tok"]
    logits = y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T
    p = jax.nn.softmax(logits, axis=-1)
    weight, chosen = jax.lax.top_k(p, k)
    if cfg.get("norm_topk_prob", False):
        weight = weight / weight.sum(axis=-1, keepdims=True)
    return p, chosen.astype(jnp.int32), weight


def experts(w, prefix, y, cfg):
    """y [N, D] -> (out [N, D], p [N, E], chosen [N, k])."""
    E = cfg["num_experts"]
    p, chosen, weight = route(w, prefix, y, cfg)
    out = jnp.zeros(y.shape, jnp.float32)
    for e in range(E):
        name = f"{prefix}experts.{e}."
        # this expert's weight for every token: its routing probability where
        # it is one of the token's k, else 0
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        hidden = (jax.nn.silu(linear(y, w[name + "gate_proj.weight"]))
                  * linear(y, w[name + "up_proj.weight"]))
        out = out + mine[:, None] * linear(hidden, w[name + "down_proj.weight"]
                                           ).astype(jnp.float32)
    return out.astype(y.dtype), p, chosen


def forward(w, cfg, input_ids, dtype=jnp.float32):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per layer the router probabilities ``p`` [B*T, E] and the chosen
    experts ``chosen`` [B*T, k]."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        B, T, D = x.shape
        routing = []
        for i in range(cfg["num_hidden_layers"]):
            layer = f"model.layers.{i}."
            h = x + attention(w, layer + "self_attn.",
                              rms_norm(x, w[layer + "input_layernorm.weight"], eps), cfg)
            y = rms_norm(h, w[layer + "post_attention_layernorm.weight"], eps)
            out, p, chosen = experts(w, layer + "mlp.", y.reshape(B * T, D), cfg)
            x = h + out.reshape(B, T, D)
            routing.append({"p": p, "chosen": chosen})
        x = rms_norm(x, w["model.norm.weight"], eps)
        logits = linear(x, w["lm_head.weight"]).astype(jnp.float32)
    return logits, routing


def balancing_loss(routing, cfg):
    """The source's ``load_balancing_loss_func`` (no attention mask): all
    layers' tokens concatenated."""
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


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32):
    """batch_ids [B, T + 1] -> dict: ``loss`` (CE + coef * aux), ``ce``,
    ``aux``, ``logits`` [B, T, V], ``expert_tokens`` [L, E], ``routing``."""
    logits, routing = forward(w, cfg, batch_ids[:, :-1], dtype)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    aux = balancing_loss(routing, cfg)
    return {"loss": ce + cfg.get("router_aux_loss_coef", 0.01) * aux,
            "ce": ce, "aux": aux, "logits": logits,
            "expert_tokens": expert_tokens(routing, cfg), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32):
    return loss_parts(w, cfg, batch_ids, dtype)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype)


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) elsewhere; the norms' gains
    drawn around 1 so that leaving one out shows."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    E, V = cfg["num_experts"], cfg["vocab_size"]
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}
    for i in range(cfg["num_hidden_layers"]):
        layer = f"model.layers.{i}."
        for n in ("q", "k", "v", "o"):
            shapes[f"{layer}self_attn.{n}_proj.weight"] = (D, D)
        for n in ("self_attn.q_norm", "self_attn.k_norm", "input_layernorm",
                  "post_attention_layernorm"):
            shapes[f"{layer}{n}.weight"] = (D,)
        shapes[layer + "mlp.gate.weight"] = (E, D)
        for e in range(E):
            shapes[f"{layer}mlp.experts.{e}.gate_proj.weight"] = (F, D)
            shapes[f"{layer}mlp.experts.{e}.up_proj.weight"] = (F, D)
            shapes[f"{layer}mlp.experts.{e}.down_proj.weight"] = (D, F)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        draw = jax.random.normal(key, shape, jnp.float32)
        if len(shape) == 1:
            out[name] = 1.0 + 0.1 * draw
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * draw
        else:
            out[name] = draw / (shape[1] ** 0.5)
    return out
