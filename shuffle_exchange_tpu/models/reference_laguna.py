"""A plain reference for Laguna-XS.2 (``poolside/Laguna-XS.2``, ``model_type:
laguna``): the forward pass, the training loss and its gradients in
straightforward ``jax.numpy``, as the published ``config.json`` describes the
architecture. ``tests/test_laguna.py`` holds the program to it at small sizes;
``chipbench/reference_laguna.py`` is a byte-identical copy (below this
docstring) that decides the cell ``laguna-train``'s ``correct``.
"""

# Everything below is written from the source's config.json (the catalog's
# row; there is no modelling code to read here: chipbench/LAGUNA.md lists what
# that leaves to be assumed). float32 throughout and every matmul at
# jax.default_matmul_precision("highest") (a TPU otherwise multiplies float32
# in bf16 passes); no kernel, no cache, no batching trick, nothing imported
# from shuffle_exchange_tpu. Weights are a flat dict under the family's
# conventional names, each matrix laid out as torch's nn.Linear stores it
# ([out, in]: y = x @ W.T); H_i = num_attention_heads_per_layer[i]:
#
#   model.embed_tokens.weight                                  [V, D]
#   model.layers.{i}.input_layernorm.weight                    [D]
#   model.layers.{i}.post_attention_layernorm.weight           [D]
#   model.layers.{i}.self_attn.q_proj.weight                   [H_i Dh, D]
#   model.layers.{i}.self_attn.{k,v}_proj.weight               [KV Dh, D]
#   model.layers.{i}.self_attn.o_proj.weight                   [D, H_i Dh]
#   layers whose mlp_layer_types[i] is "dense" (SwiGLU, intermediate_size):
#   model.layers.{i}.mlp.{gate,up}_proj.weight                 [Fd, D]
#   model.layers.{i}.mlp.down_proj.weight                      [D, Fd]
#   the others ("sparse"):
#   model.layers.{i}.mlp.gate.weight                           [E, D]
#   model.layers.{i}.mlp.experts.{e}.{gate,up}_proj.weight     [F, D]
#   model.layers.{i}.mlp.experts.{e}.down_proj.weight          [D, F]
#   model.layers.{i}.mlp.shared_expert.{gate,up}_proj.weight   [Fs, D]
#   model.layers.{i}.mlp.shared_expert.down_proj.weight        [D, Fs]
#   model.norm.weight                                          [D]
#   lm_head.weight                                             [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size, head_dim,
# num_key_value_heads, num_attention_heads_per_layer, layer_types,
# mlp_layer_types, sliding_window, rope_parameters, rms_norm_eps,
# num_hidden_layers: the FIRST that many entries of the three lists are the
# layers here, intermediate_size, moe_intermediate_size,
# shared_expert_intermediate_size, num_experts, num_experts_per_tok,
# moe_routed_scaling_factor, vocab_size) plus, for one expert-parallel rank's
# share, ``num_experts_held`` and ``expert_first`` (the experts [expert_first,
# expert_first + num_experts_held) exist here, the router still scores all
# ``num_experts``) and ``aux_loss_alpha`` (the balance loss below).
#
# The equations (D = hidden_size, Dh = head_dim, KV = num_key_value_heads):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a plain gain.
#   block i    h = h + attention_i(N(h));  h = h + ffn_i(N(h)).  Final N,
#              untied head, mean token cross-entropy.
#   attention  q = x Wq [H_i x Dh], k = x Wk, v = x Wv [KV x Dh]; query head
#              h reads KV head h // (H_i / KV). RoPE by the layer's TYPE
#              (rope_parameters[layer_types[i]]), rotate-half pairing over the
#              first partial_rotary_factor x Dh dims of each head, the rest
#              untouched: "default" = inverse frequencies theta^(-2j/d);
#              "yarn" = those blended with their / factor by the linear ramp
#              between the dims that turn beta_fast and beta_slow times over
#              original_max_position_embeddings (floor / ceil), and cos and
#              sin times attention_factor. Scores q k^T / sqrt(Dh); key j
#              visible to query i iff 0 <= i - j (full_attention) or
#              0 <= i - j < sliding_window (sliding_attention); softmax in
#              float32; y = concat(o) Wo. No gate on the output.
#   dense ffn  (silu(x Wg) * x Wu) Wd at intermediate_size.
#   sparse ffn s = sigmoid(float32(x) Wr^T) over all E; the k largest chosen;
#              weights w = s[chosen] / (sum of the chosen s + 1e-20) times
#              moe_routed_scaling_factor; routed = sum over the token's
#              choices THAT ARE HELD HERE of w_k E_{i_k}(x), as a loop over
#              the held experts with masks; shared = ONE SwiGLU of
#              shared_expert_intermediate_size, added as it is;
#              ffn = routed + shared.
#   balance    ``aux_loss_alpha`` x the sum over the sparse layers of the
#              mean over the sequences of sum_e f_e P_e, f_e = E / (k T) x
#              the sequence's token-choices of expert e, P_e the sequence's
#              mean of s_e / sum_j s_j (DeepSeek-V3's sequence-wise loss; off
#              without the key).
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: with ``num_experts_held`` the routed sum runs over the
#     held experts only and what the absent ones would add is left out;
#   - the masked softmax is computed a head and a block of ``QUERY_BLOCK``
#     queries at a time against ALL keys (a dense mask row block), so that
#     16,384 positions fit; the values are those of the whole [T, T] form;
#   - ``remat`` wraps each layer, each head, each query block and each expert
#     in jax.checkpoint: the same values, computed again in the backward;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the router and the cross-entropy stay float32.

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_parameters(cfg, kind):
    return cfg["rope_parameters"][kind]


def inverse_frequencies(rp, head_dim):
    """(inv_freq [d / 2] float32, the factor on cos and sin) of one layer
    type's ``rope_parameters``, d = partial_rotary_factor x head_dim."""
    d = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    theta = float(rp["rope_theta"])
    pos = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rp.get("rope_type", "default") == "default":
        return 1.0 / pos, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    factor, original = float(rp["factor"]), float(rp["original_max_position_embeddings"])
    fast, slow = float(rp.get("beta_fast", 32)), float(rp.get("beta_slow", 1))

    def turns_at(rotations):
        """The (real-valued) pair index whose frequency turns ``rotations``
        times over the original context."""
        return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(turns_at(fast)), 0), min(math.ceil(turns_at(slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv = (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)
    scale = rp.get("attention_factor")
    return inv, float(scale if scale is not None else 0.1 * math.log(factor) + 1.0)


def rope(x, rp):
    """x [B, T, H, Dh]: the first d dims of each head rotated (rotate-half
    pairs (i, i + d / 2)), the rest as they are."""
    Dh = x.shape[-1]
    inv, scale = inverse_frequencies(rp, Dh)
    d = 2 * inv.shape[0]
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, d]
    cos = (jnp.cos(angles) * scale)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * scale)[None, :, None, :].astype(x.dtype)
    turned = x[..., :d] * cos + rotate_half(x[..., :d]) * sin
    return jnp.concatenate([turned, x[..., d:]], axis=-1)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def heads_of(i, cfg):
    per_layer = cfg.get("num_attention_heads_per_layer")
    return int(per_layer[i]) if per_layer else int(cfg["num_attention_heads"])


def window_of(i, cfg):
    """The keys a query of layer i sees, itself included; 0 = all before it."""
    return int(cfg["sliding_window"]) if cfg["layer_types"][i] == "sliding_attention" else 0


def visible(rows, T, window):
    """[len(rows), T] bool: key j is visible to query i."""
    gap = rows[:, None] - jnp.arange(T)[None, :]
    return (gap >= 0) & ((gap < window) if window else True)


def kv_head(h, H, KV):
    """The KV head that query head h of H reads: consecutive groups of H / KV."""
    return h // (H // KV)


def softmax_rows(scores):
    """Masked scores [.., q, T] float32 -> probabilities, float32."""
    return jax.nn.softmax(scores, axis=-1)


def score_scale(cfg):
    return cfg["head_dim"] ** -0.5


def attention(w, prefix, x, cfg, i, remat=False):
    B, T, D = x.shape
    H, KV, Dh = heads_of(i, cfg), cfg["num_key_value_heads"], cfg["head_dim"]
    rp = rope_parameters(cfg, cfg["layer_types"][i])
    q = rope(linear(x, w[prefix + "q_proj.weight"]).reshape(B, T, H, Dh), rp)
    k = rope(linear(x, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh), rp)
    v = linear(x, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    window, scale = window_of(i, cfg), score_scale(cfg)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    starts = jnp.arange(0, T, block)

    def head(h):
        kh, vh = k[:, :, kv_head(h, H, KV)], v[:, :, kv_head(h, H, KV)]

        def rows(start):
            """[block, T] scores of one head's query block against all keys."""
            qb = jax.lax.dynamic_slice_in_dim(q[:, :, h], start, block, axis=1)
            scores = jnp.einsum("bqd,bkd->bqk", qb, kh,
                                preferred_element_type=jnp.float32) * scale
            seen = visible(start + jnp.arange(block), T, window)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", softmax_rows(scores).astype(x.dtype), vh)

        o = jax.lax.map(jax.checkpoint(rows) if remat else rows, starts)
        return jnp.moveaxis(o, 0, 1).reshape(B, T, Dh)               # [B, T, Dh]

    o = jax.lax.map(jax.checkpoint(head) if remat else head, jnp.arange(H))
    return linear(jnp.moveaxis(o, 0, 2).reshape(B, T, H * Dh), w[prefix + "o_proj.weight"])


def router_logits(w, prefix, y):
    """y [N, D] -> [N, E] float32: the router multiplies in float32."""
    return y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T


def choose(logits, cfg):
    """The router's logits [N, E] -> (s [N, E] float32 scores, chosen [N, k]
    int32, weight [N, k])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s, k)
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = weight * float(cfg.get("moe_routed_scaling_factor", 1.0))
    return s, chosen.astype(jnp.int32), weight


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["num_experts"])


def mlp(w, name, y):
    return linear(jax.nn.silu(linear(y, w[name + "gate_proj.weight"]))
                  * linear(y, w[name + "up_proj.weight"]), w[name + "down_proj.weight"])


def shared(w, prefix, y, remat=False):
    """The shared expert: one SwiGLU, added as it is."""
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    return one(w, prefix + "shared_expert.", y).astype(jnp.float32)


def experts(w, prefix, y, cfg, remat=False):
    """y [N, D] -> (out [N, D], s [N, E], chosen [N, k], weight [N, k]): the
    held experts' part of the routed sum, plus the shared expert."""
    s, chosen, weight = choose(router_logits(w, prefix, y), cfg)
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
    return cfg["mlp_layer_types"][i] == "dense"


def layer(w, i, x, cfg, remat=False):
    """Block i: x [B, T, D] -> (x, router scores, chosen experts, their
    weights); the last three are None for a dense layer."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps)
    h = x + attention(w, name + "self_attn.", y, cfg, i, remat)
    y = rms_norm(h, w[name + "post_attention_layernorm.weight"], eps)
    if is_dense(i, cfg):
        return h + mlp(w, name + "mlp.", y), None, None, None
    out, s, chosen, weight = experts(w, name + "mlp.", y.reshape(B * T, D), cfg, remat)
    return h + out.reshape(B, T, D), s, chosen, weight


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per SPARSE layer the router scores ``s`` [B*T, E], the chosen
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
    """[sparse layers, E] int32: the token-choices each expert of each sparse
    layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def balance_loss(routing, cfg, sequences):
    """The sequence-wise balance loss WITHOUT its alpha: the sum over the
    sparse layers of the mean over the ``sequences`` of sum_e f_e P_e."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    total = jnp.zeros((), jnp.float32)
    for r in routing:
        s = r["s"].reshape(sequences, -1, E)
        chosen = jax.nn.one_hot(r["chosen"], E, dtype=jnp.float32).sum(axis=-2)
        f = chosen.reshape(sequences, -1, E).mean(axis=1) * (E / k)
        p = (s / s.sum(axis=-1, keepdims=True)).mean(axis=1)
        total = total + jnp.mean(jnp.sum(f * p, axis=-1))
    return total


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> dict: ``loss`` (the cross-entropy, plus
    ``aux_loss_alpha`` x ``balance_loss`` where ``cfg`` has it), ``logits``
    [B, T, V], ``expert_tokens`` [sparse layers, E], ``held_rows`` [sparse
    layers] (the token-choices that fell on the held experts), ``routing``."""
    logits, routing = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    alpha = float(cfg.get("aux_loss_alpha") or 0.0)
    if alpha:
        ce = ce + alpha * balance_loss(routing, cfg, batch_ids.shape[0])
    tokens = expert_tokens(routing, cfg)
    first, end = held_range(cfg)
    return {"loss": ce, "logits": logits, "expert_tokens": tokens,
            "held_rows": tokens[:, first:end].sum(axis=1), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    KV, Dh, E = cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"]
    Fd, F = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = cfg["shared_expert_intermediate_size"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}

    def swiglu(name, width):
        return {name + "gate_proj.weight": (width, D),
                name + "up_proj.weight": (width, D),
                name + "down_proj.weight": (D, width)}

    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        a, m, H = name + "self_attn.", name + "mlp.", heads_of(i, cfg)
        shapes.update({name + "input_layernorm.weight": (D,),
                       name + "post_attention_layernorm.weight": (D,),
                       a + "q_proj.weight": (H * Dh, D),
                       a + "k_proj.weight": (KV * Dh, D),
                       a + "v_proj.weight": (KV * Dh, D),
                       a + "o_proj.weight": (D, H * Dh)})
        if is_dense(i, cfg):
            shapes.update(swiglu(m, Fd))
            continue
        shapes[m + "gate.weight"] = (E, D)
        for e in range(first, end):
            shapes.update(swiglu(f"{m}experts.{e}.", F))
        shapes.update(swiglu(m + "shared_expert.", Fs))
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices; gains drawn
    from [0.5, 1.5) so that leaving one out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
