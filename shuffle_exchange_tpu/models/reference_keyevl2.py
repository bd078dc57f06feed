"""A plain reference for the language model of Kwai-Keye's Keye-VL-2.0-30B-A3B
(``model_type: KeyeVL2``): forward pass, both losses and gradients in
``jax.numpy``, float32, every matmul at ``jax.default_matmul_precision(
"highest")``; no kernel, no cache, no batching trick, nothing imported from
``shuffle_exchange_tpu``. This file is in the repository TWICE, byte for byte:
``shuffle_exchange_tpu/models/reference_keyevl2.py`` (the program's tests hold
the program to it) and ``chipbench/reference_keyevl2.py`` (the cell
``keyevl2-train``'s ``correct`` is decided by it, and the benchmark imports
nothing of the program to judge it). ``tests/test_keyevl2.py`` holds the two
together.
"""

# Written from the source's config.json (the catalog's row) and, for what it
# leaves open, the Qwen3-MoE family's convention and DeepSeek-V3.2-Exp's
# published indexer and sparse training stage (no network here: the
# configuration file's ``assumed`` lists each such item). The vision tower is
# NOT here: what it leaves in the language model is three position streams a
# token. Weights are a flat dict, each matrix laid out as torch's nn.Linear
# stores it ([out, in]: y = x @ W.T); H = num_attention_heads, KV =
# num_key_value_heads, Dh = head_dim, D = hidden_size, E = num_experts, F =
# moe_intermediate_size, Hi / Di = sa_config's indexer_num_heads /
# indexer_head_dim:
#
#   model.embed_tokens.weight                                   [V, D]
#   model.layers.{i}.input_layernorm.weight                     [D]
#   model.layers.{i}.post_attention_layernorm.weight            [D]
#   model.layers.{i}.self_attn.q_proj.weight                    [H Dh, D]
#   model.layers.{i}.self_attn.{k,v}_proj.weight                [KV Dh, D]
#   model.layers.{i}.self_attn.o_proj.weight                    [D, H Dh]
#   model.layers.{i}.self_attn.{q,k}_norm.weight                [Dh]
#   model.layers.{i}.self_attn.indexer.wq.weight                [Hi Di, D]
#   model.layers.{i}.self_attn.indexer.wk.weight                [Di, D]
#   model.layers.{i}.self_attn.indexer.k_norm.{weight,bias}     [Di]
#   model.layers.{i}.self_attn.indexer.weights_proj.weight      [Hi, D]
#   model.layers.{i}.mlp.gate.weight                            [E, D]
#   model.layers.{i}.mlp.experts.{e}.{gate,up}_proj.weight      [F, D]
#   model.layers.{i}.mlp.experts.{e}.down_proj.weight           [D, F]
#   model.norm.weight                                           [D]
#   lm_head.weight                                              [V, D]
#
# ``cfg`` is the source's config.json as a dict, plus, for one expert-parallel
# rank's share, ``num_experts_held`` and ``expert_first``, plus
# ``router_aux_loss_coef``.
#
# The equations, block i on x [T, D] with sg = stop_gradient:
#   y      = N(x; g1)                N(x; g) = x * rsqrt(mean(x^2) + eps) * g
#   q,k,v  = y Wq [H x Dh], y Wk, y Wv [KV x Dh]; q and k normed per head over
#            Dh (gains gq, gk) BEFORE the rotation; query head h reads KV head
#            h // (H / KV)
#   M-RoPE   rotate-half pairs (j, j + Dh/2), pair j by theta^(-2j/Dh) times
#            position stream 0 for j < 16, 1 for 16 <= j < 40, 2 for 40 <= j
#            (mrope_section [16, 24, 24], chunked); text: the streams are equal
#   indexer  qI = sg(y) WIq [Hi x Di], kI = LayerNorm(sg(y) WIk; gain, bias)
#            [Di], both rotated over all Di dims (pairs (i, i + Di/2)) by
#            stream 0, w = sg(y) WIw [Hi],
#            I[t, s] = Hi^-1/2 Di^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])
#   S_t    = the min(t + 1, topk) keys s <= t of largest I[t, s], ties to the
#            earlier key (jax.lax.top_k's order); no gradient
#   P_h    = softmax over s in S_t of q_h[t] . k[s] / sqrt(Dh), float32
#   a      = concat_h(sum over S_t of P_h[t, s] v[s]);  h = x + a Wo
#   LI     = mean over layers and t of KL(p[t] || softmax over S_t of I[t]),
#            p[t, s] = (1 / H) sum_h sg(P_h[t, s])
#   y2     = N(h; g2);  s = y2 Wr^T [E] float32; chosen = the 8 largest of
#            softmax(s), weights renormalised to sum 1
#   out    = h + sum over the token's choices THAT ARE HELD HERE of
#            weight * ((silu(y2 Wg_e^T) * (y2 Wu_e^T)) Wd_e^T)
#   then N(.; g_f), the untied head, mean token cross-entropy L_LM.
#   L      = L_LM + router_aux_loss_coef x balance + LI,
#            balance = HF's load_balancing_loss_func over ALL layers' tokens.
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: with ``num_experts_held`` the routed sum runs over the
#     held experts only and what the absent ones would add is left out;
#   - the core is computed a block of ``QUERY_BLOCK`` queries at a time
#     against ALL keys under a dense row-block of the mask, head after head,
#     so that 16,384 positions fit; the values are those of the whole form;
#   - ``remat`` wraps each layer, each query block, each head of it and each
#     expert in jax.checkpoint: the same values, computed again in the backward;
#   - ``selected`` hands ``attention`` the sets S_t (a [B, T, T] mask) in
#     place of its own: the comparison of the arithmetic under ONE selection;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the indexer's sums, the router and the
#     cross-entropy stay float32.

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
MOE = "mlp."
INDEXER = "indexer."


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, gain, bias, eps):
    """(x - mean) / sqrt(var + eps) * gain + bias over the last axis, float32."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * gain.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def text_positions(B, T):
    """[3, B, T]: on text the three streams are the position in the sequence."""
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, B, T))


def rope(x, theta, positions, sections=None):
    """x [B, T, H, Dh] rotated over all Dh dims (rotate-half pairs
    (j, j + Dh / 2), plain inverse frequencies): pair j turns by its frequency
    times the position stream ``sections`` gives it (M-RoPE, chunked: the
    first sections[0] pairs stream 0, the next sections[1] stream 1, ...;
    None: stream 0 for every pair). positions [3, B, T]."""
    Dh = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    pos = positions.astype(jnp.float32)
    if sections is None:
        per_pair = pos[0][..., None] * jnp.ones((Dh // 2,), jnp.float32)
    else:
        stream = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                            total_repeat_length=Dh // 2)
        per_pair = jnp.moveaxis(pos, 0, -1)[..., stream]              # [B, T, Dh/2]
    angles = per_pair * inv
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]  # [B, T, 1, Dh]
    return x * jnp.cos(angles).astype(x.dtype) + rotate_half(x) * jnp.sin(angles).astype(x.dtype)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def kv_head(h, H, KV):
    """The KV head that query head h of H reads: consecutive groups of H / KV."""
    return h // (H // KV)


def indexer_gate(x):
    """The nonlinearity on a head's score before the heads are weighed: ReLU."""
    return jax.nn.relu(x)


def indexer_inputs(w, prefix, y, cfg, positions):
    """The indexer's (qI [B, T, Hi, Di], kI [B, T, Di], w [B, T, Hi]) from
    the DETACHED normed block input y [B, T, D]."""
    sa = cfg["sa_config"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    B, T, _ = y.shape
    yd = jax.lax.stop_gradient(y)
    p = prefix + INDEXER
    qi = linear(yd, w[p + "wq.weight"]).reshape(B, T, Hi, Di)
    ki = layer_norm(linear(yd, w[p + "wk.weight"]), w[p + "k_norm.weight"],
                    w[p + "k_norm.bias"], cfg["rms_norm_eps"])
    qi = rope(qi, cfg["rope_theta"], positions)
    ki = rope(ki[:, :, None, :], cfg["rope_theta"], positions)[:, :, 0]
    return qi, ki, linear(yd, w[p + "weights_proj.weight"])


def index_scores(qi, ki, wi):
    """I of a block of queries against every key: qi [B, q, Hi, Di],
    ki [B, T, Di], wi [B, q, Hi] -> [B, q, T] float32."""
    Hi, Di = qi.shape[-2:]
    dots = jnp.einsum("bqhd,bkd->bqhk", qi, ki, preferred_element_type=jnp.float32)
    weighed = jnp.einsum("bqh,bqhk->bqk", wi.astype(jnp.float32), indexer_gate(dots))
    return (Hi ** -0.5) * (Di ** -0.5) * weighed


def causal(rows, T):
    """[len(rows), T] bool: key s <= query t."""
    return jnp.arange(T)[None, :] <= rows[:, None]


def choose_keys(scores, seen, topk):
    """S_t of a block: scores [B, q, T] float32, seen [q, T] bool -> [B, q, T]
    bool, the min(seen, topk) largest seen entries of each row, ties to the
    earlier key (jax.lax.top_k's order)."""
    k = min(int(topk), scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(seen[None], scores, -jnp.inf), k)
    picked = jnp.zeros(scores.shape, bool)
    b = jnp.arange(scores.shape[0])[:, None, None]
    q = jnp.arange(scores.shape[1])[None, :, None]
    return picked.at[b, q, idx].set(True) & seen[None]


def core_softmax(scores, axis=-1):
    """The core's softmax over the chosen keys (the others at -inf), float32."""
    return jax.nn.softmax(scores, axis=axis)


def head_average(probabilities):
    """p of the indexer's loss from the heads' [H, B, q, T] probabilities:
    their mean (each sums to 1 over S_t, so the mean does)."""
    return jnp.mean(probabilities, axis=0)


def attention(w, prefix, y, cfg, remat=False, positions=None, selected=None,
              keep=False):
    """The mixer on its normed input y [B, T, D] -> (out [B, T, D], parts):
    ``kl`` the SUM over b, t of KL(p || softmax over S_t of I), ``held`` [B, T]
    the keys each query holds; with ``keep`` also ``scores`` [B, T, T] (I),
    ``mask`` [B, T, T] (S_t) and ``p`` [B, T, T]. ``selected`` [B, T, T]:
    the sets to use in place of the indexer's own choice."""
    B, T, D = y.shape
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    topk = cfg["sa_config"]["topk"]
    positions = text_positions(B, T) if positions is None else positions
    sections = (cfg.get("rope_scaling") or {}).get("mrope_section")
    q = linear(y, w[prefix + "q_proj.weight"]).reshape(B, T, H, Dh)
    k = linear(y, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh)
    v = linear(y, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    q = rms_norm(q, w[prefix + "q_norm.weight"], eps)
    k = rms_norm(k, w[prefix + "k_norm.weight"], eps)
    q, k = rope(q, theta, positions, sections), rope(k, theta, positions, sections)
    qi, ki, wi = indexer_inputs(w, prefix, y, cfg, positions)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    scale = Dh ** -0.5

    def rows(start):
        part = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=1)
        seen = causal(start + jnp.arange(block), T)
        scores = index_scores(part(qi), ki, part(wi))                # [B, q, T]
        mask = (choose_keys(jax.lax.stop_gradient(scores), seen, topk)
                if selected is None else part(selected).astype(bool) & seen[None])
        qb = part(q)

        def head(h):
            g = kv_head(h, H, KV)
            s = jnp.einsum("bqd,bkd->bqk", qb[:, :, h], k[:, :, g],
                           preferred_element_type=jnp.float32) * scale
            prob = core_softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return (jnp.einsum("bqk,bkd->bqd", prob.astype(y.dtype), v[:, :, g]),
                    prob.astype(jnp.float32))

        o, probs = jax.lax.map(jax.checkpoint(head) if remat else head,
                               jnp.arange(H))             # [H, B, q, Dh], [H, B, q, T]
        p = jax.lax.stop_gradient(head_average(probs))
        logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        logp = jnp.log(jnp.where(p > 0, p, 1.0))
        kl = jnp.sum(jnp.where(mask, p * (logp - jnp.where(mask, logq, 0.0)), 0.0))
        out = jnp.moveaxis(o, 0, 2).reshape(B, block, H * Dh)
        kept = (scores, mask, p) if keep else ()
        return out, kl, jnp.sum(mask, axis=-1), kept

    starts = jnp.arange(0, T, block)
    out, kl, held, kept = jax.lax.map(jax.checkpoint(rows) if remat else rows, starts)
    whole = lambda a: jnp.moveaxis(a, 0, 1).reshape((B, T) + a.shape[3:])
    parts = {"kl": jnp.sum(kl), "held": whole(held)}
    if keep:
        parts.update(zip(("scores", "mask", "p"), (whole(a) for a in kept)))
    return linear(whole(out), w[prefix + "o_proj.weight"]), parts


def router_logits(w, prefix, y2):
    """y2 [N, D] -> [N, E] float32: the router multiplies in float32."""
    return y2.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T


def choose(logits, cfg):
    """The router's logits [N, E] -> (p [N, E] float32, the softmax over all
    E; chosen [N, k] int32; weight [N, k], the chosen probabilities
    renormalised to sum 1: norm_topk_prob)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return p, chosen.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["num_experts"])


def gate_act(x):
    """The nonlinearity on an expert's gate: SiLU."""
    return jax.nn.silu(x)


def mlp(w, name, y):
    """One expert: (silu(y Wg^T) * (y Wu^T)) Wd^T."""
    return linear(gate_act(linear(y, w[name + "gate_proj.weight"]))
                  * linear(y, w[name + "up_proj.weight"]), w[name + "down_proj.weight"])


def experts(w, prefix, y2, cfg, remat=False):
    """y2 [N, D] -> (out [N, D], p [N, E], chosen [N, k], weight [N, k]):
    the held experts' part of the sum."""
    p, chosen, weight = choose(router_logits(w, prefix, y2), cfg)
    first, end = held_range(cfg)
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    out = jnp.zeros(y2.shape, jnp.float32)
    for e in range(first, end):
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        out = out + mine[:, None] * one(w, f"{prefix}experts.{e}.", y2).astype(jnp.float32)
    return out.astype(y2.dtype), p, chosen, weight


def layer(w, i, x, cfg, remat=False, positions=None, selected=None):
    """Block i: x [B, T, D] -> (x, the router's and the indexer's parts)."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps)
    a, sparse = attention(w, name + "self_attn.", y, cfg, remat, positions, selected)
    h = x + a
    y2 = rms_norm(h, w[name + "post_attention_layernorm.weight"], eps)
    out, p, chosen, weight = experts(w, name + MOE, y2.reshape(B * T, D), cfg, remat)
    return h + out.reshape(B, T, D), {"p": p, "chosen": chosen, "weight": weight,
                                      "kl": sparse["kl"], "held": sparse["held"]}


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False, positions=None,
            selected=None):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per layer the router's softmax ``p`` [B*T, E], ``chosen`` [B*T, k],
    ``weight`` [B*T, k], the indexer's ``kl`` (summed over b, t) and ``held``
    [B, T]. ``selected``: per layer a [B, T, T] mask in place of the choice."""
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        routing = []
        for i in range(cfg["num_hidden_layers"]):
            block = (jax.checkpoint(layer, static_argnums=(1, 3, 4))
                     if remat else layer)
            x, parts = block(w, i, x, _Static(cfg), remat, positions,
                             None if selected is None else selected[i])
            routing.append(parts)
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
    """[layers, E] int32: the token-choices each expert of each layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def balancing_loss(routing, cfg):
    """HF's ``load_balancing_loss_func`` (no attention mask) WITHOUT its
    coefficient: all layers' tokens concatenated, over all E experts."""
    E = cfg["num_experts"]
    p = jnp.concatenate([r["p"] for r in routing], axis=0)               # [L*N, E]
    chosen = jnp.concatenate([r["chosen"] for r in routing], axis=0)     # [L*N, k]
    f = jnp.mean(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=0)   # [k, E]
    return E * jnp.sum(f * jnp.mean(p, axis=0)[None, :])


def indexer_loss(routing, tokens):
    """LI: the mean over layers and the ``tokens`` (b, t) of the layers' KL."""
    return sum(r["kl"] for r in routing) / (len(routing) * tokens)


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False, positions=None,
               selected=None):
    """batch_ids [B, T + 1] -> dict: ``loss`` (``ce`` + ``router_aux_loss_coef``
    x ``aux`` + ``kl``), ``ce``, ``aux``, ``kl`` (LI),
    ``logits`` [B, T, V], ``expert_tokens`` [layers, E], ``held_rows`` [layers]
    (the token-choices that fell on the held experts), ``held_keys`` [layers,
    B, T] (the keys each query holds), ``routing``."""
    ids = batch_ids[:, :-1]
    logits, routing = forward(w, cfg, ids, dtype, remat, positions, selected)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    aux = balancing_loss(routing, cfg)
    li = indexer_loss(routing, ids.shape[0] * ids.shape[1])
    tokens = expert_tokens(routing, cfg)
    first, end = held_range(cfg)
    total = ce + float(cfg.get("router_aux_loss_coef") or 0.0) * aux + li
    return {"loss": total, "ce": ce, "aux": aux, "kl": li, "logits": logits,
            "expert_tokens": tokens, "held_rows": tokens[:, first:end].sum(axis=1),
            "held_keys": jnp.stack([r["held"] for r in routing]), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False, positions=None):
    return loss_parts(w, cfg, batch_ids, dtype, remat, positions)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False, positions=None):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat, positions)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    Hi, Di = cfg["sa_config"]["indexer_num_heads"], cfg["sa_config"]["indexer_head_dim"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}
    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        a, m = name + "self_attn.", name + MOE
        shapes.update({name + "input_layernorm.weight": (D,),
                       name + "post_attention_layernorm.weight": (D,),
                       a + "q_proj.weight": (H * Dh, D),
                       a + "k_proj.weight": (KV * Dh, D),
                       a + "v_proj.weight": (KV * Dh, D),
                       a + "o_proj.weight": (D, H * Dh),
                       a + "q_norm.weight": (Dh,), a + "k_norm.weight": (Dh,),
                       a + INDEXER + "wq.weight": (Hi * Di, D),
                       a + INDEXER + "wk.weight": (Di, D),
                       a + INDEXER + "k_norm.weight": (Di,),
                       a + INDEXER + "k_norm.bias": (Di,),
                       a + INDEXER + "weights_proj.weight": (Hi, D),
                       m + "gate.weight": (E, D)})
        for e in range(first, end):
            shapes.update({f"{m}experts.{e}.gate_proj.weight": (F, D),
                           f"{m}experts.{e}.up_proj.weight": (F, D),
                           f"{m}experts.{e}.down_proj.weight": (D, F)})
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices; gains drawn
    from [0.5, 1.5) and the one bias from [-0.5, 0.5) so that leaving one out
    shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith(".bias"):
            out[name] = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
