"""A plain reference for NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type:
nemotron_h``): forward pass, loss and gradients in straightforward
``jax.numpy``.

``tests/test_nemotron3.py`` holds the program (``models/transformer.py`` with
mixer "ssm" beside an "attn" that rotates nothing, layers whose ffn is "none",
ungated relu2 experts under a sigmoid router with a selection bias, a held
share of the experts, an untied head) to this file on seeded random weights;
``chipbench/reference_nemotron3.py`` is its byte-identical copy below this
docstring and decides the cell ``nemotron3-train``'s ``correct``.
"""

# Everything below is written from the source's config.json (the catalog's
# row) and the layer equations of ISSUE 46; the leaf names are those of the
# family's published modelling code as the builder knows them, with no
# modelling code here to check them against (chipbench/NEMOTRON3.md lists what
# that leaves to be assumed). float32 throughout and every matmul at
# jax.default_matmul_precision("highest") (a TPU otherwise multiplies float32
# in bf16 passes); no kernel, no cache, no batching trick, nothing imported
# from shuffle_exchange_tpu. Weights are a flat dict under the source's names,
# each matrix laid out as torch's nn.Linear stores it ([out, in]: y = x @ W.T)
# and the taps as torch's depthwise nn.Conv1d stores them ([C, 1, K]):
#
#   backbone.embeddings.weight                                   [V, D]
#   backbone.layers.{i}.norm.weight                              [D]
#   layers whose letter is "M" (H heads of P, G groups of N; inner = H P,
#   conv = inner + 2 G N):
#   backbone.layers.{i}.mixer.in_proj.weight                     [inner + conv + H, D]
#   backbone.layers.{i}.mixer.conv1d.weight                      [conv, 1, K]
#   backbone.layers.{i}.mixer.conv1d.bias                        [conv]
#   backbone.layers.{i}.mixer.{dt_bias,A_log,D}                  [H]
#   backbone.layers.{i}.mixer.norm.weight                        [inner]
#   backbone.layers.{i}.mixer.out_proj.weight                    [D, inner]
#   layers whose letter is "*":
#   backbone.layers.{i}.mixer.q_proj.weight                      [Ha Dh, D]
#   backbone.layers.{i}.mixer.{k,v}_proj.weight                  [KV Dh, D]
#   backbone.layers.{i}.mixer.o_proj.weight                      [D, Ha Dh]
#   layers whose letter is "E":
#   backbone.layers.{i}.mixer.gate.weight                        [E, D]
#   backbone.layers.{i}.mixer.gate.e_score_correction_bias       [E]
#   backbone.layers.{i}.mixer.experts.{e}.up_proj.weight         [F, D]
#   backbone.layers.{i}.mixer.experts.{e}.down_proj.weight       [D, F]
#   backbone.layers.{i}.mixer.shared_experts.up_proj.weight      [Fs, D]
#   backbone.layers.{i}.mixer.shared_experts.down_proj.weight    [D, Fs]
#   backbone.norm_f.weight                                       [D]
#   lm_head.weight                                               [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size,
# hybrid_override_pattern, num_hidden_layers, mamba_num_heads, mamba_head_dim,
# n_groups, ssm_state_size, conv_kernel, num_attention_heads,
# num_key_value_heads, head_dim, layer_norm_epsilon, n_routed_experts,
# num_experts_per_tok, norm_topk_prob, routed_scaling_factor,
# moe_intermediate_size, moe_shared_expert_intermediate_size, vocab_size) plus,
# for one expert-parallel rank's share, ``num_experts_held`` and
# ``expert_first`` (the experts [expert_first, expert_first +
# num_experts_held) exist here, the router still scores all
# ``n_routed_experts``), and the family's training keys ``aux_loss_alpha`` /
# ``seq_aux`` / ``bias_update_speed``. The first ``num_hidden_layers`` letters
# of ``hybrid_override_pattern`` are the layers that exist here.
#
# The equations (D = hidden_size; layer i is ONE residual step):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a plain gain.
#   layer i    h = h + f_i(N(h; norm_i)), f_i by the layer's letter. Final N
#              (norm_f), an untied head, mean token cross-entropy.
#   M          [z | xBC | dt] = y W_in^T (inner, conv, H wide; xBC = [x inner |
#              B G N | C G N]); xBC = silu(conv(xBC) + b), conv[t] = sum_j
#              w[:, 0, j] * xBC[t - (K - 1) + j], zero before position 0;
#              dt = softplus(dt + dt_bias), unclamped; A = -exp(A_log); with x
#              as [H, P] and B, C as [G, N], head h reading group g = h // (H /
#              G): S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h]
#              (outer) B_t[g] ([P, N], S_{-1} = 0), o_t[h] = S_t[h] C_t[g] +
#              D[h] x_t[h], a scan over the tokens; o = o * silu(z), THEN an
#              RMSNorm over each of the G groups of inner / G channels with one
#              gain [inner]; f = o W_out^T.
#   *          q = y Wq [Ha x Dh], k = y Wk, v = y Wv [KV x Dh]; NO rotation
#              and no other position signal; query head h reads KV head h //
#              (Ha / KV); causal softmax of q k^T / sqrt(Dh) in float32; f =
#              concat(o) Wo^T.
#   E          s = sigmoid(float32(y) Wr^T) over all E; the k largest of s +
#              bias chosen (the bias selects, is not weighed and gets no
#              gradient); w = s[chosen] / (sum of the chosen s + 1e-20)
#              (norm_topk_prob) times routed_scaling_factor; routed = sum over
#              the token's choices THAT ARE HELD HERE of w_k W2_e relu(W1_e
#              y)^2, as a loop over the held experts with masks; shared = W2_s
#              relu(W1_s y)^2 at its own width, no gate; f = routed + shared.
#   balance    the source's config has none. The router's family (DeepSeek-V3,
#              whose keys the config repeats) trains with the complementary
#              sequence-wise balance loss and the aux-free bias update, both
#              here, both off unless ``cfg`` has their keys (``balance_loss``,
#              ``bias_update``), as ``reference_kanana2`` has them.
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: with ``num_experts_held`` the routed sum runs over the
#     held experts only and what the absent ones would add is left out;
#   - the scan runs in blocks of ``SCAN_BLOCK`` tokens, each block a
#     checkpointed inner scan, so that its backward fits at 8192 tokens: the
#     same values, computed again in the backward;
#   - the causal softmax is computed a head at a time over the whole [T, T];
#   - ``remat`` wraps each layer, each head and each expert in
#     jax.checkpoint: the same values, computed again in the backward;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the router, the taps' sum, the scan's state and
#     decay and the cross-entropy stay float32.

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def letters(cfg):
    """The letters of the layers that exist here."""
    return str(cfg["hybrid_override_pattern"])[:int(cfg["num_hidden_layers"])]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# -- the state-space layer ---------------------------------------------------


def taps_sum(u, taps, bias):
    """The causal depthwise convolution: u [B, T, C], taps [C, 1, K], bias [C]
    -> c[t] = bias + sum_j taps[:, 0, j] * u[t - (K - 1) + j] with u zero before
    position 0, as K shifted products summed in float32."""
    B, T, C = u.shape
    K = taps.shape[-1]
    u32 = u.astype(jnp.float32)
    out = jnp.zeros((B, T, C), jnp.float32) + bias.astype(jnp.float32)
    for j in range(K):
        back = K - 1 - j                     # tap j reads ``back`` rows before t
        shifted = jnp.pad(u32, ((0, 0), (back, 0), (0, 0)))[:, :T]
        out = out + taps[:, 0, j].astype(jnp.float32)[None, None, :] * shifted
    return out.astype(u.dtype)


def group_of(h, H, G):
    """The group of B and C that head h of H reads: consecutive runs of H / G."""
    return h // (H // G)


def step_and_decay(dt, dt_bias, A_log):
    """(dt [.., H] float32 after its softplus, unclamped; A [H] = -exp(A_log))."""
    f32 = jnp.float32
    return (jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
            -jnp.exp(A_log.astype(f32)))


def decay_of(dt, A):
    """exp(dt A): what a token leaves of the state before it."""
    return jnp.exp(dt * A)


def state_dtype():
    """The precision the scan's state is kept in (float32; the band's wrong
    model keeps it lower)."""
    return jnp.float32


def scan(x, dt, A, B, C, D):
    """The recurrence, token by token: x [Bt, T, H, P], dt [Bt, T, H] float32,
    A [H], B and C [Bt, T, G, N], D [H] -> o [Bt, T, H, P] float32. In blocks
    of ``SCAN_BLOCK`` tokens, each a checkpointed inner scan."""
    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    heads_group = jnp.asarray([group_of(h, H, G) for h in range(H)])
    keep = state_dtype()

    def token(S, row):
        xt, dtt, Bt_, Ct = row                # [Bt,H,P] [Bt,H] [Bt,G,N] [Bt,G,N]
        Bh, Ch = Bt_[:, heads_group], Ct[:, heads_group]          # [Bt, H, N]
        S = (decay_of(dtt, A)[..., None, None] * S.astype(f32)
             + (dtt[..., None] * xt)[..., None] * Bh[:, :, None, :])
        o = jnp.sum(S * Ch[:, :, None, :], axis=-1) + D[None, :, None] * xt
        return S.astype(keep), o

    def block(S, rows):
        return jax.lax.scan(token, S, rows)

    pad = -T % SCAN_BLOCK
    n = (T + pad) // SCAN_BLOCK

    def cut(a):
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)                                   # [T, Bt, ...]
        return a.reshape((n, SCAN_BLOCK) + a.shape[1:])

    S0 = jnp.zeros((Bt, H, P, N), keep)
    _, o = jax.lax.scan(jax.checkpoint(block), S0, (cut(x), cut(dt), cut(B), cut(C)))
    o = o.reshape((n * SCAN_BLOCK,) + o.shape[2:])[:T]
    return jnp.moveaxis(o, 0, 1)


def gated_norm(o, z, gain, groups, eps):
    """The gate FIRST, then an RMSNorm over each of ``groups`` runs of
    channels under one gain: o, z [B, T, inner] -> [B, T, inner]."""
    f32 = jnp.float32
    B, T, inner = o.shape
    g = (o.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(B, T, groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(B, T, inner) * gain.astype(f32)).astype(o.dtype)


def mamba_split(zxbcdt, cfg):
    """The input projection's output -> (z, xBC, dt)."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    inner, conv = H * P, H * P + 2 * G * N
    return zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv], zxbcdt[..., inner + conv:]


def mamba_core(xbc, dt, w, prefix, cfg):
    """What lies between the convolution and the gated norm: the convolved
    xBC [B, T, conv] and the raw dt [B, T, H] -> o [B, T, inner] float32."""
    B, T = xbc.shape[:2]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    inner = H * P
    x = xbc[..., :inner].reshape(B, T, H, P)
    Bm = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
    Cm = xbc[..., inner + G * N:].reshape(B, T, G, N)
    step, A = step_and_decay(dt, w[prefix + "dt_bias"], w[prefix + "A_log"])
    return scan(x, step, A, Bm, Cm, w[prefix + "D"].astype(jnp.float32)).reshape(B, T, inner)


def mamba(w, prefix, y, cfg):
    """The Mamba-2 layer on the normed input y [B, T, D]."""
    z, xbc, dt = mamba_split(linear(y, w[prefix + "in_proj.weight"]), cfg)
    xbc = jax.nn.silu(taps_sum(xbc, w[prefix + "conv1d.weight"], w[prefix + "conv1d.bias"]))
    o = mamba_core(xbc, dt, w, prefix, cfg).astype(y.dtype)
    o = gated_norm(o, z, w[prefix + "norm.weight"], cfg["n_groups"],
                   cfg.get("layer_norm_epsilon", 1e-5))
    return linear(o, w[prefix + "out_proj.weight"])


# -- attention ----------------------------------------------------------------


def head_dim(cfg):
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def kv_head(h, H, KV):
    """The KV head that query head h of H reads: consecutive groups of H / KV."""
    return h // (H // KV)


def positioned(q, k, cfg):
    """q and k as the scores read them: nothing rotates, nothing else marks a
    position (the band's wrong model rotates here)."""
    del cfg
    return q, k


def attention(w, prefix, y, cfg, remat=False):
    B, T, D = y.shape
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = linear(y, w[prefix + "q_proj.weight"]).reshape(B, T, H, Dh)
    k = linear(y, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh)
    v = linear(y, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    q, k = positioned(q, k, cfg)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = Dh ** -0.5

    def head(h):
        """whole [T, T] scores of one head."""
        g = kv_head(h, H, KV)
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, g],
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs.astype(y.dtype), v[:, :, g])

    o = jax.lax.map(jax.checkpoint(head) if remat else head, jnp.arange(H))  # [H, B, T, Dh]
    return linear(jnp.moveaxis(o, 0, 2).reshape(B, T, H * Dh), w[prefix + "o_proj.weight"])


# -- the experts ----------------------------------------------------------------


def router_logits(w, prefix, y):
    """y [N, D] -> [N, E] float32: the router multiplies in float32."""
    return y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T


def choose(logits, bias, cfg):
    """The router's logits [N, E] and its selection bias [E] -> (s [N, E]
    float32 scores, chosen [N, k] int32, weight [N, k]): one group."""
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
    """An ungated expert: W2 relu(W1 y)^2."""
    return linear(relu2(linear(y, w[name + "up_proj.weight"])), w[name + "down_proj.weight"])


def experts(w, prefix, y, cfg, remat=False):
    """y [N, D] -> (out [N, D], s [N, E], chosen [N, k], weight [N, k]): the
    held experts' part of the routed sum plus the shared expert, added as it
    is."""
    s, chosen, weight = route(w, prefix, y, cfg)
    first, end = held_range(cfg)
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    out = jnp.zeros(y.shape, jnp.float32)
    for e in range(first, end):
        # this expert's weight for every token: its normalised score where it
        # is one of the token's k, else 0
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        out = out + mine[:, None] * one(w, f"{prefix}experts.{e}.", y).astype(jnp.float32)
    if int(cfg.get("n_shared_experts") or 0):
        out = out + one(w, prefix + "shared_experts.", y).astype(jnp.float32)
    return out.astype(y.dtype), s, chosen, weight


# -- the model ------------------------------------------------------------------


def layer(w, i, x, cfg, remat=False):
    """Layer i, ONE residual step: x [B, T, D] -> (x, router scores, chosen
    experts, their weights); the last three are None unless the letter is E."""
    B, T, D = x.shape
    name = f"backbone.layers.{i}."
    letter = letters(cfg)[i]
    y = rms_norm(x, w[name + "norm.weight"], cfg.get("layer_norm_epsilon", 1e-5))
    if letter == "M":
        return x + mamba(w, name + "mixer.", y, cfg), None, None, None
    if letter == "*":
        return x + attention(w, name + "mixer.", y, cfg, remat), None, None, None
    if letter != "E":
        raise ValueError(f"hybrid_override_pattern[{i}] = {letter!r}: M, * and E are written down")
    out, s, chosen, weight = experts(w, name + "mixer.", y.reshape(B * T, D), cfg, remat)
    return x + out.reshape(B, T, D), s, chosen, weight


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per ROUTED layer the router scores ``s`` [B*T, E], the chosen
    experts ``chosen`` [B*T, k] and their weights ``weight`` [B*T, k]."""
    with jax.default_matmul_precision("highest"):
        x = w["backbone.embeddings.weight"].astype(dtype)[input_ids]
        routing = []
        for i in range(len(letters(cfg))):
            block = (jax.checkpoint(layer, static_argnums=(1, 3, 4))
                     if remat else layer)
            x, s, chosen, weight = block(w, i, x, _Static(cfg), remat)
            if chosen is not None:
                routing.append({"s": s, "chosen": chosen, "weight": weight})
        x = rms_norm(x, w["backbone.norm_f.weight"], cfg.get("layer_norm_epsilon", 1e-5))
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
    routed layers of the mean over the ``sequences`` of sum_e f_e P_e, f_e = E
    / (k T) x the sequence's token-choices of expert e, P_e the sequence's
    mean of s_e / sum_j s_j."""
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
    """batch_ids [B, T + 1] -> dict: ``loss`` (the mean token cross-entropy,
    plus ``aux_loss_alpha`` x ``balance_loss`` where ``cfg`` has it),
    ``logits`` [B, T, V], ``expert_tokens`` and ``expert_weight`` [routed
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
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], int(cfg.get("conv_kernel", 4))
    inner, conv = H * P, H * P + 2 * G * N
    Ha, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    E, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    Fs = int(cfg.get("moe_shared_expert_intermediate_size") or 0)
    first, end = held_range(cfg)
    shapes = {"backbone.embeddings.weight": (V, D), "backbone.norm_f.weight": (D,),
              "lm_head.weight": (V, D)}
    for i, letter in enumerate(letters(cfg)):
        name = f"backbone.layers.{i}."
        m = name + "mixer."
        shapes[name + "norm.weight"] = (D,)
        if letter == "M":
            shapes.update({m + "in_proj.weight": (inner + conv + H, D),
                           m + "conv1d.weight": (conv, 1, K), m + "conv1d.bias": (conv,),
                           m + "dt_bias": (H,), m + "A_log": (H,), m + "D": (H,),
                           m + "norm.weight": (inner,), m + "out_proj.weight": (D, inner)})
        elif letter == "*":
            shapes.update({m + "q_proj.weight": (Ha * Dh, D), m + "k_proj.weight": (KV * Dh, D),
                           m + "v_proj.weight": (KV * Dh, D), m + "o_proj.weight": (D, Ha * Dh)})
        else:
            shapes[m + "gate.weight"] = (E, D)
            shapes[m + "gate.e_score_correction_bias"] = (E,)
            for e in range(first, end):
                shapes[f"{m}experts.{e}.up_proj.weight"] = (F, D)
                shapes[f"{m}experts.{e}.down_proj.weight"] = (D, F)
            if int(cfg.get("n_shared_experts") or 0):
                shapes[m + "shared_experts.up_proj.weight"] = (Fs, D)
                shapes[m + "shared_experts.down_proj.weight"] = (D, Fs)
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal, 0.02
    for the embedding and the head, 1/sqrt(fan_in) for matrices and taps;
    gains, the skip D and the convolution's bias drawn away from their
    neutral values (gains and D from [0.5, 1.5), the bias from a normal of
    0.1) and the selection bias from a normal of 0.05, so that leaving one out
    shows; A_log = log U[1, 16] and dt_bias the inverse softplus of a
    log-uniform step in [0.001, 0.1], the family's own draws."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("e_score_correction_bias"):
            out[name] = 0.05 * jax.random.normal(key, shape, jnp.float32)
        elif name.endswith("A_log"):
            out[name] = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                              jnp.log(1e-3), jnp.log(1e-1)))
            out[name] = step + jnp.log(-jnp.expm1(-step))
        elif name.endswith("conv1d.bias"):
            out[name] = 0.1 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("backbone.embeddings.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 3:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[-1] ** 0.5)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
