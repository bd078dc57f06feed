"""A plain reference for IBM granite-4.0-h-micro (``model_type:
granitemoehybrid``, the dense members of the family): forward pass, loss and
gradients in straightforward ``jax.numpy``.

``tests/test_granite4h.py`` holds the program (``models/transformer.py`` with
mixer "ssm" at ONE group beside an "attn" that rotates nothing, a gated MLP in
every block, the family's four multipliers, a tied head) to this file on
seeded random weights, and this file to ``transformers``'
``GraniteMoeHybridForCausalLM``; ``chipbench/reference_granite4h.py`` is its
byte-identical copy below this docstring and decides the cell
``granite4h-train``'s ``correct``.
"""

# Everything below is written from the source's config.json (the catalog's
# row), the layer equations of ISSUE 55 and the family's published modelling
# code (transformers 4.57: models/granitemoehybrid/modeling_granitemoehybrid.py,
# whose tensor names these are). float32 throughout and every matmul at
# jax.default_matmul_precision("highest") (a TPU otherwise multiplies float32
# in bf16 passes); no kernel, no cache, no batching trick, nothing imported
# from shuffle_exchange_tpu. Weights are a flat dict under the source's names,
# each matrix laid out as torch's nn.Linear stores it ([out, in]: y = x @ W.T)
# and the taps as torch's depthwise nn.Conv1d stores them ([C, 1, K]):
#
#   model.embed_tokens.weight                                    [V, D]
#   model.layers.{i}.input_layernorm.weight                      [D]
#   model.layers.{i}.post_attention_layernorm.weight             [D]
#   model.layers.{i}.shared_mlp.input_linear.weight              [2 F, D]  (gate rows, then up rows)
#   model.layers.{i}.shared_mlp.output_linear.weight             [D, F]
#   layers whose type is "mamba" (H heads of P, G groups of N; inner = H P,
#   conv = inner + 2 G N):
#   model.layers.{i}.mamba.in_proj.weight                        [inner + conv + H, D]
#   model.layers.{i}.mamba.conv1d.weight                         [conv, 1, K]
#   model.layers.{i}.mamba.conv1d.bias                           [conv]
#   model.layers.{i}.mamba.{dt_bias,A_log,D}                     [H]
#   model.layers.{i}.mamba.norm.weight                           [inner]
#   model.layers.{i}.mamba.out_proj.weight                       [D, inner]
#   layers whose type is "attention":
#   model.layers.{i}.self_attn.q_proj.weight                     [Ha Dh, D]
#   model.layers.{i}.self_attn.{k,v}_proj.weight                 [KV Dh, D]
#   model.layers.{i}.self_attn.o_proj.weight                     [D, Ha Dh]
#   model.norm.weight                                            [D]
#   (no lm_head tensor: tie_word_embeddings, the head reads the embedding)
#
# ``cfg`` is the source's config.json as a dict (hidden_size, layer_types,
# num_hidden_layers, mamba_n_heads, mamba_d_head, mamba_n_groups,
# mamba_d_state, mamba_d_conv, num_attention_heads, num_key_value_heads,
# shared_intermediate_size, rms_norm_eps, vocab_size and the four multipliers
# embedding_multiplier, residual_multiplier, attention_multiplier,
# logits_scaling) plus, for a cut in depth, ``layers_held`` (the indices into
# ``layer_types`` of the layers that exist here; without it the first
# ``num_hidden_layers``).
#
# The equations (D = hidden_size; e, r, a, s the four multipliers):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a plain gain.
#   embedding  h = e * E[ids].
#   block i    h = h + r * mix_i(N(h; input_layernorm));
#              h = h + r * mlp(N(h; post_attention_layernorm)).
#   mlp        [g | u] = y W_in^T (F each); (silu(g) * u) W_out^T.
#   mamba      [z | xBC | dt] = y W_in^T (inner, conv, H wide; xBC = [x inner |
#              B G N | C G N]); xBC = silu(conv(xBC) + b), conv[t] = sum_j
#              w[:, 0, j] * xBC[t - (K - 1) + j], zero before position 0;
#              dt = softplus(dt + dt_bias), unclamped (time_step_limit (0,
#              inf)); A = -exp(A_log); with x as [H, P] and B, C as [G, N],
#              head h reading group g = h // (H / G): S_t[h] = exp(dt_t[h]
#              A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g] ([P, N], S_{-1}
#              = 0), o_t[h] = S_t[h] C_t[g] + D[h] x_t[h], a scan over the
#              tokens; g = o * silu(z), THEN an RMSNorm over each of the G
#              groups of inner / G channels (granite-4.0-h-micro: G = 1, ALL
#              4096) with one gain [inner]; mix = g W_out^T.
#   attention  q = y Wq [Ha x Dh], k = y Wk, v = y Wv [KV x Dh]; NO rotation
#              and no other position signal (position_embedding_type "nope");
#              query head h reads KV head h // (Ha / KV); causal softmax of
#              a * q k^T in float32 (a = attention_multiplier, NOT 1 /
#              sqrt(Dh)); mix = concat(o) Wo^T.
#   head       logits = N(h; model.norm) E^T / s; mean token cross-entropy.
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - a sliced vocabulary is a smaller vocabulary: ``vocab_size`` rows exist,
#     ids, logits and loss are over them;
#   - the scan runs in blocks of ``SCAN_BLOCK`` tokens, each block a
#     checkpointed inner scan, so that its backward fits at 16,384 tokens: the
#     same values, computed again in the backward;
#   - the causal softmax is computed a head at a time over the whole [T, T];
#   - ``remat`` wraps each layer and each head in jax.checkpoint: the same
#     values, computed again in the backward;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the taps' sum, the scan's state and decay, the
#     multipliers' products and the cross-entropy stay float32.

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


def scaled(x, m):
    """``m * x`` for one of the family's multipliers: the product in float32,
    rounded once to x's dtype (the band's lower precision multiplies in x's)."""
    return (float(m) * x.astype(jnp.float32)).astype(x.dtype)


def added(h, out, r):
    """``h + r * out``: the residual step, the sum formed in float32."""
    return (h.astype(jnp.float32) + float(r) * out.astype(jnp.float32)).astype(h.dtype)


def layer_types(cfg):
    """The types of the layers that exist here, in order."""
    types = list(cfg["layer_types"])
    held = cfg.get("layers_held") or range(int(cfg["num_hidden_layers"]))
    return [types[int(i)] for i in held]


def eps_of(cfg):
    return float(cfg.get("rms_norm_eps", 1e-5))


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


def stat_dtype():
    """The precision the gated norm's mean of squares is formed in (float32;
    the band's wrong model forms it lower)."""
    return jnp.float32


def norm_groups(cfg):
    """The runs of channels the gated norm's statistic is taken over: the
    scan's groups (granite-4.0-h-micro: ONE, all 4096 channels)."""
    return int(cfg.get("mamba_n_groups", 1))


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
    low = g.astype(stat_dtype())
    mean = jnp.mean(low * low, axis=-1, keepdims=True, dtype=stat_dtype()).astype(f32)
    g = g * jax.lax.rsqrt(mean + eps)
    return (g.reshape(B, T, inner) * gain.astype(f32)).astype(o.dtype)


def mamba_split(zxbcdt, cfg):
    """The input projection's output -> (z, xBC, dt)."""
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner, conv = H * P, H * P + 2 * G * N
    return zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv], zxbcdt[..., inner + conv:]


def mamba_core(xbc, dt, w, prefix, cfg):
    """What lies between the convolution and the gated norm: the convolved
    xBC [B, T, conv] and the raw dt [B, T, H] -> o [B, T, inner] float32."""
    B, T = xbc.shape[:2]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
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
    o = gated_norm(o, z, w[prefix + "norm.weight"], norm_groups(cfg), eps_of(cfg))
    return linear(o, w[prefix + "out_proj.weight"])


# -- attention ----------------------------------------------------------------


def head_dim(cfg):
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def kv_head(h, H, KV):
    """The KV head that query head h of H reads: consecutive groups of H / KV."""
    return h // (H // KV)


def attention(w, prefix, y, cfg, remat=False):
    B, T, D = y.shape
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = linear(y, w[prefix + "q_proj.weight"]).reshape(B, T, H, Dh)
    k = linear(y, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh)
    v = linear(y, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = float(cfg["attention_multiplier"])      # in place of 1 / sqrt(Dh)

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


# -- the gated MLP --------------------------------------------------------------


def mlp(w, prefix, y):
    """(silu(g) * u) W_out^T with [g | u] = y W_in^T."""
    gu = linear(y, w[prefix + "input_linear.weight"])
    F = gu.shape[-1] // 2
    return linear(jax.nn.silu(gu[..., :F]) * gu[..., F:], w[prefix + "output_linear.weight"])


# -- the model ------------------------------------------------------------------


def layer(w, i, x, cfg, remat=False):
    """Block i, TWO residual steps: x [B, T, D] -> x."""
    name = f"model.layers.{i}."
    kind = layer_types(cfg)[i]
    r = cfg.get("residual_multiplier", 1.0)
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps_of(cfg))
    if kind == "mamba":
        mix = mamba(w, name + "mamba.", y, cfg)
    elif kind == "attention":
        mix = attention(w, name + "self_attn.", y, cfg, remat)
    else:
        raise ValueError(f"layer_types[{i}] = {kind!r}: mamba and attention are written down")
    x = added(x, mix, r)
    y = rms_norm(x, w[name + "post_attention_layernorm.weight"], eps_of(cfg))
    return added(x, mlp(w, name + "shared_mlp.", y), r)


def hidden(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> the final norm's output [B, T, D]."""
    x = scaled(w["model.embed_tokens.weight"].astype(dtype)[input_ids],
               cfg.get("embedding_multiplier", 1.0))
    for i in range(len(layer_types(cfg))):
        block = jax.checkpoint(layer, static_argnums=(1, 3, 4)) if remat else layer
        x = block(w, i, x, _Static(cfg), remat)
    return rms_norm(x, w["model.norm.weight"], eps_of(cfg))


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> logits [B, T, V] float32: the tied head, divided
    by ``logits_scaling``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, cfg, input_ids, dtype, remat)
        logits = linear(x, w["model.embed_tokens.weight"]).astype(jnp.float32)
        return logits / float(cfg.get("logits_scaling", 1.0))


class _Static(dict):
    """``cfg`` as a hashable static argument of jax.checkpoint."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> the mean token cross-entropy."""
    logits = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch_ids[:, 1:, None], axis=-1))


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names (the embedding's: the
    sum of the lookup's, scaled by e, and the head's, scaled by 1 / s)."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["shared_intermediate_size"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], int(cfg.get("mamba_d_conv", 4))
    inner, conv = H * P, H * P + 2 * G * N
    Ha, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "model.norm.weight": (D,)}
    for i, kind in enumerate(layer_types(cfg)):
        name = f"model.layers.{i}."
        shapes.update({name + "input_layernorm.weight": (D,),
                       name + "post_attention_layernorm.weight": (D,),
                       name + "shared_mlp.input_linear.weight": (2 * F, D),
                       name + "shared_mlp.output_linear.weight": (D, F)})
        if kind == "mamba":
            m = name + "mamba."
            shapes.update({m + "in_proj.weight": (inner + conv + H, D),
                           m + "conv1d.weight": (conv, 1, K), m + "conv1d.bias": (conv,),
                           m + "dt_bias": (H,), m + "A_log": (H,), m + "D": (H,),
                           m + "norm.weight": (inner,), m + "out_proj.weight": (D, inner)})
        else:
            a = name + "self_attn."
            shapes.update({a + "q_proj.weight": (Ha * Dh, D), a + "k_proj.weight": (KV * Dh, D),
                           a + "v_proj.weight": (KV * Dh, D), a + "o_proj.weight": (D, Ha * Dh)})
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal, 0.02
    for the embedding, 1/sqrt(fan_in) for matrices and taps; gains, the skip D
    and the convolution's bias drawn away from their neutral values (gains and
    D from [0.5, 1.5), the bias from a normal of 0.1), so that leaving one out
    shows; A_log = log U[1, 16] and dt_bias the inverse softplus of a
    log-uniform step in [0.001, 0.1], the family's own draws."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("A_log"):
            out[name] = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                              jnp.log(1e-3), jnp.log(1e-1)))
            out[name] = step + jnp.log(-jnp.expm1(-step))
        elif name.endswith("conv1d.bias"):
            out[name] = 0.1 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name == "model.embed_tokens.weight":
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 3:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[-1] ** 0.5)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
