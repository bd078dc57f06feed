"""The benchmark's own copy of the plain reference for Olmo Hybrid
(``allenai/Olmo-Hybrid-7B``): what ``drivers/train_steps_gdn.py`` holds the
first loss and the first gradient to. Byte-identical, below this docstring, to
``shuffle_exchange_tpu/models/reference_olmohybrid.py``
(``tests/test_olmohybrid.py`` compares them); kept here so that no later change
to the program changes what ``correct`` means.
"""

# float32 throughout and every matmul at
# jax.default_matmul_precision("highest") (a TPU otherwise multiplies float32
# in bf16 passes); the delta rule is the RECURRENCE, one token at a time: no
# chunk, no kernel, no cache, no batching trick, nothing imported from
# shuffle_exchange_tpu. Weights are a flat dict under the source's names (the
# Olmo 2 / 3 block's and FLA's GatedDeltaNet layer's: ASSUMED, the catalog row
# gives the configuration and no state dict), each matrix laid out as torch's
# nn.Linear stores it ([out, in]: y = x @ W.T):
#
#   model.embed_tokens.weight                                [V, D]
#   model.layers.{i}.post_attention_layernorm.weight         [D]
#   model.layers.{i}.post_feedforward_layernorm.weight       [D]
#   model.layers.{i}.mlp.{gate,up}_proj.weight               [F, D]
#   model.layers.{i}.mlp.down_proj.weight                    [D, F]
#   layer_types[i] == "full_attention":
#   model.layers.{i}.self_attn.{q,k,v}_proj.weight           [H Dh, D]  (KV = H)
#   model.layers.{i}.self_attn.o_proj.weight                 [D, H Dh]
#   model.layers.{i}.self_attn.{q,k}_norm.weight             [H Dh]
#   layer_types[i] == "linear_attention":
#   model.layers.{i}.linear_attn.{q,k}_proj.weight           [Hk dk, D]
#   model.layers.{i}.linear_attn.{v,g}_proj.weight           [Hv dv, D]
#   model.layers.{i}.linear_attn.{a,b}_proj.weight           [Hv, D]
#   model.layers.{i}.linear_attn.{q,k}_conv1d.weight         [Hk dk, 1, K]
#   model.layers.{i}.linear_attn.v_conv1d.weight             [Hv dv, 1, K]
#   model.layers.{i}.linear_attn.{A_log,dt_bias}             [Hv]
#   model.layers.{i}.linear_attn.o_norm.weight               [dv]
#   model.layers.{i}.linear_attn.o_proj.weight               [D, Hv dv]
#   model.norm.weight                                        [D]
#   lm_head.weight                                           [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size,
# intermediate_size, num_attention_heads, num_key_value_heads,
# num_hidden_layers, layer_types, rms_norm_eps, linear_num_key_heads,
# linear_num_value_heads, linear_key_head_dim, linear_value_head_dim,
# linear_conv_kernel_dim, linear_allow_neg_eigval, rope_parameters).
#
# The equations (D = hidden_size; N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a
# plain gain, the statistic in float32):
#   block i    h = h + N(mixer_i(h); w1);  h = h + N(FFN(h); w2): every
#              sublayer reads h AS IT IS and its OUTPUT is normed (the Olmo
#              2 / 3 order). FFN(h) = W_down (silu(W_gate h) * W_up h).
#              After the last block N(h; w_f), the untied head, mean token
#              cross-entropy.
#   linear_attention (Gated DeltaNet, H = Hk = Hv heads here):
#              q~ = silu(conv(h Wq)), k~ = silu(conv(h Wk)), v = silu(conv(h
#              Wv)): causal depthwise convolutions of K taps, no bias;
#              q_t = l2norm(q~_t) / sqrt(dk), k_t = l2norm(k~_t) per head;
#              beta_t = 2 sigmoid(h W_b) where linear_allow_neg_eigval (the
#              transition I - beta k k^T then has eigenvalues in (-1, 1)),
#              else sigmoid; g_t = -exp(A_log) softplus(h W_a + dt_bias);
#              S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T
#              k_t)^T, S_0 = 0 in R^{dk x dv}, float32; o_t = S_t^T q_t;
#              out = (w_o * o_t / rms(o_t) * silu(h W_g)) W_o: the norm per
#              head over dv, THEN the gate.
#   full_attention:
#              q = N(h Wq; w_q), k = N(h Wk; w_k) over ALL H Dh channels of
#              the projection (not per head), v = h Wv; NO rotation
#              (rope_parameters.rope_theta is null: the linear layers carry
#              the order); causal softmax(q k^T / sqrt(Dh)) v per head; Wo.
#              No bias, no gate.
#   dtype      ``dtype`` below float32 is what the band's measurement of a
#              lower precision uses: weights and activations are rounded to
#              it; the norms' statistics, the softmax, g, beta, the state S
#              and the cross-entropy stay float32.

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    """Plain gain: x / rms(x) * gain over the last axis, float32 inside."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def l2norm(x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)
            ).astype(x.dtype)


def linear(x, w):
    return x @ w.astype(x.dtype).T


def qk_norm(q, k, wq, wk, cfg):
    """q [B, T, H Dh], k [B, T, KV Dh]: each normed over its WHOLE width."""
    eps = cfg["rms_norm_eps"]
    return rms_norm(q, wq, eps), rms_norm(k, wk, eps)


def position(q, k, cfg):
    """q, k [B, T, H, Dh] as the scores take them: rotated by nothing."""
    return q, k


def attention(w, prefix, x, cfg, remat=False):
    B, T, D = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim") or D // H
    q, k = qk_norm(linear(x, w[prefix + "q_proj.weight"]),
                   linear(x, w[prefix + "k_proj.weight"]),
                   w[prefix + "q_norm.weight"], w[prefix + "k_norm.weight"], cfg)
    q, k = position(q.reshape(B, T, H, Dh), k.reshape(B, T, KV, Dh), cfg)
    v = linear(x, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        """whole [T, T] scores of one query head: q [B, T, Dh], k, v."""
        qh, kh, vh = qkv
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh).astype(jnp.float32) / (Dh ** 0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1).astype(x.dtype), vh)

    per_kv = H // KV
    one = lambda h: head((q[:, :, h], k[:, :, h // per_kv], v[:, :, h // per_kv]))
    o = jax.lax.map(jax.checkpoint(one) if remat else one, jnp.arange(H))
    return linear(jnp.moveaxis(o, 0, 2).reshape(B, T, H * Dh), w[prefix + "o_proj.weight"])


def causal_conv(x, weight):
    """x [B, T, C]; weight [C, 1, K] as torch's depthwise Conv1d stores it,
    padding K - 1 on the left, no bias: y[t] = sum_j weight[c, 0, j] *
    x[t - (K - 1) + j]."""
    K, T = weight.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * weight[:, 0, j].astype(x.dtype) for j in range(K))


def write_strength(b, cfg):
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return 2.0 * beta if cfg.get("linear_allow_neg_eigval", False) else beta


def log_decay(a, A_log, dt_bias):
    return -jnp.exp(A_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))


def query_scale(dk, dv):
    return dk ** -0.5


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


def gated_out_norm(o, z, gain, eps):
    """o [B, T, H, dv] float32, z the gate's input: the norm per head, THEN
    the gate."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return gain.astype(jnp.float32) * o * jax.nn.silu(z.astype(jnp.float32))


def delta_net(w, prefix, x, cfg, remat=False):
    B, T, D = x.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = Hv // Hk
    mixed = lambda name: jax.nn.silu(causal_conv(
        linear(x, w[prefix + name + "_proj.weight"]), w[prefix + name + "_conv1d.weight"]))
    q = jnp.repeat(mixed("q").reshape(B, T, Hk, dk), r, axis=2)
    k = jnp.repeat(mixed("k").reshape(B, T, Hk, dk), r, axis=2)
    v = mixed("v").reshape(B, T, Hv, dv)
    z = linear(x, w[prefix + "g_proj.weight"]).reshape(B, T, Hv, dv)
    beta = write_strength(linear(x, w[prefix + "b_proj.weight"]), cfg)
    g = log_decay(linear(x, w[prefix + "a_proj.weight"]),
                  w[prefix + "A_log"], w[prefix + "dt_bias"])
    o = delta_rule(l2norm(q) * query_scale(dk, dv), l2norm(k), v, g, beta,
                   remat=remat)                                   # float32
    o = gated_out_norm(o, z, w[prefix + "o_norm.weight"], cfg["rms_norm_eps"])
    return linear(o.astype(x.dtype).reshape(B, T, Hv * dv), w[prefix + "o_proj.weight"])


def mlp(w, prefix, y):
    inner = jax.nn.silu(linear(y, w[prefix + "gate_proj.weight"])) * linear(
        y, w[prefix + "up_proj.weight"])
    return linear(inner, w[prefix + "down_proj.weight"])


def is_full_attention(i, cfg):
    return cfg["layer_types"][i] == "full_attention"


def layer(w, i, x, cfg, remat=False):
    """Block i: x [B, T, D] -> x. Each sublayer's OUTPUT is normed."""
    eps = cfg["rms_norm_eps"]
    name = f"model.layers.{i}."
    if is_full_attention(i, cfg):
        out = attention(w, name + "self_attn.", x, cfg, remat)
    else:
        out = delta_net(w, name + "linear_attn.", x, cfg, remat)
    h = x + rms_norm(out, w[name + "post_attention_layernorm.weight"], eps)
    return h + rms_norm(mlp(w, name + "mlp.", h),
                        w[name + "post_feedforward_layernorm.weight"], eps)


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> logits [B, T, V] float32."""
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        for i in range(cfg["num_hidden_layers"]):
            # (a closure a layer: a checkpoint of its own, traced where it stands)
            block = lambda w, x, i=i: layer(w, i, x, cfg, remat)
            x = (jax.checkpoint(block) if remat else block)(w, x)
        x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
        return linear(x, w["lm_head.weight"]).astype(jnp.float32)


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> {"loss": mean token cross-entropy, "logits"}."""
    logits = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return {"loss": ce, "logits": logits}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim") or D // H
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}
    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        shapes[name + "post_attention_layernorm.weight"] = (D,)
        shapes[name + "post_feedforward_layernorm.weight"] = (D,)
        shapes.update({name + "mlp.gate_proj.weight": (F, D),
                       name + "mlp.up_proj.weight": (F, D),
                       name + "mlp.down_proj.weight": (D, F)})
        if is_full_attention(i, cfg):
            a = name + "self_attn."
            shapes.update({a + "q_proj.weight": (H * Dh, D), a + "k_proj.weight": (KV * Dh, D),
                           a + "v_proj.weight": (KV * Dh, D), a + "o_proj.weight": (D, H * Dh),
                           a + "q_norm.weight": (H * Dh,), a + "k_norm.weight": (KV * Dh,)})
        else:
            a = name + "linear_attn."
            shapes.update({a + "q_proj.weight": (Hk * dk, D), a + "k_proj.weight": (Hk * dk, D),
                           a + "v_proj.weight": (Hv * dv, D), a + "g_proj.weight": (Hv * dv, D),
                           a + "a_proj.weight": (Hv, D), a + "b_proj.weight": (Hv, D),
                           a + "q_conv1d.weight": (Hk * dk, 1, K),
                           a + "k_conv1d.weight": (Hk * dk, 1, K),
                           a + "v_conv1d.weight": (Hv * dv, 1, K),
                           a + "A_log": (Hv,), a + "dt_bias": (Hv,),
                           a + "o_norm.weight": (dv,), a + "o_proj.weight": (D, Hv * dv)})
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal, 0.02
    for embedding and head, 1/sqrt(fan_in) for matrices; every gain drawn from
    [0.5, 1.5), A_log = log U(0, 16), dt_bias around 1: so that leaving one
    out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("A_log"):
            out[name] = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))
        elif name.endswith("dt_bias"):
            out[name] = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            fan_in = shape[-1] if len(shape) == 3 else shape[1]
            out[name] = jax.random.normal(key, shape, jnp.float32) / (fan_in ** 0.5)
    return out
