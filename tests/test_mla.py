"""Latent attention (MLA) and the sigmoid router, piece by piece, at tiny sizes
on the CPU: the ``mla`` mixer against the plain reference's attention
(``models/reference_kanana2.py``), forward and every gradient; the program's
in-place pair rotation against the source's de-interleave + rotate-half;
attention whose values are narrower than its scores through
``Transformer._attention``, the kept kernel route (splash with the values'
own width) in interpret mode, and what ``attention_route`` says it takes;
``topk_select`` with sigmoid scores, a selection bias, a scale and no
balancing loss, its softmax callers bit-equal to what they had.

float32 against float32: the two sides compute the same equations in another
order of additions: 1e-5 of a result's size, 2e-4 of a gradient leaf's norm.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_kanana2 as ref  # noqa: E402
from shuffle_exchange_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, apply_rope, rope_table)
from shuffle_exchange_tpu.moe.gating import topk_select  # noqa: E402

fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")   # the module, not the function

SRC = {"hidden_size": 32, "num_attention_heads": 4, "qk_nope_head_dim": 8,
       "qk_rope_head_dim": 4, "v_head_dim": 6, "kv_lora_rank": 16,
       "rope_theta": 1000000, "rms_norm_eps": 1e-6}
CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=1, n_heads=4, max_seq_len=64,
    activation="swiglu", norm="rmsnorm", position="rope", rope_theta=1e6,
    norm_eps=1e-6, head_size=12, rotary_dim=4, rope_interleaved=True,
    mla_kv_rank=16, mla_qk_content_dim=8, mla_qk_rope_dim=4, mla_v_dim=6,
    layer_pattern=(("mla", "mlp"),), tie_embeddings=False)
B, T = 2, 24
NAMES = {"mla_wq": "q_proj.weight", "mla_wkv_a": "kv_a_proj_with_mqa.weight",
         "mla_kv_norm_w": "kv_a_layernorm.weight", "mla_wkv_b": "kv_b_proj.weight",
         "mla_wo": "o_proj.weight"}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def mixer():
    model = Transformer(CFG)
    layer = jax.tree.map(lambda a: a[0], model.init(jax.random.PRNGKey(1))["layers"])
    layer["mla_kv_norm_w"] = jax.random.uniform(jax.random.PRNGKey(2), (16,), jnp.float32, 0.5, 1.5)
    lw = {k: layer[k] for k in NAMES}
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, 32), jnp.float32)
    rope = rope_table(T, CFG.rotary_dims, CFG.rope_theta)
    ours = lambda lw, x: model._mla(lw, x, rope)
    theirs = lambda lw, x: ref.attention(
        {"a." + NAMES[k]: (v.T if v.ndim == 2 else v) for k, v in lw.items()},
        "a.", x, SRC)
    return lw, x, ours, theirs


def test_the_mla_mixer_forward(mixer):
    lw, x, ours, theirs = mixer
    with jax.default_matmul_precision("highest"):
        assert rel(ours(lw, x), theirs(lw, x)) < 1e-5


def test_the_mla_mixer_every_gradient(mixer):
    lw, x, ours, theirs = mixer
    g = jax.random.normal(jax.random.PRNGKey(4), (B, T, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ga = jax.grad(lambda lw, x: jnp.sum(ours(lw, x) * g), argnums=(0, 1))(lw, x)
        gb = jax.grad(lambda lw, x: jnp.sum(theirs(lw, x) * g), argnums=(0, 1))(lw, x)
    worst = {k: rel(ga[0][k], gb[0][k]) for k in lw}
    worst["x"] = rel(ga[1], gb[1])
    assert max(worst.values()) < 2e-4, worst


def test_pair_rotation_in_place_gives_the_sources_scores():
    """The source moves the even dims in front of the odd ones and rotates
    halves; the program turns the adjacent pairs where they are. The results
    differ by that permutation, which sits in q and k alike: equal scores."""
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, 4, 8), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, 1, 8), jnp.float32)
    cos, sin = rope_table(T, 8, 1e6)
    ours = lambda x: apply_rope(x, cos, sin, interleaved=True)
    scores = lambda a, b: jnp.einsum("bqhd,bkd->bhqk", a, b[:, :, 0])
    got, want = scores(ours(q), ours(k)), scores(ref.rope(q, 1e6), ref.rope(k, 1e6))
    assert rel(got, want) < 1e-6
    # and the permutation is the one the source applies
    even_first = lambda x: jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    assert rel(even_first(ours(q)), ref.rope(q, 1e6)) < 1e-6
    # a half-rotation of the pairs as stored is another function
    half = lambda x: apply_rope(x, cos, sin, interleaved=False)
    assert rel(scores(half(q), half(k)), want) > 1e-2


def naive(q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def test_attention_at_unequal_widths_through_the_models_attention():
    model = Transformer(CFG)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k = (jax.random.normal(kk, (B, T, 4, 12), jnp.float32) for kk in ks[:2])
    v = jax.random.normal(ks[2], (B, T, 4, 6), jnp.float32)
    out = model._attention(q, k, v, None)
    assert out.shape == (B, T, 4, 6)
    assert rel(out, naive(q, k, v)) < 1e-5
    g = jax.grad(lambda *a: jnp.sum(model._attention(*a, None) ** 2), argnums=(0, 1, 2))(q, k, v)
    w = jax.grad(lambda *a: jnp.sum(naive(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    assert max(rel(a, b) for a, b in zip(g, w)) < 1e-4


def test_the_kept_kernel_route_in_interpret_mode():
    """Splash with the values' own width (192 / 128, as the cell runs it),
    forward and the three input gradients against the jnp oracle."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    shape = (1, 256, 2)
    q, k = (jax.random.normal(kk, shape + (192,), jnp.float32) for kk in ks[:2])
    v = jax.random.normal(ks[2], shape + (128,), jnp.float32)
    g = jax.random.normal(ks[3], shape + (128,), jnp.float32)
    kernel = lambda q, k, v: fa.splash_attention_gqa(q, k, v, causal=True, interpret=True)
    out, back = jax.vjp(kernel, q, k, v)
    want, wback = jax.vjp(lambda *a: fa.reference_attention(*a, causal=True), q, k, v)
    assert out.shape == shape + (128,)
    assert rel(out, want) < 2e-3
    assert max(rel(a, b) for a, b in zip(back(g), wback(g))) < 5e-3


@pytest.mark.parametrize("shapes, impl, enabled, want", [
    (((2, 256, 4, 192), (2, 256, 4, 192), (2, 256, 4, 128)), "auto", True, "splash_own_v"),
    # MHA off a kernel mesh: the splash kernels at a group of one (PR 56;
    # on a mesh of several devices "stock_flash": tests/test_mha_route.py)
    (((2, 256, 4, 128), (2, 256, 4, 128), (2, 256, 4, 128)), "auto", True, "splash"),
    (((2, 256, 4, 64), (2, 256, 4, 64), (2, 256, 4, 64)), "auto", True, "splash"),
    (((2, 256, 8, 128), (2, 256, 2, 128), (2, 256, 2, 128)), "auto", True, "splash"),
    (((2, 256, 4, 192), (2, 256, 4, 192), (2, 256, 4, 128)), "auto", False, "reference"),
    (((2, 256, 4, 192), (2, 256, 4, 192), (2, 256, 4, 128)), "reference", True, "reference"),
    (((2, 64, 4, 192), (2, 64, 4, 192), (2, 64, 4, 128)), "auto", True, "reference"),
])
def test_attention_route_names_what_runs(monkeypatch, shapes, impl, enabled, want):
    from shuffle_exchange_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: enabled)
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    assert fa.attention_route(q, k, v, impl=impl) == want


# -- the router -----------------------------------------------------------


def old_topk_select(logits, k, normalize_weights=True, aux="first_choice"):
    """``topk_select`` as every caller had it before the sigmoid forms."""
    E = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    idxs, ws, masks, masked = [], [], [], logits
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        idxs.append(idx.astype(jnp.int32))
        ws.append(jnp.sum(gates * m, axis=-1))
        masks.append(m)
        masked = jnp.where(m > 0, -jnp.inf, masked)
    chosen = masks[0] if aux == "first_choice" else sum(masks)
    aux_loss = E * jnp.sum(gates.mean(axis=0) * chosen.mean(axis=0))
    w = jnp.stack(ws, axis=1)
    if normalize_weights and k > 1:
        w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-9)
    return jnp.stack(idxs, axis=1), w, aux_loss


LOGITS = jax.random.normal(jax.random.PRNGKey(8), (96, 16), jnp.float32)


@pytest.mark.parametrize("aux", ["first_choice", "all_choices"])
@pytest.mark.parametrize("normalize", [True, False])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_softmax_callers_are_bit_equal(k, normalize, aux):
    got = topk_select(LOGITS, k, normalize_weights=normalize, aux=aux)[:3]
    want = old_topk_select(LOGITS, k, normalize, aux)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sigmoid_router_the_bias_selects_and_is_not_weighed():
    bias = jnp.zeros((16,)).at[3].set(10.0).at[5].set(-10.0)
    cfg = {"num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 2.448}
    idx, w, aux, _ = topk_select(LOGITS, 4, score="sigmoid", select_bias=bias,
                                 weight_scale=2.448, aux="none")
    s, chosen, weight = ref.choose(LOGITS, bias, cfg)
    np.testing.assert_array_equal(np.sort(idx, axis=1), np.sort(chosen, axis=1))
    order = lambda i, x: np.take_along_axis(np.asarray(x), np.argsort(np.asarray(i), axis=1), axis=1)
    np.testing.assert_allclose(order(idx, w), order(chosen, weight), rtol=1e-6)
    assert np.all(np.any(np.asarray(idx) == 3, axis=1))        # the bias selects
    assert not np.any(np.asarray(idx) == 5)
    # ... and is not weighed: expert 3's weight is its plain score's share
    plain = jax.nn.sigmoid(LOGITS)
    mine = np.take_along_axis(np.asarray(plain), np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.asarray(w), 2.448 * mine / mine.sum(axis=1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.448, rtol=1e-6)   # normalised, scaled
    assert float(aux) == 0.0
    # no gradient reaches the bias; the logits get one
    g = jax.grad(lambda b: topk_select(LOGITS, 4, score="sigmoid", select_bias=b,
                                       aux="none")[1].sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0


def test_sigmoid_router_forms_one_by_one():
    raw = topk_select(LOGITS, 3, score="sigmoid", normalize_weights=False, aux="none")[1]
    s = np.sort(np.asarray(jax.nn.sigmoid(LOGITS)), axis=1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.asarray(raw), s, rtol=1e-6)      # raw scores, the 3 largest
    with pytest.raises(ValueError, match="score"):
        topk_select(LOGITS, 2, score="tanh")
    with pytest.raises(ValueError, match="aux"):
        topk_select(LOGITS, 2, aux="some")


@pytest.mark.parametrize("sequences", [1, 2, 4])
def test_the_sequence_wise_balance_loss(sequences):
    """DeepSeek-V3's complementary loss, written out: per sequence, sum over
    the experts of f_e = E / (k T) x the choices of e and P_e = the mean of
    the scores' share; the mean over the sequences. Its gradient reaches the
    logits through P only, and the reference computes the same."""
    S, E = LOGITS.shape
    k, T = 4, S // sequences
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(7), (E,))
    idx, _, aux, _ = topk_select(LOGITS, k, score="sigmoid", select_bias=bias,
                                 aux="sequence", sequences=sequences)
    s = np.asarray(jax.nn.sigmoid(LOGITS), np.float64)
    share = s / s.sum(axis=1, keepdims=True)
    want = 0.0
    for b in range(sequences):
        rows = slice(b * T, (b + 1) * T)
        f = np.bincount(np.asarray(idx)[rows].reshape(-1), minlength=E) * E / (k * T)
        want += float(np.sum(f * share[rows].mean(axis=0))) / sequences
    assert abs(float(aux) - want) < 1e-6 * want
    cfg = {"n_routed_experts": E, "num_experts_per_tok": k}
    s32, chosen, _ = ref.choose(LOGITS, bias, cfg)
    theirs = ref.balance_loss([{"s": s32, "chosen": chosen}], cfg, sequences)
    assert abs(float(theirs) - want) < 1e-6 * want
    g = jax.grad(lambda z: topk_select(z, k, score="sigmoid", select_bias=bias,
                                       aux="sequence", sequences=sequences)[2])(LOGITS)
    assert float(jnp.abs(g).max()) > 0.0


def test_the_other_router_forms_run_the_dropless_impl_only():
    from shuffle_exchange_tpu.moe.layer import init_expert_mlp, moe_layer

    experts = init_expert_mlp(jax.random.PRNGKey(0), 4, 16, 8, "swiglu")
    gate = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    x = jax.random.normal(jax.random.PRNGKey(2), (12, 16))
    with pytest.raises(ValueError, match="ragged"):
        moe_layer(gate, experts, x, impl="capacity", score="sigmoid")
    with pytest.raises(ValueError, match="ragged"):
        moe_layer(gate, experts, x, impl="capacity", aux="sequence")
    out = moe_layer(gate, experts, x, impl="ragged", score="sigmoid", aux="none",
                    select_bias=jnp.zeros((4,)), weight_scale=2.0)
    assert out.output.shape == x.shape and float(out.aux_loss) == 0.0
    # a router with a selection bias also says what each expert's choices weigh
    weight = np.asarray(out.metadata["expert_weight"])
    np.testing.assert_allclose(weight.sum(), 2.0 * 12, rtol=1e-6)   # normalised x scale, a token
    assert "expert_weight" not in moe_layer(gate, experts, x, impl="ragged").metadata
