"""Kimi-Linear-48B-A3B (``model_type: kimi_linear``) through the normal path
against the plain reference (``models/reference_kimilinear.py``), at a tiny
size on the CPU: one leading dense KDA layer and one period (KDA, KDA, latent
attention, KDA; all routed), hidden 64, 2 KDA heads of 16 / 16 with 4 taps, 4
latent-attention heads of scores 8 + 4 and values 8, latent 16, 8 experts of
which 4 are held here, top 3, one shared expert, vocabulary 256, 48 positions.
The weights are drawn by ``Transformer.init`` (gains and the selection bias
redrawn, as the cell's driver does) and reach the reference through the
driver's own mapping (``chipbench/drivers/train_steps_kda.py``), so that
mapping is part of what is compared; and the rule's three bodies
(``ops/kda.py``) against each other.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions. Loss 1e-5; routing exact; gradients 2e-3 of
each leaf's norm.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import shuffle_exchange_tpu as sxt  # noqa: E402
from chipbench.drivers import train_steps_kda as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_kimilinear as ref  # noqa: E402
from shuffle_exchange_tpu.models import transformer as tr  # noqa: E402
from shuffle_exchange_tpu.models.hf import (config_from_hf, kimi_linear_state_dict,  # noqa: E402
                                            params_from_state_dict)
from shuffle_exchange_tpu.ops import gated_delta, kda  # noqa: E402
from tests.test_gated_delta import calls  # noqa: E402

HF = {"model_type": "kimi_linear", "architectures": ["KimiLinearForCausalLM"],
      "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
      "head_dim": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
      "v_head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None, "mla_use_nope": True,
      "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                             "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4},
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_experts": 8, "num_experts_per_token": 3, "num_shared_experts": 1,
      "first_k_dense_replace": 1, "moe_layer_freq": 1, "num_hidden_layers": 5,
      "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
      "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
      "routed_scaling_factor": 2.446, "rope_theta": 10000, "rope_scaling": None,
      "rms_norm_eps": 1e-5, "hidden_act": "silu", "vocab_size": 256,
      "model_max_length": 128, "tie_word_embeddings": False,
      "num_nextn_predict_layers": 0,
      "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
      # the assumed balancing, at a size that shows in every comparison below
      "aux_loss_alpha": 0.01, "seq_aux": True, "bias_update_speed": 0.01}
SEQ, BATCH, BIAS = 48, 2, 0.05


def gaps(ours, theirs):
    return {k: float(np.linalg.norm(np.asarray(ours[k]) - np.asarray(theirs[k]))
                     / np.linalg.norm(np.asarray(theirs[k]))) for k in theirs}


@pytest.fixture(scope="module")
def case():
    cfg = config_from_hf(HF)
    model = Transformer(cfg)
    params = driver.initial_params(model, 5, BIAS)
    weights = driver.to_source_names(params, HF)
    ids = np.random.default_rng(3).integers(0, HF["vocab_size"],
                                            (BATCH, SEQ + 1)).astype(np.int32)
    parts = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(weights, ids)
    grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(weights, ids), HF)
    return {"cfg": cfg, "model": model, "params": params, "weights": weights,
            "ids": ids, "ref": parts, "ref_grads": grads}


def test_config_from_hf_on_the_rows_own_keys():
    """The catalog row's keys, as the cell's configuration file has them: the
    published widths, the cut, and the count of what is held."""
    from chipbench import harness

    src = harness.load_cell("kimilinear-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("kda", "moe"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe"))
    assert cfg.latent and cfg.recurrent and cfg.unrotated_mixers == ("mla",)
    assert (cfg.lead_layers, tuple(cfg.lead_kind), cfg.n_layers, cfg.routed_layers,
            cfg.kda_layers) == (1, ("kda", "mlp"), 5, 4, 4)
    assert Transformer(cfg).rope_for("mla", 8) == (None, None)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim) == (2304, 32, 192)
    assert (cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim, cfg.kda_conv_kernel,
            cfg.kda_gate_rank) == (32, 128, 128, 4, 128)
    assert (cfg.mla_kv_rank, cfg.mla_qk_content_dim, cfg.mla_qk_rope_dim,
            cfg.mla_v_dim) == (512, 128, 64, 128)
    assert cfg.norm_eps == 1e-5 and cfg.vocab_size == 20480 and not cfg.tie_embeddings
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.dense_ff_dim, cfg.moe_shared_expert_ff) == (256, 8, 8, 1024, 9216, 1024)
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_weight_scale, cfg.moe_aux,
            cfg.moe_shared_gate, cfg.moe_norm_topk, cfg.moe_impl) == (
        "sigmoid", True, 2.446, "sequence", "none", True, "ragged")
    assert (cfg.aux_loss_coef, cfg.moe_bias_update_rate) == (1e-4, 1e-3)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's table (602,434,432), plus the unused bias leaves of the
    # plain RMSNorms (two a layer, one for the final norm)
    kda_mixer, mla_mixer = 39_514_272, 29_114_880
    assert n == 602_434_432 + (2 * 5 + 1) * 2304
    assert shapes["lead"]["kda_w_qkv"].shape == (1, 2304, 3 * 4096)
    assert sum(int(np.prod(x.shape[1:])) for k, x in shapes["lead"].items()
               if k.startswith("kda_")) == kda_mixer
    assert sum(int(np.prod(x.shape[2:])) for k, x in shapes["layers"]["mla_moe"].items()
               if k.startswith("mla_")) == mla_mixer
    assert shapes["layers"]["kda_moe"]["moe_w_up"].shape == (1, 3, 8, 2304, 1024)
    assert shapes["layers"]["mla_moe"]["moe_select_bias"].shape == (1, 1, 256)


def test_the_published_stack_is_refused_by_name_and_whole_periods_run():
    """27 layers end part of the way into the period: refused with the
    reason; a depth that ends on a period gives lead + periods."""
    from chipbench import harness

    src = harness.load_cell("kimilinear-train")["config"]
    whole = {**src, "num_hidden_layers": 27, "linear_attn_config": {
        **src["linear_attn_config"], **src["published"]["linear_attn_config"]}}
    with pytest.raises(ValueError, match="period cut short|cut to two"):
        config_from_hf(whole)
    nine = config_from_hf({**whole, "num_hidden_layers": 9})
    assert (nine.kda_layers, nine.routed_layers, len(nine.pattern)) == (7, 8, 4)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("num_expert_group", 8), ("mla_use_nope", False), ("moe_layer_freq", 2),
    ("moe_router_activation_func", "softmax"), ("moe_renormalize", False),
    ("num_nextn_predict_layers", 1), ("tie_word_embeddings", True),
    ("linear_attn_config", {**HF["linear_attn_config"], "full_attn_layers": [3, 4]})])
def test_what_is_not_written_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value})


def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    # the counters are over the ROUTED layers: the dense layer has no row
    assert stats["moe_expert_tokens"].shape == (4, 8)
    np.testing.assert_array_equal(stats["moe_expert_tokens"], case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    assert int(stats["moe_overflow_rows"].sum()) == 0
    np.testing.assert_allclose(stats["moe_expert_weight"], case["ref"]["expert_weight"],
                               rtol=1e-4, atol=1e-5)


def test_the_step_says_what_its_rules_keep(case):
    """``kda_layers`` is the configuration's count and ``rope_layers_rotated``
    the layers ``rope_for`` hands a table (the one latent layer, where the same
    stack is told to rotate it); ``kda_decay_mean`` / ``kda_decay_min`` are exp(the summed g over a
    chunk) over the four KDA layers, computed here from the reference's g."""
    _, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert (int(stats["kda_layers"]), int(stats["rope_layers_rotated"])) == (4, 0)
    w, x = case["weights"], None
    kept = []
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"][case["ids"][:, :-1]]
        for i in range(5):
            name = f"model.layers.{i}."
            if ref.is_kda(i, HF):
                y = ref.rms_norm(x, w[name + "input_layernorm.weight"], HF["rms_norm_eps"])
                g = ref.log_decay(w, name + "self_attn.", y, HF)
                kept.append(np.exp(np.asarray(g).sum(axis=1)))       # one chunk: T < 64
            x = ref.layer(w, i, x, HF)[0]
    kept = np.stack(kept)
    assert float(stats["kda_decay_mean"]) == pytest.approx(kept.mean(), rel=1e-4)
    assert float(stats["kda_decay_min"]) == pytest.approx(kept.min(), rel=1e-3, abs=1e-30)
    assert 0.0 < float(stats["kda_decay_mean"]) < 1.0
    # the counter follows the tables handed out, not the layout's name
    rotating = Transformer(dataclasses.replace(case["cfg"], unrotated_mixers=()))
    _, stats = jax.jit(rotating.loss_and_stats)(case["params"], {"input_ids": case["ids"]})
    assert int(stats["rope_layers_rotated"]) == 1


def test_logits(case):
    x, rope = case["model"].embed(case["params"], case["ids"][:, :-1])
    x, _ = case["model"].stack_apply(case["params"]["layers"], x, rope,
                                     lead=case["params"]["lead"])
    logits = case["model"].head(case["params"], x)
    np.testing.assert_allclose(logits, case["ref"]["logits"], rtol=2e-4, atol=2e-5)


def test_every_leafs_gradient(case):
    g = jax.jit(jax.grad(case["model"].loss))(case["params"], {"input_ids": case["ids"]})
    ours = driver.flat_tree(g)
    want = {k: v for k, v in case["ref_grads"].items() if not k.endswith("moe_select_bias")}
    assert set(want) <= set(ours)
    worst = gaps(ours, want)
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    # the buffer takes no gradient, in either kind of the period
    for kind in ("kda_moe", "mla_moe"):
        assert float(jnp.abs(g["layers"][kind]["moe_select_bias"]).max()) == 0.0


def test_the_bias_buffer_moves_in_both_kinds_of_the_period(case):
    """After one engine step the selection bias of every routed layer, KDA or
    latent, is the reference's aux-free update of the one before, and nothing
    of the optimizer's."""
    engine = sxt.initialize(
        model=Transformer(case["cfg"]), params=jax.tree.map(jnp.array, case["params"]),
        config={"train_batch_size": 8, "steps_per_print": 10 ** 9,   # a row a device
                "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-2, "weight_decay": 0.1}}},
        seed=0)[0]
    engine.train_batch({"input_ids": np.tile(case["ids"], (4, 1))})
    tokens = np.asarray(engine.last_step_stats()["moe_expert_tokens"])
    want = np.asarray(ref.bias_update(
        np.stack([case["weights"][f"model.layers.{i}.block_sparse_moe.gate."
                                  "e_score_correction_bias"] for i in range(1, 5)]),
        tokens, HF["bias_update_speed"]))
    after = driver.flat_tree(engine.state.master)
    got = np.stack([after["layers/kda_moe/moe_select_bias"][0, 0],
                    after["layers/kda_moe/moe_select_bias"][0, 1],
                    after["layers/mla_moe/moe_select_bias"][0, 0],
                    after["layers/kda_moe/moe_select_bias"][0, 2]])
    np.testing.assert_allclose(got, want, atol=1e-7)
    assert np.abs(got - want + HF["bias_update_speed"]).min() >= 0   # moved, by gamma steps


def test_the_32_shares_add_up_to_the_uncut_layer(case):
    """The share test: a routed layer's output summed over the ranks' partial
    results (each rank its experts alone, the shared expert counted once) is
    the uncut reference's layer."""
    src = {**HF, "num_experts_held": 2}
    whole = {**HF, "num_experts_held": None}
    w = ref.init_weights(whole, 11)
    y = jax.random.normal(jax.random.PRNGKey(2), (BATCH * SEQ, 64), jnp.float32)
    prefix = "model.layers.1.block_sparse_moe."
    with jax.default_matmul_precision("highest"):
        full = ref.experts(w, prefix, y, whole)[0]
        once = ref.shared(w, prefix, y)
        parts = sum(ref.experts(w, prefix, y, {**src, "expert_first": first})[0] - once
                    for first in range(0, 8, 2))
    np.testing.assert_allclose(parts + once, full, rtol=1e-4, atol=1e-5)
    # and the program's held share is the reference's, rank by rank
    cfg = dataclasses.replace(case["cfg"], n_experts_held=2, expert_first=4)
    model = Transformer(cfg)
    params = driver.initial_params(model, 5, BIAS)
    weights = driver.to_source_names(params, {**src, "expert_first": 4})
    loss = jax.jit(model.loss)(params, {"input_ids": case["ids"]})
    want = jax.jit(lambda w, i: ref.loss(w, {**src, "expert_first": 4}, i))(weights, case["ids"])
    assert abs(float(loss) - float(want)) < 1e-5


def test_mla_unrotated_is_mla_with_a_unit_table(case):
    """``_mla`` under ``unrotated_mixers`` == the rotated ``_mla`` handed cos =
    1, sin = 0, on the same leaves."""
    cfg = case["cfg"]
    lw = jax.tree.map(lambda a: a[0, 0], case["params"]["layers"]["mla_moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64), jnp.float32)
    rotating = Transformer(dataclasses.replace(cfg, unrotated_mixers=()))
    unit = (jnp.ones((SEQ, cfg.mla_qk_rope_dim // 2)), jnp.zeros((SEQ, cfg.mla_qk_rope_dim // 2)))
    assert case["model"].rope_for("mla", SEQ) == (None, None)
    np.testing.assert_allclose(case["model"]._mla(lw, x, (None, None)),
                               rotating._mla(lw, x, unit), rtol=1e-6, atol=1e-6)
    # and a real table is another function
    table = rotating.rope_for("mla", SEQ)
    assert float(jnp.abs(rotating._mla(lw, x, table)
                         - case["model"]._mla(lw, x, (None, None))).max()) > 1e-3


def rule_inputs(seed, B, T, H, dk, dv, scale=0.5, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gated_delta.l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = gated_delta.l2norm(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -scale * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def rule_grads(fn, args, dv):
    w = jnp.cos(jnp.arange(float(dv)))
    return jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("scale", [0.5, 30.0], ids=["mild", "overflowing_split"])
@pytest.mark.parametrize("chunk, T", [(8, 20), (32, 70), (64, 130)])
def test_chunked_xla_form_is_the_recurrence(chunk, T, scale):
    """o and all five gradients, dg per channel; at scale 30 a chunk's
    cumulated decay passes e^-1000: exp(-Gamma) of the naive split overflows
    float32 and the exact form must not notice."""
    args = rule_inputs(0, 2, T, 2, 16, 8, scale)
    assert kda.kernel_route(*args[:3], chunk) == "xla"
    assert float(-args[3].reshape(2, T, -1).sum(axis=1).min()) > (88.0 if scale > 1 else 0.0)
    o_r, o_c = kda.kda_recurrent(*args), kda.kda_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(o_c, o_r, atol=2e-6)
    got = rule_grads(lambda *a: kda.kda_chunked(*a, chunk=chunk), args, 8)
    want = rule_grads(kda.kda_recurrent, args, 8)
    assert got[3].shape == args[3].shape                      # dg per channel
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 2e-4 * float(jnp.abs(b).max()) + 1e-7


@pytest.mark.parametrize("scale", [0.5, 30.0], ids=["mild", "overflowing_split"])
def test_the_kernels_are_the_recurrence(monkeypatch, scale):
    """The three Pallas kernels through the interpreter (forward, the
    S0-keeping forward, the backward with dg per channel) on a ragged tail."""
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    args = rule_inputs(1, 1, 130, 4, 128, 128, scale)
    assert kda.kernel_route(*args[:3]) == "interpret"
    np.testing.assert_allclose(kda.kda_chunked(*args), kda.kda_recurrent(*args), atol=2e-6)
    got = rule_grads(kda.kda_chunked, args, 128)
    want = rule_grads(kda.kda_recurrent, args, 128)
    assert got[3].shape == args[3].shape
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 3e-4 * float(jnp.abs(b).max()) + 1e-7


# ----------------------------------------------------------------------
# The mixer's prologue (``kda_prologue``): kernels against the composition
# ----------------------------------------------------------------------

PROLOGUE_PARTS = ("q", "k", "v", "dqkv", "dconv_w")


def prologue_inputs(K, dtype, T=150, B=2, H=4, d=128):
    """(qkv, conv_w) as the mixer has them and cotangents of q, k, v: two
    rows of 150 tokens in blocks of 64 rows is a first block (zeros before
    position 0), one with a block on both sides, and a ragged last one."""
    ks = jax.random.split(jax.random.PRNGKey(10 * K + H), 5)
    qkv = jax.random.normal(ks[0], (B, T, 3 * H * d)).astype(dtype)
    conv_w = 0.5 * jax.random.normal(ks[1], (K, 3 * H * d))
    cotangents = tuple(jax.random.normal(k, (B, T, H, d)).astype(dtype) for k in ks[2:])
    return (qkv, conv_w), cotangents


def as_the_mixer_ran_it(qkv, conv_w, H, dk, dv):
    """The prologue as ``_kda`` composed it before ``kda_prologue``:
    ``ssm_conv`` on the three column ranges without bias, then ``l2norm``."""
    from shuffle_exchange_tpu.ops.ssm_conv import ssm_conv

    B, T, _ = qkv.shape
    q, k, v = ssm_conv(qkv, conv_w, jnp.zeros((conv_w.shape[1],), jnp.float32),
                       0, (H * dk, H * dk, H * dv))[1:4]
    q = (gated_delta.l2norm(q.reshape(B, T, H, dk)) * dk ** -0.5).astype(qkv.dtype)
    k = gated_delta.l2norm(k.reshape(B, T, H, dk)).astype(qkv.dtype)
    return q, k, v.reshape(B, T, H, dv)


def prologue_answers(fn, args, cotangents, heads, exact=False):
    """(q, k, v, dqkv, dconv_w) of ``fn`` (a route is chosen while tracing)
    in float32; ``exact``: on the same numbers held in float32 throughout."""
    def both(args, cotangents):
        qkv, conv_w = args
        if exact:
            qkv = qkv.astype(jnp.float32)
            cotangents = tuple(c.astype(jnp.float32) for c in cotangents)
        out, back = jax.vjp(lambda x, w: fn(x, w, *heads), qkv, conv_w)
        return tuple(a.astype(jnp.float32) for a in out + back(cotangents))
    return jax.jit(both)(args, cotangents)


# (heads, head width, taps, tokens, heads a grid step): four heads a step and
# two steps of them; 2 taps; 6 heads in threes; ONE head (the trainer test's
# model); heads of two lane tiles; whole blocks of rows
PROLOGUE_CASES = [(8, 128, 4, 150, 4), (4, 128, 2, 150, 4), (6, 128, 4, 80, 3),
                  (1, 128, 4, 150, 1), (2, 256, 4, 80, 2), (4, 128, 4, 128, 4)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("H, d, K, T, G", PROLOGUE_CASES, ids=lambda v: str(v))
def test_prologue_kernels_equal_the_composition(monkeypatch, H, d, K, T, G, dtype):
    """The one pass (interpreted; blocks of 64 rows) against the XLA body
    and against ``ssm_conv`` + ``l2norm`` as the mixer ran them: q, k, v and
    the gradients of ``qkv`` and ``conv_w``, two rows of the batch. float32:
    the same numbers to rounding. bf16: the pass rounds once, at the write,
    where the compositions round the convolution's result and again after the
    norm: no part is further from the float32 composition than they are."""
    heads = (H, d, d)
    args, cotangents = prologue_inputs(K, dtype, T=T, H=H, d=d)
    assert kda.prologue_route(*args, d, d) == "xla" and kda._prologue_heads(H, d) == G
    want = prologue_answers(kda.kda_prologue, args, cotangents, heads)
    older = prologue_answers(as_the_mixer_ran_it, args, cotangents, heads)
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    assert kda.prologue_route(*args, d, d) == "interpret"
    got = prologue_answers(functools.partial(kda.kda_prologue, rows=64), args, cotangents, heads)
    monkeypatch.delenv("SXT_FUSED_INTERPRET")
    gap = lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
    for a, b, part in zip(got, want, PROLOGUE_PARTS):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a))), part
    # the XLA body is the arithmetic the mixer had
    for a, b, part in zip(want, older, PROLOGUE_PARTS):
        assert gap(a, b) < 1e-6, (part, gap(a, b))
    if dtype == jnp.float32:
        for a, b, part in zip(got, want, PROLOGUE_PARTS):
            assert gap(a, b) < 2e-6, (part, gap(a, b))
    else:
        exact = prologue_answers(kda.kda_prologue, args, cotangents, heads, exact=True)
        for a, b, c, part in zip(got, want, exact, PROLOGUE_PARTS):
            assert gap(a, c) <= 1.02 * gap(b, c) and gap(a, c) < 4e-3, (
                part, gap(a, c), gap(b, c))


def test_prologue_reads_its_own_sequence_and_nothing_later(monkeypatch):
    """A bump at position 70 of row 0 (the second block's 7th row) moves no
    output before it and nothing of row 1 (a grid step never reads another
    sequence's rows); differentiated, the two launches carry their names."""
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    (qkv, conv_w), cotangents = prologue_inputs(4, jnp.bfloat16)
    run = lambda x: kda.kda_prologue(x, conv_w, 4, 128, 128, rows=64)
    plain, bumped = run(qkv), run(qkv.at[0, 70].add(1.0))
    for a, b in zip(plain, bumped):
        np.testing.assert_array_equal(a[0, :70], b[0, :70])
        np.testing.assert_array_equal(a[1], b[1])
        assert bool(jnp.any(a[0, 70:74] != b[0, 70:74]))
    assert calls(run, qkv) == [("kda_prologue_fwd", 3)]
    both = lambda x, w: jax.vjp(lambda x, w: kda.kda_prologue(
        x, w, 4, 128, 128, rows=64), x, w)[1](cotangents)
    assert calls(both, qkv, conv_w) == [("kda_prologue_fwd", 3),
                                              ("kda_prologue_bwd", 2)]


@pytest.mark.parametrize("why, H, dk, dv, K, dtype, forced, want", [
    ("eligible", 32, 128, 128, 4, jnp.bfloat16, True, "interpret"),
    ("float32", 32, 128, 128, 4, jnp.float32, True, "interpret"),
    ("a_sublane_tile_of_taps", 4, 128, 128, 8, jnp.bfloat16, True, "interpret"),
    ("heads_of_two_lane_tiles", 4, 256, 256, 4, jnp.bfloat16, True, "interpret"),
    ("off_a_tpu", 32, 128, 128, 4, jnp.bfloat16, False, "xla"),
    # the tests' 16-wide heads; a width between lane tiles
    ("narrow_heads", 2, 16, 16, 4, jnp.float32, True, "xla"),
    ("heads_between_lane_tiles", 4, 192, 192, 4, jnp.bfloat16, True, "xla"),
    # a step takes the same block of lanes out of each column range
    ("values_wider_than_keys", 4, 128, 256, 4, jnp.bfloat16, True, "xla"),
    ("more_taps_than_a_sublane_tile", 4, 128, 128, 9, jnp.bfloat16, True, "xla"),
    ("float16", 4, 128, 128, 4, jnp.float16, True, "xla"),
    # one head of 10 lane tiles: wider than the widest block that was run
    ("a_head_wider_than_a_step_takes", 2, 1280, 1280, 4, jnp.bfloat16, True, "xla"),
])
def test_the_prologue_is_chosen_by_backend_and_shape(monkeypatch, why, H, dk, dv, K,
                                                     dtype, forced, want):
    if forced:
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    qkv = jnp.zeros((1, 64, H * (2 * dk + dv)), dtype)
    conv_w = jnp.zeros((K, H * (2 * dk + dv)), jnp.float32)
    assert kda.prologue_route(qkv, conv_w, dk, dv) == want
    if want == "xla":
        # an ineligible shape runs the composition: no kernel in the program
        assert calls(lambda x, w: kda.kda_prologue(x, w, H, dk, dv), qkv, conv_w) == []


def test_the_prologue_takes_the_most_heads_a_step_that_divide_them():
    """``_prologue_heads``: up to ``_PROLOGUE_HEADS`` and to the widest block
    of lanes the shared kernels were run at."""
    assert [kda._prologue_heads(H, 128) for H in (32, 6, 5, 1)] == [4, 3, 1, 1]
    assert [kda._prologue_heads(4, d) for d in (256, 384, 1152, 1280)] == [4, 2, 1, 0]


def test_the_statistics_read_one_chunk_in_sixteen():
    """``chunk_sample`` hands ``chunk_decay`` the first whole chunk of every
    run of 16 (all T where T is shorter than a chunk), so the step statistics
    cost a sixteenth of a pass over the decays."""
    x = jnp.arange(2 * 2100 * 3, dtype=jnp.float32).reshape(2, 2100, 3)
    picked = kda.chunk_sample(x)                 # 32 whole chunks: chunks 0 and 16
    np.testing.assert_array_equal(
        picked, jnp.concatenate([x[:, :64], x[:, 1024:1088]], axis=1))
    np.testing.assert_array_equal(kda.chunk_sample(x[:, :40]), x[:, :40])
    np.testing.assert_array_equal(kda.chunk_sample(x[:, :100]), x[:, :64])
    g = -jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, 2048, 2, 8))) / 64
    kept = kda.chunk_decay(kda.chunk_sample(g))
    assert kept.shape == (1, 2, 2, 8)
    np.testing.assert_allclose(kept[:, 1], jnp.exp(g[:, 1024:1088].sum(axis=1)), rtol=1e-6)


def test_constant_decay_over_the_channels_is_the_scalar_rule():
    """With g[..., d] = g0 for all d the rule is Gated DeltaNet's: the two
    modules' chunked forms and gradients agree to rounding."""
    q, k, v, g, beta = rule_inputs(2, 1, 130, 2, 16, 8)
    g0 = g[..., 0]
    wide = jnp.broadcast_to(g0[..., None], g.shape)
    np.testing.assert_allclose(kda.kda_chunked(q, k, v, wide, beta),
                               gated_delta.gated_delta_chunked(q, k, v, g0, beta), atol=2e-6)
    got = rule_grads(kda.kda_chunked, (q, k, v, wide, beta), 8)
    want = rule_grads(gated_delta.gated_delta_chunked, (q, k, v, g0, beta), 8)
    np.testing.assert_allclose(got[3].sum(axis=-1), want[3], rtol=2e-4, atol=1e-6)
    for i in (0, 1, 2, 4):
        np.testing.assert_allclose(got[i], want[i], rtol=2e-4, atol=1e-6)


def test_the_trainer_runs_the_rules_kernels(monkeypatch, case):
    """A model at 128 / 128 heads under ``SXT_FUSED_INTERPRET=1`` takes the
    kernels (the prologue's two and the rule's, and no convolution
    launch of ``ops/ssm_conv.py``'s) and reads the XLA forms' loss and
    gradients."""
    hf = {**HF, "hidden_size": 128, "num_hidden_layers": 5,
          "linear_attn_config": {**HF["linear_attn_config"], "num_heads": 1, "head_dim": 128}}
    model = Transformer(config_from_hf(hf))
    params = driver.initial_params(model, 3, BIAS)
    ids = case["ids"][:1, :41]
    plain = jax.jit(jax.value_and_grad(model.loss))(params, {"input_ids": ids})
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    step = jax.value_and_grad(lambda p, b: model.loss(p, b))
    names = {name for name, _ in calls(step, params, {"input_ids": ids})}
    # (nothing is checkpointed here: the forward that keeps the states alone)
    assert names == {"kda_prologue_fwd", "kda_prologue_bwd", "kda_rule_fwd_keep",
                     "kda_rule_bwd"}, names
    fused = jax.jit(step)(params, {"input_ids": ids})
    assert abs(float(plain[0]) - float(fused[0])) < 1e-5
    worst = gaps(driver.flat_tree(fused[1]), {
        k: v for k, v in driver.flat_tree(plain[1]).items() if float(jnp.abs(v).max()) > 0})
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]


def test_hf_round_trip_of_every_leaf(case):
    params = jax.tree.map(np.asarray, case["params"])
    sd = kimi_linear_state_dict(params, case["cfg"])
    # the driver's mapping and the importer's name the same tensors
    named = driver.to_source_names(case["params"], HF)
    assert set(sd) == set(named)
    for name in sd:
        np.testing.assert_array_equal(sd[name], named[name])
    back = params_from_state_dict(sd, case["cfg"], "kimilinear")
    ours, theirs = driver.flat_tree(params), driver.flat_tree(back)
    assert set(ours) == set(theirs)
    for leaf in ours:
        np.testing.assert_array_equal(ours[leaf], theirs[leaf])


@pytest.mark.parametrize("engine_class", ["InferenceEngine", "InferenceEngineV2"])
def test_both_inference_engines_refuse_the_model_by_name(case, engine_class):
    import shuffle_exchange_tpu.inference as inference

    with pytest.raises(NotImplementedError, match="mixer 'kda'.*rotates nothing"):
        getattr(inference, engine_class)(case["model"], params=case["params"])


def test_zero3_on_the_mesh_gives_the_one_device_loss(case):
    """ZeRO-3 over the 8-device CPU mesh (the rule and the convolutions inside
    ``shard_kernel``, full remat) reads the one-device loss."""
    batch = {"input_ids": np.tile(case["ids"], (4, 1))}
    losses = []
    for extra in ({}, {"zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8},
                       "activation_checkpointing": {"enabled": True, "policy": "full"}}):
        engine = sxt.initialize(
            model=Transformer(case["cfg"]), params=jax.tree.map(jnp.array, case["params"]),
            config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                    "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}}, **extra},
            seed=0)[0]
        losses.append([float(engine.train_batch(batch)) for _ in range(2)])
    np.testing.assert_allclose(losses[1], losses[0], rtol=2e-5)


def test_models_without_kda_hand_out_no_decay_stats():
    """The new mixer's plumbing is reached only where a configuration names
    it: a DeltaNet stack's and a rotated-MLA stack's stats have no ``kda_*``
    key and no ``rope_layers_rotated``."""
    cfg = tr.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, max_seq_len=32, activation="swiglu",
        norm="rmsnorm", position="rope", tie_embeddings=False,
        layer_pattern=(("gdn", "mlp"), ("attn", "mlp")), gdn_key_heads=2, gdn_value_heads=2,
        gdn_key_dim=8, gdn_value_dim=8)
    model = Transformer(cfg)
    _, stats = model.loss_and_stats(model.init(jax.random.PRNGKey(0)),
                                    {"input_ids": np.zeros((1, 17), np.int32)})
    assert not [k for k in stats if k.startswith("kda_") or k == "rope_layers_rotated"]
