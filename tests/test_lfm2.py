"""LFM2-8B-A1B (``model_type: lfm2_moe``) through the normal path against the
plain reference (``models/reference_lfm2.py``), at a tiny size on the CPU: the
cell's five-layer pattern (a leading conv + dense layer, then attention, conv,
conv, conv routed: layers 0 and 2-5 of the published 24), hidden 64, 3 taps, 4
heads of 16 over 2 KV heads with a per-head q/k RMSNorm before RoPE, 8 experts
of which 4 are held here, top 2 under a sigmoid router with a selection bias,
no shared expert, a tied head over 128 rows, 48 positions. The weights are
drawn by ``Transformer.init`` (gains and bias redrawn, as the cell's driver
does) and reach the reference through the driver's own mapping
(``chipbench/drivers/train_steps_sconv.py``), so that mapping is part of what
is compared.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions. Loss 1e-5; routing exact; gradients 2e-3 of
each leaf's norm.
"""

import dataclasses
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
from chipbench import lfm2_band  # noqa: E402
from chipbench import reference_lfm2 as bench_ref  # noqa: E402
from chipbench.drivers import train_steps_sconv as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_lfm2 as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402
from shuffle_exchange_tpu.models.transformer import _head_norm, apply_rope, rope_table  # noqa: E402
from shuffle_exchange_tpu.ops.gated_delta import causal_conv1d  # noqa: E402
from shuffle_exchange_tpu.ops.short_conv import sconv_mix, sconv_route  # noqa: E402

PUBLISHED_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                   "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv",
                   "full_attention", "conv", "conv"]
HF = {"model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
      "intermediate_size": 96, "layer_types": PUBLISHED_TYPES, "max_position_embeddings": 1024,
      "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
      "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
      "num_experts_per_tok": 2, "num_hidden_layers": 5, "layers_held": [0, 2, 3, 4, 5],
      "num_key_value_heads": 2, "rope_theta": 1000000, "routed_scaling_factor": 1,
      "use_expert_bias": True, "vocab_size": 128, "tie_word_embeddings": True,
      "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
      "bias_update_speed": 0.001}
SEQ, BATCH, BIAS_STD = 48, 2, 0.05


def gaps(ours, theirs):
    return {k: float(np.linalg.norm(np.asarray(ours[k]) - np.asarray(theirs[k]))
                     / np.linalg.norm(np.asarray(theirs[k]))) for k in theirs}


@pytest.fixture(scope="module")
def case():
    cfg = config_from_hf(HF)
    model = Transformer(cfg)
    params = driver.initial_params(model, 5, BIAS_STD)
    weights = driver.to_source_names(params, HF)
    ids = np.random.default_rng(3).integers(0, HF["vocab_size"],
                                            (BATCH, SEQ + 1)).astype(np.int32)
    parts = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(weights, ids)
    grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(weights, ids), HF)
    grads = {k: v for k, v in grads.items() if not k.endswith("/moe_select_bias")}
    return {"cfg": cfg, "model": model, "params": params, "weights": weights,
            "ids": ids, "ref": parts, "ref_grads": grads}


# -- the configuration ----------------------------------------------------------------

def test_config_from_hf_on_the_cells_own_file():
    """The catalog row's keys, as the cell's configuration file has them: the
    published widths, the cut, and the count of what is held."""
    from chipbench import harness

    src = harness.load_cell("lfm2-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("attn", "moe"),) + (("sconv", "moe"),) * 3
    assert (cfg.lead_layers, tuple(cfg.lead_kind), cfg.n_layers, cfg.routed_layers) == (
        1, ("sconv", "mlp"), 5, 4)
    assert cfg.several_kinds and not cfg.recurrent and not cfg.latent
    assert (cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.kv_heads) == (2048, 64, 32, 8)
    assert (cfg.sconv_taps, cfg.qk_norm, cfg.rope_theta, cfg.rotary_dims) == (
        3, "head", 1000000.0, 64)
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim, cfg.dense_ff_dim,
            cfg.moe_shared_expert_ff) == (32, 8, 4, 1792, 7168, 0)
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_weight_scale, cfg.moe_aux,
            cfg.moe_norm_topk, cfg.moe_impl, cfg.moe_bias_update_rate,
            cfg.moe_held_rows_factor) == ("sigmoid", True, 1.0, "none", True, "ragged",
                                          0.001, 3.0)
    assert cfg.vocab_size == 16384 and cfg.tie_embeddings and cfg.norm_eps == 1e-5
    assert (cfg.norm, cfg.activation, cfg.position) == ("rmsnorm", "swiglu", "rope")
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's table (507,820,288), plus the unused bias leaves of the plain
    # RMSNorms (two a layer, one for the final norm)
    assert n == 507_820_288 + (2 * 5 + 1) * 2048
    assert n - (2 * 5 + 1) * 2048 == src["counts"]["parameters"]
    assert "unembed" not in shapes
    assert shapes["lead"]["sconv_w_in"].shape == (1, 2048, 6144)
    assert shapes["lead"]["w_up"].shape == (1, 2048, 7168)
    assert shapes["layers"]["sconv_moe"]["sconv_w"].shape == (1, 3, 3, 2048)
    assert shapes["layers"]["sconv_moe"]["sconv_w_out"].shape == (1, 3, 2048, 2048)
    assert shapes["layers"]["attn_moe"]["wq"].shape == (1, 1, 2048, 32 * 64)
    assert shapes["layers"]["attn_moe"]["wk"].shape == (1, 1, 2048, 8 * 64)
    assert shapes["layers"]["attn_moe"]["q_norm_w"].shape == (1, 1, 64)
    assert shapes["layers"]["sconv_moe"]["moe_w_up"].shape == (1, 3, 8, 2048, 1792)
    assert shapes["layers"]["sconv_moe"]["moe_select_bias"].shape == (1, 3, 32)
    assert "q_norm_w" not in shapes["layers"]["sconv_moe"]
    assert "wq" not in shapes["lead"] and "moe_shared_w_up" not in shapes["layers"]["attn_moe"]


def test_the_published_24_layers_are_one_unrolled_period():
    """``layer_types`` as published (attention at 2, 6, 10, 14, 18, 21: no
    whole periods after the two leading layers) is ONE period of 22."""
    whole = {k: v for k, v in HF.items() if k not in (
        "layers_held", "num_experts_held", "expert_first", "expert_buffer_factor")}
    cfg = config_from_hf(dict(whole, num_hidden_layers=24))
    assert (cfg.n_layers, cfg.lead_layers, tuple(cfg.lead_kind)) == (24, 2, ("sconv", "mlp"))
    assert len(cfg.pattern) == 22 and cfg.routed_layers == 22
    assert [2 + i for i, (m, _) in enumerate(cfg.pattern) if m == "attn"] == [2, 6, 10, 14, 18, 21]
    assert all(ffn == "moe" for _, ffn in cfg.pattern)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert params["lead"]["sconv_w_in"].shape == (2, 64, 192)
    assert params["layers"]["attn_moe"]["wq"].shape == (1, 6, 64, 64)
    assert params["layers"]["sconv_moe"]["sconv_w"].shape == (1, 16, 3, 64)
    ids = np.random.default_rng(0).integers(0, 128, (1, 17)).astype(np.int32)
    loss, stats = jax.jit(model.loss_and_stats)(params, {"input_ids": ids})
    assert np.isfinite(float(loss)) and stats["moe_expert_tokens"].shape == (22, 8)


@pytest.mark.parametrize("key, value, match", [
    ("conv_bias", True, "conv_bias"),
    ("layer_types", ["conv", "conv", "sliding_attention"] + PUBLISHED_TYPES[3:], "sliding_attention"),
    ("layers_held", [0, 2, 3, 4], "layers_held"),
    ("layers_held", [0, 2, 3, 5, 4], "layers_held"),
    ("layers_held", [0, 2, 3, 4, 24], "layers_held")])
def test_what_is_not_written_is_refused_by_name(key, value, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(dict(HF, **{key: value}))


def test_a_share_states_its_own_buffer():
    cut = {k: v for k, v in HF.items() if k != "expert_buffer_factor"}
    with pytest.raises(ValueError, match="expert_buffer_factor"):
        config_from_hf(cut)
    whole = config_from_hf({k: v for k, v in cut.items() if k != "num_experts_held"})
    assert whole.experts_held == whole.n_experts == 8


# -- the program against the reference -------------------------------------------------

def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    # the counters are over the ROUTED layers: the dense layer has no row
    assert stats["moe_expert_tokens"].shape == (4, 8)
    np.testing.assert_array_equal(stats["moe_expert_tokens"], case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    assert int(np.asarray(stats["moe_overflow_rows"]).sum()) == 0
    assert np.all(np.asarray(stats["moe_expert_tokens"]).sum(axis=1) == BATCH * SEQ * 2)
    np.testing.assert_allclose(stats["moe_expert_weight"], case["ref"]["expert_weight"],
                               rtol=1e-4, atol=1e-5)


def test_logits_of_the_tied_sliced_head(case):
    logits = jax.jit(case["model"].apply)(case["params"], case["ids"][:, :-1])
    want = np.asarray(case["ref"]["logits"])
    assert logits.shape == (BATCH, SEQ, 128) and "unembed" not in case["params"]
    assert np.max(np.abs(np.asarray(logits) - want)) < 1e-4 * np.max(np.abs(want))


def test_every_gradient_leaf(case):
    got = driver.flat_tree(jax.jit(jax.grad(case["model"].loss))(
        case["params"], {"input_ids": case["ids"]}))
    unused = {k for k in got if k.endswith(("ln1_b", "ln2_b", "ln_f_b", "moe_select_bias"))}
    assert set(got) - unused == set(case["ref_grads"])
    # both mixers, three kinds of layer, every leaf of each; the tied head's
    # gradient lands on the embedding
    assert {"lead/sconv_w_in", "lead/sconv_w", "lead/w_up", "layers/sconv_moe/sconv_w",
            "layers/sconv_moe/sconv_w_out", "layers/attn_moe/q_norm_w",
            "layers/attn_moe/k_norm_w", "layers/attn_moe/wq", "layers/sconv_moe/moe_w_down",
            "layers/attn_moe/moe_gate", "embed"} <= set(got)
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 2e-3, worst
    # the selection bias is a buffer: no gradient reaches it
    assert all(float(jnp.abs(got[k]).max()) == 0.0 for k in got if k.endswith("moe_select_bias"))


@pytest.mark.parametrize("wrong", lfm2_band.WRONG)
def test_the_nearest_wrong_models_read_far(case, wrong, monkeypatch):
    """What the comparison is FOR: each of the nearest wrong models, as the
    band script builds it for the chip (``chipbench/lfm2_band.variants``: one
    piece of the benchmark's copy of the reference replaced), is far from the
    program on the first loss, where the program itself sits at 1e-5 (float32
    both sides); an untied head of the embedding's values keeps the loss and
    loses the head's part of the embedding's gradient."""
    for name, fn in lfm2_band.variants(HF)[wrong].items():
        if name != "loss_parts":                      # the band's bf16 base
            monkeypatch.setattr(bench_ref, name, fn)
    got, grad = jax.jit(jax.value_and_grad(lambda w, i: bench_ref.loss(w, HF, i)))(
        dict(case["weights"]), case["ids"])
    want = float(case["ref"]["loss"])
    if wrong == "untied_head":
        embed = np.asarray(driver._relaid(("embed",), grad["model.embed_tokens.weight"]))
        assert abs(float(got) - want) < 1e-6
        assert gaps({"embed": embed}, {"embed": case["ref_grads"]["embed"]})["embed"] > 0.1
    else:
        assert abs(float(got) - want) > 2e-5, (wrong, float(got), want)


def test_remat_halves_give_the_same_loss_and_gradients(case):
    """Per-half remat on every kind of the stack (the convolution mixer's half
    too): the values of the program without it."""
    model = Transformer(dataclasses.replace(case["cfg"], remat=True, remat_policy="full"))
    batch = {"input_ids": case["ids"]}
    a = jax.jit(jax.value_and_grad(case["model"].loss))(case["params"], batch)
    b = jax.jit(jax.value_and_grad(model.loss))(case["params"], batch)
    assert abs(float(a[0]) - float(b[0])) < 1e-6
    worst = gaps(driver.flat_tree(b[1]), {k: v for k, v in driver.flat_tree(a[1]).items()
                                          if float(jnp.abs(v).max()) > 0})
    assert max(worst.values()) < 1e-5, worst


def test_the_trainer_through_initialize(case):
    """``sxt.initialize(...).train_batch`` in float32: the first loss, the
    counters it hands out, the first gradient out of Adam's moment, and the
    selection bias of every kind carried by the aux-free rule and nothing of
    the optimizer's."""
    model = Transformer(case["cfg"])
    rows = 8                                  # one per device of the test mesh
    ids = np.random.default_rng(9).integers(0, 128, (rows, SEQ + 1)).astype(np.int32)
    want = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(case["weights"], ids)
    want_grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(case["weights"], ids), HF)
    engine = sxt.initialize(
        model=model, params=driver.initial_params(model, 5, BIAS_STD),
        config={"optimizer": {"type": "FusedAdam",
                              "params": {"lr": 1e-4, "weight_decay": 0.1}},
                "zero_optimization": {"stage": 3},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "train_batch_size": rows, "steps_per_print": 10 ** 9}, seed=5)[0]
    assert model.config.remat
    bias = lambda: {k: np.asarray(v) for k, v in driver.to_source_names(
        engine.state.master, HF).items() if k.endswith("expert_bias")}
    before = bias()
    loss = float(engine.train_batch({"input_ids": ids}))
    assert abs(loss - float(want["loss"])) < 2e-5
    stats = engine.last_step_stats()
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], want["held_rows"])
    moment = driver.first_moment(engine.state.opt_state)
    got = {k: np.asarray(v) * 10.0 for k, v in moment.items()}     # / (1 - beta1)
    worst = gaps(got, {k: v for k, v in want_grads.items()
                       if not k.endswith("/moe_select_bias")})
    assert max(worst.values()) < 2e-3, worst
    # layers 2, 3, 4, 5 are the counters' rows 0..3
    after = bias()
    for row, i in enumerate((2, 3, 4, 5)):
        name = f"model.layers.{i}.feed_forward.expert_bias"
        np.testing.assert_allclose(after[name], ref.bias_update(
            before[name], np.asarray(want["expert_tokens"])[row], 0.001), atol=1e-7)
        assert np.abs(after[name] - before[name]).max() > 5e-4


# -- the guide's tie ------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """At the published router (32 wide, top 4, the bias selecting) cut to a
    small width: the parts of one routed layer's result that the 4 ranks'
    shares of 8 experts give add up to the uncut reference's layer (there is
    no shared expert to count once)."""
    whole_src = {**{k: v for k, v in HF.items() if k not in (
        "num_experts_held", "expert_first", "expert_buffer_factor")},
        "num_experts": 32, "num_experts_per_tok": 4, "moe_intermediate_size": 8}
    whole = config_from_hf(whole_src)
    assert (whole.n_experts, whole.experts_held, whole.moe_top_k) == (32, 32, 4)
    model = Transformer(whole)
    params = driver.initial_params(model, 11, BIAS_STD)
    weights = driver.to_source_names(params, whole_src)
    y = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(weights, "model.layers.3.feed_forward.", y.reshape(-1, 64),
                           whole_src)[0]
        row = jax.tree.map(lambda a: a[0, 0], params["layers"]["sconv_moe"])
        total = 0.0
        for r in range(4):
            cfg = dataclasses.replace(whole, n_experts_held=8, expert_first=r * 8,
                                      moe_held_rows_factor=4.0)
            lw = {k: (v[r * 8:(r + 1) * 8] if k.startswith("moe_w_") else v)
                  for k, v in row.items()}
            h, _, stats = Transformer(cfg)._ffn(lw, y, None, "moe")
            assert int(stats["overflow_rows"]) == 0
            total = total + h.reshape(-1, 64)
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err


# -- the convolution at its edges -------------------------------------------------------

def test_the_pass_between_the_projections_at_its_edges():
    """Position 0 and 1 see zeros before the sequence, position t sees
    t - 2 .. t and nothing of t + 1, and no row of one sequence reaches the
    next: against the closed form, and by moving one input."""
    B, T, C = 3, 9, 8
    rng = np.random.default_rng(0)
    bcx = jnp.asarray(rng.standard_normal((B, T, 3 * C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, C)), jnp.float32)
    got = np.asarray(sconv_mix(bcx, w))
    gate_in, gate_out, x = (np.asarray(bcx[..., i * C:(i + 1) * C]) for i in range(3))
    u = gate_in * x
    want = np.zeros((B, T, C), np.float32)
    for t in range(T):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += np.asarray(w)[j] * u[:, t - 2 + j]
    want *= gate_out
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], gate_out[:, 0] * np.asarray(w)[2] * u[:, 0], rtol=1e-5)
    np.testing.assert_allclose(got[:, 1], gate_out[:, 1] * (
        np.asarray(w)[1] * u[:, 0] + np.asarray(w)[2] * u[:, 1]), rtol=1e-5, atol=1e-6)
    # one input moved: row 1's position 4 reaches positions 4, 5, 6 of row 1
    # and nothing else (not position 3: causal; not row 2's first positions)
    moved = np.asarray(sconv_mix(bcx.at[1, 4, 2 * C:].add(1.0), w))
    changed = np.abs(moved - got).max(axis=-1) > 0
    assert sorted(zip(*np.nonzero(changed))) == [(1, 4), (1, 5), (1, 6)]
    last = np.asarray(sconv_mix(bcx.at[1, T - 1, 2 * C:].add(1.0), w))
    assert sorted(zip(*np.nonzero(np.abs(last - got).max(axis=-1) > 0))) == [(1, T - 1)]
    # the oracle of the taps, and the reference's three shifted products
    np.testing.assert_allclose(causal_conv1d(jnp.asarray(u), w) * gate_out, want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ref.conv_mix(bcx, w.T[:, None, :]), want, rtol=1e-5, atol=1e-6)
    assert sconv_route(bcx, w) == "xla"
    with pytest.raises(ValueError, match="three blocks"):
        sconv_mix(bcx[..., :-1], w)


def test_the_pass_rounds_once_in_bf16():
    """bf16 operands: products and the taps' sum in float32, one rounding at
    the write."""
    rng = np.random.default_rng(1)
    bcx = jnp.asarray(rng.standard_normal((2, 16, 24)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, 8)), jnp.bfloat16)
    got = sconv_mix(bcx, w)
    assert got.dtype == jnp.bfloat16
    want = sconv_mix(bcx.astype(jnp.float32), w.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_the_mixer_alone_and_its_scopes(case):
    """``Transformer._sconv`` on one layer's leaves against the reference's
    ``short_conv``, and the three scopes it opens inside the attention
    layer's."""
    lw = jax.tree.map(lambda a: a[0, 1], {k: case["params"]["layers"]["sconv_moe"][k]
                                          for k in ("sconv_w_in", "sconv_w", "sconv_w_out")})
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, SEQ, 64), jnp.float32)
    fn = lambda lw, x: case["model"]._sconv(lw, x, None)
    got = jax.jit(fn)(lw, x)
    want = driver.reference_mixer(HF, "sconv")(lw, x)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
    text = jax.jit(fn).lower(lw, x).as_text(debug_info=True)
    for outer, own in (("attn_qkv", "sconv_in"), ("attn_core", "sconv_mix"),
                       ("attn_out", "sconv_out")):
        assert f"{outer}/{own}" in text, (outer, own)


# -- per-head q/k norm -------------------------------------------------------------------

def test_per_head_qk_norm_before_rope_against_the_closed_form(case):
    """q and k of the attention layer as the scores read them: each head's 16
    dims over their own rms times the one gain vector, THEN rotated."""
    lw = jax.tree.map(lambda a: a[0, 0], case["params"]["layers"]["attn_moe"])
    y = jax.random.normal(jax.random.PRNGKey(7), (1, 12, 64), jnp.float32)
    q = (y @ lw["wq"]).reshape(1, 12, 4, 16)
    closed = q / jnp.sqrt(jnp.mean(q * q, axis=-1, keepdims=True) + 1e-5) * lw["q_norm_w"]
    np.testing.assert_allclose(_head_norm(q, lw["q_norm_w"], "rmsnorm", 1e-5), closed,
                               rtol=1e-5, atol=1e-6)
    cos, sin = rope_table(12, 16, 1000000.0)
    named = {"a." + driver._MIXER["attn"][k]: driver._relaid((k,), lw[k])
             for k in driver._MIXER["attn"]}
    want_q, _ = ref.qk(named, "a.self_attn.", y, HF)
    np.testing.assert_allclose(apply_rope(closed, cos, sin), want_q, rtol=1e-4, atol=1e-5)
    # the mixer whole
    rope = case["model"].rope_for("attn", 12)
    got = case["model"]._gqa({k: lw[k] for k in driver._MIXER["attn"]}, y, rope, mixer="attn")
    want = ref.attention(named, "a.self_attn.", y, HF)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
    text = jax.jit(lambda lw, y: case["model"]._gqa(lw, y, rope, mixer="attn")).lower(
        {k: lw[k] for k in driver._MIXER["attn"]}, y).as_text(debug_info=True)
    assert "attn_qkv/attn_qk_norm" in text


def test_a_one_kind_model_takes_the_per_head_qk_norm(case):
    """The stack cut to its attention layers is ONE kind of layer (a flat
    ``params["layers"]``): it norms q and k per head as the hybrid's attention
    layers do. Loss and every gradient against the reference on the same two
    layers, and not what the unnormed model reads. (The window kind takes the
    whole-projection norm: ``tests/test_olmohybrid.py``.)"""
    hf = dict(HF, num_hidden_layers=2, layers_held=[2, 6], num_dense_layers=0)
    cfg = config_from_hf(hf)
    assert not cfg.several_kinds and cfg.qk_norm == "head" and cfg.lead_layers == 0
    model = Transformer(cfg)
    params = driver.initial_params(model, 5, BIAS_STD)
    assert params["layers"]["q_norm_w"].shape == (2, 16)    # flat, [L, head_dim]
    weights = driver.to_source_names(params, hf)
    batch = {"input_ids": case["ids"]}
    want = float(jax.jit(lambda w, i: ref.loss_parts(w, hf, i))(weights, case["ids"])["loss"])
    loss, grad = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert abs(float(loss) - want) < 1e-5
    want_grad = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, hf, i))(weights, case["ids"]), hf)
    want_grad = {k: v for k, v in want_grad.items() if not k.endswith("/moe_select_bias")}
    assert {"layers/q_norm_w", "layers/k_norm_w", "layers/wq"} <= set(want_grad)
    worst = gaps(driver.flat_tree(grad), want_grad)
    assert max(worst.values()) < 2e-3, worst
    bare = Transformer(dataclasses.replace(cfg, qk_norm=False))
    assert abs(float(jax.jit(bare.loss)(params, batch)) - want) > 1e-4


# -- refusals ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_convolution_kind_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="gated short-convolution layers"):
        cls(case["model"], case["params"])
    with pytest.raises(NotImplementedError, match="sconv_taps - 1 rows"):
        cls(case["model"], case["params"])


def test_a_sequence_parallel_mesh_refuses_the_convolution_kind_by_name(case, monkeypatch):
    monkeypatch.setattr(Transformer, "_sp_mesh", staticmethod(lambda: (2, None)))
    lw = jax.tree.map(lambda a: a[0], case["params"]["lead"])
    with pytest.raises(NotImplementedError, match="mixer 'sconv'.*sequence-parallel"):
        case["model"]._sconv(lw, jnp.zeros((1, 8, 64)), None)


def test_the_plain_paths_refuse_the_stack(case):
    model, params = case["model"], case["params"]
    x = jnp.zeros((1, 8, 64))
    with pytest.raises(NotImplementedError, match="lead"):
        model.stack_apply(params["layers"], x, model.rope_for("attn", 8))


def test_checkpoint_import_is_not_written():
    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    with pytest.raises(NotImplementedError, match="leading layers"):
        params_from_state_dict({}, config_from_hf(HF), "lfm2moe")


def test_the_two_reference_copies_agree():
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_lfm2.py") == body(
        "shuffle_exchange_tpu/models/reference_lfm2.py")
    assert "shuffle_exchange_tpu" not in "".join(
        line for line in body("chipbench/reference_lfm2.py").splitlines()
        if line.startswith(("import", "from")))
