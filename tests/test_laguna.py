"""Laguna-XS.2 (``model_type: laguna``) through the normal path against the
plain reference (``models/reference_laguna.py``), at a tiny size on the CPU:
the cell's five-layer pattern (a leading dense full-attention layer, three
window layers, one routed full layer), hidden 64, heads of 16 dims, 6 query
heads on the full layers and 8 on the window layers over 2 KV heads, window
16, the full layers' RoPE a YaRN table over half of each head and the window
layers' a plain one, 16 experts of which 8 are held here, top 4 scaled by 2.5,
one shared expert, vocabulary 256, 64 positions. The weights are drawn by
``Transformer.init`` (gains redrawn, as the cell's driver does) and reach the
reference through the driver's own mapping (``chipbench/drivers/
train_steps_swa.py``), so that mapping is part of what is compared.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions. Loss 1e-5; routing exact; gradients 2e-3 of
each leaf's norm.
"""

import dataclasses
import importlib
import math
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
from chipbench.drivers import train_steps_swa as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_laguna as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402
from shuffle_exchange_tpu.models.transformer import rope_table, yarn_inv_freq  # noqa: E402

fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")

LAYERS = 5
HF = {"model_type": "laguna", "hidden_size": 64, "num_attention_heads": 6,
      "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
      "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
      "num_experts": 16, "num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
      "num_hidden_layers": LAYERS, "vocab_size": 256, "max_position_embeddings": 1024,
      "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
      "gating": True, "sliding_window": 16, "partial_rotary_factor": 0.5,
      "moe_apply_router_weight_on_input": False,
      "rope_parameters": {
          "full_attention": {"rope_theta": 100.0, "rope_type": "yarn", "factor": 4.0,
                             "original_max_position_embeddings": 32, "beta_slow": 1,
                             "beta_fast": 4, "attention_factor": 1.1386,
                             "partial_rotary_factor": 0.5},
          "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                                "partial_rotary_factor": 1.0},
          "original_max_position_embeddings": 32},
      # whole lists, as the published file has them: the first
      # num_hidden_layers entries are the layers here
      "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                      "sliding_attention"] * 3,
      "mlp_layer_types": ["dense"] + ["sparse"] * 11,
      "num_attention_heads_per_layer": [6, 8, 8, 8] * 3,
      "num_experts_held": 8, "expert_first": 0, "expert_buffer_factor": 2.0,
      "aux_loss_alpha": 0.01}
SEQ, BATCH = 64, 2


def gaps(ours, theirs):
    return {k: float(np.linalg.norm(np.asarray(ours[k]) - np.asarray(theirs[k]))
                     / np.linalg.norm(np.asarray(theirs[k]))) for k in theirs}


@pytest.fixture(scope="module")
def case():
    cfg = config_from_hf(HF)
    model = Transformer(cfg)
    params = driver.initial_params(model, 5)
    weights = driver.to_source_names(params, HF)
    ids = np.random.default_rng(3).integers(0, HF["vocab_size"],
                                            (BATCH, SEQ + 1)).astype(np.int32)
    parts = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(weights, ids)
    grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(weights, ids), HF)
    return {"cfg": cfg, "model": model, "params": params, "weights": weights,
            "ids": ids, "ref": parts, "ref_grads": grads}


def test_config_from_hf_on_the_cells_own_file():
    """The catalog row's keys, as the cell's configuration file has them: the
    published widths, the cut, and the count of what is held."""
    from chipbench import harness

    src = harness.load_cell("laguna-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("swa", "moe"),) * 3 + (("attn", "moe"),)
    assert (cfg.lead_layers, tuple(cfg.lead_kind), cfg.n_layers, cfg.routed_layers) == (
        1, ("attn", "mlp"), 5, 4)
    assert cfg.several_kinds and not cfg.recurrent and not cfg.latent
    assert (cfg.d_model, cfg.head_dim, cfg.kv_heads) == (2048, 128, 8)
    assert (cfg.heads_of("attn"), cfg.heads_of("swa"), cfg.swa_window) == (48, 64, 512)
    assert (cfg.rope_theta, cfg.rotary_dims, cfg.swa_rope_theta, cfg.swa_rotary_dim) == (
        500000.0, 64, 10000.0, 128)
    assert cfg.rope_yarn == (64.0, 4096.0, 64.0, 1.0, 1.4158883083359672)
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.dense_ff_dim, cfg.moe_shared_expert_ff) == (256, 32, 8, 512, 8192, 512)
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_weight_scale, cfg.moe_aux,
            cfg.moe_shared_gate, cfg.moe_norm_topk, cfg.moe_impl, cfg.aux_loss_coef) == (
        "sigmoid", False, 2.5, "sequence", "none", True, "ragged", 1e-4)
    assert cfg.vocab_size == 12544 and not cfg.tie_embeddings and cfg.norm_eps == 1e-6
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's table (691,034,112), plus the unused bias leaves of the
    # plain RMSNorms (two a layer, one for the final norm)
    assert n == 691_034_112 + (2 * 5 + 1) * 2048
    assert n - (2 * 5 + 1) * 2048 == src["counts"]["parameters"]
    assert shapes["lead"]["wq"].shape == (1, 2048, 48 * 128)
    assert shapes["lead"]["w_up"].shape == (1, 2048, 8192)
    assert shapes["layers"]["swa_moe"]["wq"].shape == (1, 3, 2048, 64 * 128)
    assert shapes["layers"]["swa_moe"]["wk"].shape == (1, 3, 2048, 8 * 128)
    assert shapes["layers"]["attn_moe"]["wo"].shape == (1, 1, 48 * 128, 2048)
    assert shapes["layers"]["swa_moe"]["moe_w_up"].shape == (1, 3, 32, 2048, 512)
    assert "moe_shared_gate" not in shapes["layers"]["swa_moe"]
    assert "moe_select_bias" not in shapes["layers"]["swa_moe"]


@pytest.mark.parametrize("key, value", [
    ("gating", "per-head"), ("attention_bias", True),
    ("moe_apply_router_weight_on_input", True), ("moe_router_logit_softcapping", 30.0),
    ("rope_scaling", {"type": "linear", "factor": 2}), ("norm_topk_prob", False),
    ("layer_types", ["full_attention", "chunked_attention"] * 6),
    ("mlp_layer_types", ["dense"] + ["shared_only"] * 11)])
def test_what_is_not_written_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value})


@pytest.mark.parametrize("kind, rope_type", [
    ("full_attention", "llama3"), ("full_attention", "linear"),
    ("full_attention", "dynamic"), ("full_attention", "longrope"),
    ("sliding_attention", "yarn")])
def test_every_other_rope_scaling_is_refused_by_name(kind, rope_type):
    """YaRN is accepted where it is written (the full layers'); any other
    ``rope_type``, and YaRN on the window layers, is still refused."""
    ropes = {k: dict(v) if isinstance(v, dict) else v
             for k, v in HF["rope_parameters"].items()}
    ropes[kind]["rope_type"] = rope_type
    with pytest.raises(ValueError, match=rope_type):
        config_from_hf({**HF, "rope_parameters": ropes})


def test_a_stack_that_ends_inside_a_period_is_one_long_period():
    """Lead + whole periods is what ``stack_apply`` scans; the published depth
    (40: the leading layer, nine periods of four and three window layers) is
    the leading layer and ONE period of 39 slots."""
    nine = config_from_hf({**HF, "num_hidden_layers": 9})
    assert (nine.n_layers, len(nine.pattern), nine.routed_layers) == (9, 4, 8)
    eight = config_from_hf({**HF, "num_hidden_layers": 8})
    assert eight.pattern == ((("swa", "moe"),) * 3 + (("attn", "moe"),)) * 1 + (
        ("swa", "moe"),) * 3 and eight.routed_layers == 7
    shapes = jax.eval_shape(Transformer(eight).init, jax.random.PRNGKey(0))
    assert shapes["layers"]["swa_moe"]["wq"].shape == (1, 6, 64, 8 * 16)
    assert shapes["layers"]["attn_moe"]["wq"].shape == (1, 1, 64, 6 * 16)
    published = config_from_hf({
        **HF, "num_hidden_layers": 40,
        "layer_types": HF["layer_types"][:4] * 10,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "num_attention_heads_per_layer": [6, 8, 8, 8] * 10})
    assert (published.lead_layers, len(published.pattern), published.routed_layers) == (
        1, 39, 39)
    assert sum(1 for mixer, _ in published.pattern if mixer == "attn") == 9
    with pytest.raises(ValueError, match="varies within a layer type"):
        config_from_hf({**HF, "num_attention_heads_per_layer": [6, 8, 8, 4] * 3})


def test_a_share_states_its_own_buffer():
    cut = {k: v for k, v in HF.items() if k != "expert_buffer_factor"}
    with pytest.raises(ValueError, match="expert_buffer_factor"):
        config_from_hf(cut)
    whole = config_from_hf({k: v for k, v in cut.items() if k != "num_experts_held"})
    assert whole.experts_held == whole.n_experts == 16


def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    # the counters are over the ROUTED layers: the dense layer has no row
    assert stats["moe_expert_tokens"].shape == (4, 16)
    np.testing.assert_array_equal(stats["moe_expert_tokens"],
                                  case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    assert int(np.asarray(stats["moe_overflow_rows"]).sum()) == 0
    assert np.all(np.asarray(stats["moe_expert_tokens"]).sum(axis=1) == BATCH * SEQ * 4)


def test_the_balance_loss_is_in_the_loss(case):
    bare = Transformer(dataclasses.replace(case["cfg"], moe_aux="none", aux_loss_coef=0.0))
    batch = {"input_ids": case["ids"]}
    with_it = float(jax.jit(case["model"].loss)(case["params"], batch))
    without = float(jax.jit(bare.loss)(case["params"], batch))
    want = HF["aux_loss_alpha"] * float(ref.balance_loss(case["ref"]["routing"], HF, BATCH))
    assert want > 1e-3 and abs((with_it - without) - want) < 1e-5


def test_logits(case):
    logits = jax.jit(case["model"].apply)(case["params"], case["ids"][:, :-1])
    want = np.asarray(case["ref"]["logits"])
    assert np.max(np.abs(np.asarray(logits) - want)) < 1e-4 * np.max(np.abs(want))


def test_every_gradient_leaf(case):
    got = driver.flat_tree(jax.jit(jax.grad(case["model"].loss))(
        case["params"], {"input_ids": case["ids"]}))
    unused = {k for k in got if k.endswith(("ln1_b", "ln2_b", "ln_f_b"))}
    assert set(got) - unused == set(case["ref_grads"])
    # two head counts, three kinds of layer, every leaf of each
    assert {"lead/wq", "layers/swa_moe/wq", "layers/attn_moe/wq",
            "layers/swa_moe/moe_w_down", "layers/attn_moe/moe_gate"} <= set(got)
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 2e-3, worst


@pytest.mark.parametrize("wrong", [
    "window_15", "window_17", "window_ignored", "swa_table_on_full",
    "full_table_on_swa", "yarn_without_factor", "all_dims_rotated_on_full",
    "softmax_router", "no_scale", "gated_shared"])
def test_the_nearest_wrong_models_read_far(case, wrong, monkeypatch):
    """What the comparison is FOR: each of the nearest wrong models, as the
    reference computes it, is far from the program on the first loss, where
    the program itself sits at 1e-5 (float32 both sides)."""
    src = dict(HF, rope_parameters={k: dict(v) if isinstance(v, dict) else v
                                    for k, v in HF["rope_parameters"].items()})
    ropes = src["rope_parameters"]
    if wrong.startswith("window_1"):
        src["sliding_window"] = int(wrong[-2:])
    elif wrong == "window_ignored":
        src["sliding_window"] = SEQ + 1              # every earlier key seen
    elif wrong == "swa_table_on_full":
        ropes["full_attention"] = dict(ropes["sliding_attention"])
    elif wrong == "full_table_on_swa":
        ropes["sliding_attention"] = dict(ropes["full_attention"])
    elif wrong == "yarn_without_factor":
        ropes["full_attention"]["attention_factor"] = 1.0
    elif wrong == "all_dims_rotated_on_full":
        ropes["full_attention"]["partial_rotary_factor"] = 1.0
    elif wrong == "no_scale":
        src["moe_routed_scaling_factor"] = 1.0
    if wrong == "softmax_router":
        monkeypatch.setattr(ref, "choose", _softmax_choose)
    elif wrong == "gated_shared":
        shared = ref.shared
        monkeypatch.setattr(ref, "shared", lambda *a, **k: 0.5 * shared(*a, **k))
    want = float(case["ref"]["loss"])
    got = float(jax.jit(lambda w, i: ref.loss(w, src, i))(dict(case["weights"]), case["ids"]))
    assert abs(got - want) > 2e-5, (wrong, got, want)


def _softmax_choose(logits, cfg):
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    weight = weight / weight.sum(axis=-1, keepdims=True) * cfg["moe_routed_scaling_factor"]
    return s, chosen.astype(jnp.int32), weight


def test_remat_halves_give_the_same_loss_and_gradients(case):
    """Per-half remat on every kind of the stack ("attn" among several kinds
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
    counters it hands out, and the first gradient out of Adam's moment."""
    model = Transformer(case["cfg"])
    rows = 8                                  # one per device of the test mesh
    ids = np.random.default_rng(9).integers(0, 256, (rows, SEQ + 1)).astype(np.int32)
    want = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(case["weights"], ids)
    want_grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(case["weights"], ids), HF)
    engine = sxt.initialize(
        model=model, params=driver.initial_params(model, 5),
        config={"optimizer": {"type": "FusedAdam",
                              "params": {"lr": 1e-4, "weight_decay": 0.1}},
                "zero_optimization": {"stage": 3},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "train_batch_size": rows, "steps_per_print": 10 ** 9}, seed=5)[0]
    assert model.config.remat
    loss = float(engine.train_batch({"input_ids": ids}))
    assert abs(loss - float(want["loss"])) < 2e-5
    stats = engine.last_step_stats()
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], want["held_rows"])
    moment = driver.first_moment(engine.state.opt_state)
    got = {k: np.asarray(v) * 10.0 for k, v in moment.items()}     # / (1 - beta1)
    worst = gaps(got, want_grads)
    assert max(worst.values()) < 2e-3, worst


@pytest.mark.parametrize("ranks", [8, 4])
def test_the_shares_add_up_to_the_uncut_layer(ranks):
    """The guide's tie, at the published router (256 wide, top 8 x 2.5) cut to
    a small width: the parts of one routed layer's result that the ``ranks``
    shares give (32 or 64 experts each), with what every rank computes alike
    (the shared expert) counted once, are the uncut reference's layer."""
    whole_src = {**{k: v for k, v in HF.items() if k not in (
        "num_experts_held", "expert_first", "expert_buffer_factor")},
        "num_experts": 256, "num_experts_per_tok": 8, "moe_intermediate_size": 8}
    whole = config_from_hf(whole_src)
    assert (whole.n_experts, whole.experts_held, whole.moe_top_k) == (256, 256, 8)
    model = Transformer(whole)
    params = driver.initial_params(model, 11)
    weights = driver.to_source_names(params, whole_src)
    y = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(weights, "model.layers.1.mlp.", y.reshape(-1, 64), whole_src)[0]
        shared = ref.shared(weights, "model.layers.1.mlp.", y.reshape(-1, 64))
        row = jax.tree.map(lambda a: a[0, 0], params["layers"]["swa_moe"])
        held = 256 // ranks
        total = 0.0
        for r in range(ranks):
            cfg = dataclasses.replace(whole, n_experts_held=held, expert_first=r * held,
                                      moe_held_rows_factor=float(ranks))
            lw = {k: (v[r * held:(r + 1) * held] if k.startswith("moe_w_") else v)
                  for k, v in row.items()}
            h, _, stats = Transformer(cfg)._ffn(lw, y, None, "moe")
            assert int(stats["overflow_rows"]) == 0
            total = total + h.reshape(-1, 64) - shared        # each part holds the shared once
        total = total + shared
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 1e-5, (ranks, err)


# -- the window, through the kernels ----------------------------------------------

@pytest.mark.parametrize("window, seq", [(512, 1024), (128, 384)])
def test_the_windows_edges_through_the_kernel_route(window, seq):
    """The splash kernels, interpreted, under the local mask the program builds
    (``splash_mask``): key i - (window - 1) is seen and key i - window is not,
    against a dense mask written out here; 8 query heads over 2 KV heads (the
    window layers' groups of 4 here, 8 in the cell). The probe: values that
    are one-hot in the key's index, so the output's row IS the attention row."""
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (1, seq, 8, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, seq, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, seq, 2, 64), jnp.float32)
    got = fa.splash_attention_gqa(q, k, v, interpret=True, window=window)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (i - j >= 0) & (i - j < window)
    scores = np.einsum("bthd,bshd->bhts", np.asarray(q) * 64 ** -0.5,
                       np.repeat(np.asarray(k), 4, axis=2))
    scores = np.where(seen[None, None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhts,bshd->bthd", p, np.repeat(np.asarray(v), 4, axis=2))
    assert np.abs(np.asarray(got) - want).max() < 2e-5
    # the edge itself: moving the key just outside the window changes nothing,
    # moving the last key inside it does
    row = seq - 1
    for key, moves in ((row - window, False), (row - window + 1, True)):
        v2 = v.at[0, key].add(100.0)
        out = fa.splash_attention_gqa(q, k, v2, interpret=True, window=window)
        moved = float(np.abs(np.asarray(out - got)[0, row]).max())
        assert (moved > 1e-3) == moves, (key, moved)
    # and its gradient passes through both backward kernels under the mask
    f = lambda q, k, v: jnp.sum(fa.splash_attention_gqa(
        q, k, v, interpret=True, window=window) ** 2)
    g = lambda q, k, v: jnp.sum(fa.reference_attention(q, k, v, window=window) ** 2)
    for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v), jax.grad(g, (0, 1, 2))(q, k, v)):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-4


def test_the_window_reaches_the_kernels_as_blocks_to_skip():
    """The engagement counter: of a window layer's causal block pairs at the
    cell's 16,384 positions, the share the kernel's own mask info visits."""
    assert fa.window_block(16384, 512) == 512
    share = fa.block_visit_share(16384, 512)
    assert share == pytest.approx(100.0 * 63 / 528)          # 11.9
    assert fa.block_visit_share(16384, 0) == 100.0           # no window: all of them
    assert fa.block_visit_share(2048, 4096) == 100.0         # a window past the end
    q = jax.ShapeDtypeStruct((1, 16384, 64, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 8, 128), jnp.bfloat16)
    assert fa.attention_route(q, kv, kv, impl="pallas", window=512) == "splash_window"
    assert fa.attention_route(q, kv, kv, impl="pallas") == "splash"
    assert fa.attention_route(q, kv, kv, impl="reference", window=512) == "reference"


def test_the_model_takes_the_kernel_route_under_per_half_remat(case, monkeypatch):
    """A window layer and a full layer of a stack of several kinds, each half
    checkpointed on its own, the splash route steered onto the CPU
    (interpreted): ONE forward launch a layer in the gradient's program (the
    kept residuals of the full layers, PR 36, hold for both kinds) and the
    values of the reference route."""
    cfg = dataclasses.replace(
        config_from_hf({**HF, "head_dim": 64, "sliding_window": 128,
                        "num_hidden_layers": 5}),
        remat=True, remat_policy="full")
    model = Transformer(cfg)
    params = driver.initial_params(model, 2)
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, 256, (1, 257)).astype(np.int32)}
    want = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    monkeypatch.setattr(fa, "_pallas_ok", lambda q, k, causal=True: True)
    monkeypatch.setattr(fa, "splash_attention_gqa", __import__("functools").partial(
        fa.splash_attention_gqa, interpret=True))
    step = jax.value_and_grad(lambda p, b: model.loss(p, b))
    jaxpr = jax.make_jaxpr(step)(params, batch).jaxpr

    def launches(jaxpr, kernel):
        n = 0
        for e in jaxpr.eqns:
            n += e.primitive.name == "pallas_call" and e.params["name"] == kernel
            for sub in jax.core.jaxprs_in_params(e.params):
                n += launches(sub, kernel)
        return n

    # the leading layer's scan body, and the period's four layers
    assert launches(jaxpr, "splash_mqa_fwd_residuals") == 5
    assert launches(jaxpr, "splash_mqa_dq_no_residuals") == 5
    got = jax.jit(step)(params, batch)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    worst = gaps(driver.flat_tree(got[1]), {k: v for k, v in driver.flat_tree(want[1]).items()
                                            if float(jnp.abs(v).max()) > 0})
    assert max(worst.values()) < 1e-3, worst


# -- the tables ----------------------------------------------------------------------

@pytest.mark.parametrize("position", [4097, 10000, 16383])
def test_the_yarn_table_against_the_closed_form(position):
    """Laguna-XS.2's own numbers (64 rotated dims, theta 500,000, factor 64
    from 4,096, beta 64 / 1, factor 1.41589 on cos and sin) at positions past
    the original context, against YaRN's closed form written out in float64:
    pair j turns at theta^(-2j/64), divided by 64 where it turns less than
    once over 4,096 positions, kept where it turns more than 64 times, a
    linear ramp in j between."""
    d, theta, factor, original = 64, 500000.0, 64.0, 4096.0
    j = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2 * j / d)
    at = lambda turns: d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low, high = math.floor(at(64.0)), math.ceil(at(1.0))
    assert (low, high) == (5, 16)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    want = plain * (1 - ramp) + plain / factor * ramp
    np.testing.assert_allclose(yarn_inv_freq(d, theta, factor, original, 64.0, 1.0),
                               want, rtol=2e-6)
    scale = 1.4158883083359672
    assert scale == pytest.approx(0.1 * math.log(factor) + 1.0)
    cos, sin = rope_table(16384, d, theta, (factor, original, 64.0, 1.0, scale))
    np.testing.assert_allclose(cos[position], scale * np.cos(position * want), atol=3e-3)
    np.testing.assert_allclose(sin[position], scale * np.sin(position * want), atol=3e-3)
    # below the ramp the table is the plain one (times the factor), above it
    # the plain one slowed 64 times
    plain_cos, _ = rope_table(16384, d, theta)
    np.testing.assert_allclose(cos[position, :6], scale * plain_cos[position, :6], atol=3e-3)
    # the reference's own table is the same closed form
    inv, ref_scale = ref.inverse_frequencies(
        {"rope_theta": theta, "rope_type": "yarn", "factor": factor,
         "original_max_position_embeddings": original, "beta_fast": 64, "beta_slow": 1,
         "attention_factor": scale, "partial_rotary_factor": 0.5}, 128)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert ref_scale == scale


def test_a_table_a_kind(case):
    """The two tables the stack rotates by, and who gets which: built once a
    call of ``stack_apply``, outside the scans."""
    model = case["model"]
    full, window = model.rope_for("attn", SEQ), model.rope_for("swa", SEQ)
    assert full[0].shape == (SEQ, 4) and window[0].shape == (SEQ, 8)
    assert float(jnp.abs(full[0]).max()) == pytest.approx(1.1386)
    assert float(jnp.abs(window[0]).max()) == pytest.approx(1.0)
    plain = Transformer(dataclasses.replace(case["cfg"], rope_yarn=()))
    assert float(jnp.abs(plain.rope_for("attn", SEQ)[0]).max()) == pytest.approx(1.0)
    jaxpr = jax.make_jaxpr(lambda p, i: model.apply(p, i))(
        case["params"], case["ids"][:, :-1]).jaxpr
    top = [e.primitive.name for e in jaxpr.eqns]
    assert top.count("cos") == 2 and top.count("scan") == 2


# -- serving refuses -----------------------------------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_window_and_full_kinds_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="window and full attention kinds"):
        cls(case["model"], case["params"])
    with pytest.raises(NotImplementedError, match="per-kind head counts"):
        cls(case["model"], case["params"])


def test_sequence_parallel_and_pipeline_paths_refuse_the_stack(case):
    model, params = case["model"], case["params"]
    x = jnp.zeros((1, 8, 64))
    rope = model.rope_for("attn", 8)
    with pytest.raises(NotImplementedError, match="lead"):
        model.stack_apply(params["layers"], x, rope)
    with pytest.raises(NotImplementedError, match="plain stack"):
        model.stack_apply(params["layers"], x, rope, layer_keep=jnp.ones((1,), bool),
                          lead=params["lead"])
    # the older families' flags reach a window layer too: a bias on v comes
    # out of the layer as that bias through Wo (every row of softmax sums to 1)
    flagged = Transformer(dataclasses.replace(case["cfg"], attn_qkv_bias=True))
    lw = jax.tree.map(lambda a: a[0, 0], params["layers"]["swa_moe"])
    b_v = jax.random.normal(jax.random.PRNGKey(2), lw["wv"].shape[-1:])
    biased = dict(lw, b_q=jnp.zeros(lw["wq"].shape[-1:]), b_k=jnp.zeros_like(b_v), b_v=b_v)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 64))
    rope = model.rope_for("swa", 8)
    H, KV = case["cfg"].heads_of("swa"), case["cfg"].kv_heads
    through_wo = jnp.repeat(b_v.reshape(KV, -1), H // KV, axis=0).reshape(-1) @ lw["wo"]
    np.testing.assert_allclose(
        flagged._gqa(biased, x, rope, mixer="swa"),
        model._gqa(lw, x, rope, mixer="swa") + through_wo, rtol=1e-4, atol=1e-5)


def test_checkpoint_import_is_not_written():
    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    with pytest.raises(NotImplementedError, match="leading layers"):
        params_from_state_dict({}, config_from_hf(HF), "laguna")


def test_gpt_neos_flag_of_one_kind_is_as_it_was():
    """``attention_pattern`` / ``local_attention_window`` (hf.py's GPT-Neo
    import) keep their dense-mask form: a flag of one kind, no new field."""
    cfg = config_from_hf({"model_type": "gpt_neo", "vocab_size": 64, "hidden_size": 32,
                          "num_layers": 2, "num_heads": 2, "max_position_embeddings": 32,
                          "attention_types": [[["global", "local"], 1]], "window_size": 4})
    assert cfg.attention_pattern == ("global", "local") and cfg.local_attention_window == 4
    assert (cfg.swa_window, cfg.swa_heads, cfg.rope_yarn, cfg.several_kinds) == (0, 0, (), False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 64, (1, 16)).astype(np.int32)
    local = jax.jit(model.apply)(params, ids)
    wide = jax.jit(Transformer(dataclasses.replace(cfg, local_attention_window=64)).apply)(
        params, ids)
    assert float(jnp.abs(local - wide)[:, 5:].max()) > 1e-6     # the window binds
    np.testing.assert_allclose(local[:, :4], wide[:, :4], atol=1e-5)


def test_the_two_reference_copies_agree():
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_laguna.py") == body(
        "shuffle_exchange_tpu/models/reference_laguna.py")
