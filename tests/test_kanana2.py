"""kanana-2-30b-a3b (``model_type: deepseek_v3``) through the normal path
against the plain reference (``models/reference_kanana2.py``), at a tiny size
on the CPU: one leading dense layer and two routed ones, hidden 64, 4 heads of
scores 8 + 4 and values 8, latent 16, 8 experts of which 4 are held here, top
3, two shared experts, vocabulary 256, 48 positions. The weights are drawn by
``Transformer.init`` (gains and the selection bias redrawn, as the cell's
driver does) and reach the reference through the driver's own mapping
(``chipbench/drivers/train_steps_mla.py``), so that mapping is part of what is
compared.

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
from chipbench.drivers import train_steps_mla as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_kanana2 as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402

HF = {"model_type": "deepseek_v3", "architectures": ["DeepseekV3ForCausalLM"],
      "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
      "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
      "v_head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None,
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 2,
      "first_k_dense_replace": 1, "moe_layer_freq": 1, "num_hidden_layers": 3,
      "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
      "scoring_func": "sigmoid", "norm_topk_prob": True,
      "routed_scaling_factor": 2.448, "rope_theta": 1000000,
      "rope_scaling": None, "rope_interleave": True, "rms_norm_eps": 1e-6,
      "attention_bias": False, "hidden_act": "silu", "vocab_size": 256,
      "max_position_embeddings": 128, "tie_word_embeddings": False,
      "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
      # the family's published balancing, at a size that shows in every
      # comparison below (the cell's: 0.0001 and 0.001)
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

    src = harness.load_cell("kanana2-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("mla", "moe"),) and cfg.latent and not cfg.recurrent
    assert (cfg.lead_layers, tuple(cfg.lead_kind), cfg.n_layers, cfg.routed_layers) == (
        1, ("mla", "mlp"), 5, 4)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.rotary_dims) == (2048, 32, 192, 64)
    assert (cfg.mla_kv_rank, cfg.mla_qk_content_dim, cfg.mla_qk_rope_dim,
            cfg.mla_v_dim) == (512, 128, 64, 128)
    assert cfg.rope_interleaved and cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.dense_ff_dim, cfg.moe_shared_expert_ff) == (128, 16, 6, 768, 6144, 1536)
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_weight_scale, cfg.moe_aux,
            cfg.moe_shared_gate, cfg.moe_norm_topk, cfg.moe_impl) == (
        "sigmoid", True, 2.448, "sequence", "none", True, "ragged")
    # the family's published balancing (keys this configuration file adds)
    assert (cfg.aux_loss_coef, cfg.moe_bias_update_rate) == (1e-4, 1e-3)
    bare = config_from_hf({k: v for k, v in src.items() if k not in (
        "aux_loss_alpha", "seq_aux", "bias_update_speed")})
    assert (bare.moe_aux, bare.aux_loss_coef, bare.moe_bias_update_rate) == ("none", 0.0, 0.0)
    assert cfg.vocab_size == 16032 and not cfg.tie_embeddings
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    mla = 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
    dense = mla + 4_096 + 37_748_736
    routed = mla + 4_096 + 262_144 + 128 + 9_437_184 + 16 * 4_718_592
    top = 2 * 32_833_536 + 2_048
    # the ISSUE's table (576.0 M), plus the unused bias leaves of the plain
    # RMSNorms (two a layer, one for the final norm)
    assert n == dense + 4 * routed + top + (2 * 5 + 1) * 2048
    assert shapes["lead"]["w_up"].shape == (1, 2048, 6144)
    assert shapes["layers"]["mla_wq"].shape == (4, 2048, 32 * 192)
    assert shapes["layers"]["mla_wkv_a"].shape == (4, 2048, 576)
    assert shapes["layers"]["mla_wkv_b"].shape == (4, 512, 32 * 256)
    assert shapes["layers"]["moe_w_up"].shape == (4, 16, 2048, 768)
    assert "moe_shared_gate" not in shapes["layers"]


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("n_group", 8), ("topk_method", "greedy"), ("attention_bias", True),
    ("scoring_func", "softmax"), ("moe_layer_freq", 2)])
def test_what_is_not_written_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value})


def test_a_share_states_its_own_buffer():
    cut = {k: v for k, v in HF.items() if k != "expert_buffer_factor"}
    with pytest.raises(ValueError, match="expert_buffer_factor"):
        config_from_hf(cut)
    whole = config_from_hf({k: v for k, v in cut.items() if k != "num_experts_held"})
    assert whole.experts_held == whole.n_experts == 8


def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    # the counters are over the ROUTED layers: the dense layer has no row
    assert stats["moe_expert_tokens"].shape == (2, 8)
    np.testing.assert_array_equal(stats["moe_expert_tokens"],
                                  case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    np.testing.assert_allclose(stats["moe_expert_weight"], case["ref"]["expert_weight"],
                               rtol=1e-5)
    assert int(np.asarray(stats["moe_overflow_rows"]).sum()) == 0
    assert np.all(np.asarray(stats["moe_expert_tokens"]).sum(axis=1) == BATCH * SEQ * 3)


def test_the_bias_moves_the_choices(case):
    """At the drawn size the selection bias is no bystander: without it a
    good share of the token-choices go elsewhere."""
    flat = dict(case["weights"])
    for name in list(flat):
        if name.endswith("e_score_correction_bias"):
            flat[name] = jnp.zeros_like(flat[name])
    other = jax.jit(lambda w, i: ref.loss_parts(w, HF, i)["expert_tokens"])(flat, case["ids"])
    moved = np.abs(np.asarray(other) - np.asarray(case["ref"]["expert_tokens"])).sum() / 2
    assert moved / np.asarray(other).sum() > 0.02


def test_the_balance_loss_is_in_the_loss(case):
    """alpha x the sequence-wise balance loss over the routed layers: the
    program's loss without it is lower by what the reference says it is."""
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
    bias = [k for k in got if k.endswith("moe_select_bias")]
    assert bias and all(float(jnp.abs(got[k]).max()) == 0.0 for k in bias)
    theirs = {k: v for k, v in case["ref_grads"].items() if k not in bias}
    worst = gaps(got, theirs)
    assert max(worst.values()) < 2e-3, worst


def test_remat_halves_give_the_same_loss_and_gradients(case):
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
        model=model, params=driver.initial_params(model, 5, BIAS),
        config={"optimizer": {"type": "FusedAdam",
                              "params": {"lr": 1e-4, "weight_decay": 0.1}},
                "zero_optimization": {"stage": 3},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "train_batch_size": rows, "steps_per_print": 10 ** 9}, seed=5)[0]
    assert model.config.remat
    bias = np.asarray(engine.state.master["layers"]["moe_select_bias"])
    loss = float(engine.train_batch({"input_ids": ids}))
    assert abs(loss - float(want["loss"])) < 2e-5
    stats = engine.last_step_stats()
    # the bias is a buffer: after the step it is the aux-free update of what
    # it was, on the step's own counts, with nothing of AdamW's decay
    moved = np.asarray(ref.bias_update(bias, np.asarray(stats["moe_expert_tokens"]), 0.01))
    assert np.abs(moved - bias).max() == pytest.approx(0.01)
    np.testing.assert_array_equal(
        np.asarray(engine.state.master["layers"]["moe_select_bias"]), moved)
    np.testing.assert_allclose(stats["moe_expert_weight"], want["expert_weight"], rtol=1e-5)
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], want["held_rows"])
    moment = driver.first_moment(engine.state.opt_state)
    got = {k: np.asarray(v) * 10.0 for k, v in moment.items()}     # / (1 - beta1)
    theirs = {k: v for k, v in want_grads.items() if not k.endswith("moe_select_bias")}
    worst = gaps(got, theirs)
    assert max(worst.values()) < 2e-3, worst
    assert float(np.abs(got["layers/moe_select_bias"]).max()) == 0.0


def test_a_model_without_a_selection_bias_keeps_the_optimizers_masters():
    """``update_buffers`` hands back the optimizer's tree itself: nothing is
    added to the step of a model that has no buffer."""
    from shuffle_exchange_tpu.models.transformer import tiny_moe

    model = Transformer(tiny_moe())
    new = {"layers": {"moe_gate": 1}}
    assert model.update_buffers({"layers": {"moe_gate": 0}}, new, {}) is new


def test_a_held_fixed_bias_stays_as_it_was(case):
    """``bias_update_speed`` 0 (or a step without stats): the optimizer's
    update of the buffer is thrown away, the weights' is kept."""
    model = Transformer(dataclasses.replace(case["cfg"], moe_bias_update_rate=0.0))
    old = case["params"]
    new = jax.tree.map(lambda a: a * 0.5, old)
    stats = {"moe_expert_tokens": jnp.ones((2, 8), jnp.int32)}
    for got in (model.update_buffers(old, new, stats),
                case["model"].update_buffers(old, new, {})):
        np.testing.assert_array_equal(got["layers"]["moe_select_bias"],
                                      old["layers"]["moe_select_bias"])
        np.testing.assert_array_equal(got["layers"]["moe_gate"], new["layers"]["moe_gate"])


def test_the_shares_add_up_to_the_uncut_layer(case):
    """Two ranks of 4 experts each (and four of 2): the parts of one routed
    layer's result that the shares give, with what every rank computes alike
    (the shared experts) counted once, are the uncut reference's layer."""
    whole_src = {k: v for k, v in HF.items() if k not in ("num_experts_held", "expert_first")}
    whole = config_from_hf({**whole_src})
    model = Transformer(whole)
    params = driver.initial_params(model, 11, BIAS)
    weights = driver.to_source_names(params, whole_src)
    y = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(weights, "model.layers.1.mlp.", y.reshape(-1, 64), whole_src)[0]
        shared = ref.shared(weights, "model.layers.1.mlp.", y.reshape(-1, 64))
        row = jax.tree.map(lambda a: a[0], params["layers"])
        for ranks in (2, 4):
            held = 8 // ranks
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


def test_leading_layers_come_from_a_key_of_their_own(case):
    """The layers that follow the leading ones are drawn as a model without
    leading layers draws its own (the scale of the two output projections
    apart, which reads the depth)."""
    cfg = case["cfg"]
    rng = jax.random.PRNGKey(7)
    with_lead = Transformer(cfg).init(rng)
    without = Transformer(dataclasses.replace(
        cfg, lead_layers=0, lead_kind=(), n_layers=cfg.n_layers - 1)).init(rng)
    assert set(with_lead) == set(without) | {"lead"}
    for name in ("embed", "unembed"):
        np.testing.assert_array_equal(with_lead[name], without[name])
    for name in ("mla_wq", "mla_wkv_a", "mla_wkv_b", "moe_gate", "moe_w_up", "moe_shared_w_up"):
        np.testing.assert_array_equal(with_lead["layers"][name], without["layers"][name])
    assert with_lead["lead"]["w_up"].shape == (1, 64, 96)
    assert with_lead["layers"]["moe_w_up"].shape == (2, 4, 64, 32)


# what the parent of PR 35 drew from PRNGKey(7): [shape, sum, sum of |x|]
PARENT_DRAWS = {
    "tiny": {"embed": [[256, 64], -1.665031, 260.398625],
             "layers/w_down": [[2, 256, 64], 3.317414, 821.52646],
             "layers/w_up": [[2, 64, 256], 5.235818, 3246.705078],
             "layers/wk": [[2, 64, 64], 1.70065, 817.108942]},
    "tiny_moe": {"layers/moe_gate": [[2, 64, 4], -1.925945, 53.785411],
                 "layers/moe_w_down": [[2, 4, 256, 64], -31.390336, 6539.44587],
                 "layers/moe_w_gate": [[2, 4, 64, 256], -1.581869, 13074.564637]},
    "qwen3next": {"layers/gated_attn_moe/moe_gate": [[2, 1, 64, 8], 3.336399, 100.047569],
                  "layers/gated_attn_moe/moe_shared_w_up": [[2, 1, 64, 32], -11.092157, 402.027658],
                  "layers/gated_attn_moe/moe_w_down": [[2, 1, 4, 32, 64], -7.0793, 2311.638284]},
}


@pytest.mark.parametrize("name", sorted(PARENT_DRAWS))
def test_older_models_keep_their_trees_and_draws(name):
    from shuffle_exchange_tpu.models.transformer import tiny, tiny_moe

    if name == "qwen3next":
        src = open(os.path.join(ROOT, "tests", "test_qwen3next.py")).read()
        scope = {}
        exec(src[src.index("HF = {"):src.index("SEQ, BATCH")], scope)
        cfg = config_from_hf(scope["HF"])
    else:
        cfg = {"tiny": tiny, "tiny_moe": tiny_moe}[name]()
    params = Transformer(cfg).init(jax.random.PRNGKey(7))
    assert "lead" not in params
    flat = driver.flat_tree(params)
    if name == "qwen3next":
        assert "layers/gdn_moe/moe_shared_gate" in flat          # the gated form stays
        assert not any(k.endswith("moe_select_bias") for k in flat)
    for leaf, (shape, total, size) in PARENT_DRAWS[name].items():
        x = np.asarray(flat[leaf], np.float64)
        assert list(x.shape) == shape
        assert abs(x.sum() - total) < 1e-4 and abs(np.abs(x).sum() - size) < 1e-3


def test_a_dense_layer_among_routed_ones_has_no_row():
    """Megatron's interleaving: the counters are over the routed layers."""
    from shuffle_exchange_tpu.models.transformer import tiny_moe

    cfg = tiny_moe(layers=4, moe_layer_pattern=(True, False), moe_impl="ragged")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 256, (2, 17)).astype(np.int32)
    _, stats = jax.jit(model.loss_and_stats)(params, {"input_ids": ids})
    assert stats["moe_expert_tokens"].shape == (2, 4)
    assert np.all(np.asarray(stats["moe_expert_tokens"]).sum(axis=1) == 2 * 16 * 2)


def test_pipeline_stages_and_layer_drop_refuse_leading_layers(case):
    model, params = case["model"], case["params"]
    x = jnp.zeros((1, 8, 64))
    rope = (jnp.ones((8, 2)), jnp.zeros((8, 2)))
    with pytest.raises(NotImplementedError, match="lead"):
        model.stack_apply(params["layers"], x, rope)
    with pytest.raises(NotImplementedError, match="plain stack"):
        model.stack_apply(params["layers"], x, rope, layer_keep=jnp.ones((2,), bool),
                          lead=params["lead"])


@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_latent_attention_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="latent attention"):
        cls(case["model"], case["params"])


def test_checkpoint_import_is_not_written():
    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    with pytest.raises(NotImplementedError, match="latent"):
        params_from_state_dict({}, config_from_hf(HF), "deepseekv3")


def test_the_two_reference_copies_agree():
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_kanana2.py") == body(
        "shuffle_exchange_tpu/models/reference_kanana2.py")
