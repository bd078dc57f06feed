"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) through the
normal path against the plain reference (``models/reference_nemotron3.py``), at
a tiny size on the CPU: the cell's nine-layer pattern ``MEMEM*EME`` (layers 0-8
of the published 52: five (mixer, ffn) blocks, one of them a mixer alone),
hidden 64, 8 Mamba heads of 8 over 2 groups of state 16 with 4 taps (the scan's
chunk is ``ops/ssd.CHUNK``, whatever ``chunk_size`` says), 4 attention heads of 16 over 2 KV heads that rotate nothing, 8 ungated
relu2 experts of which 4 are held here, top 2 under a sigmoid router with a
selection bias, a shared expert of twice their width, an untied head over 128
rows, 48 positions. The weights are drawn by ``Transformer.init`` (gains, skip
and bias redrawn, as the cell's driver does) and reach the reference through
the driver's own mapping (``chipbench/drivers/train_steps_ssm.py``), so that
mapping is part of what is compared.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions (the chunked scan against the recurrence). Loss
1e-5; routing exact; gradients 2e-3 of each leaf's norm.
"""

import dataclasses
import json
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
from chipbench import nemotron3_band  # noqa: E402
from chipbench import reference_nemotron3 as bench_ref  # noqa: E402
from chipbench.drivers import train_steps_ssm as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_nemotron3 as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import _nemotron_h_pairs, config_from_hf  # noqa: E402
from shuffle_exchange_tpu.models.transformer import activation_fn  # noqa: E402
from shuffle_exchange_tpu.profiling import trace  # noqa: E402

PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
HF = {"model_type": "nemotron_h", "hidden_size": 64, "hybrid_override_pattern": PUBLISHED,
      "num_hidden_layers": 9, "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
      "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16, "use_conv_bias": True,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "layer_norm_epsilon": 1e-5, "n_routed_experts": 8, "num_experts_per_tok": 2,
      "norm_topk_prob": True, "routed_scaling_factor": 2.5, "moe_intermediate_size": 32,
      "moe_shared_expert_intermediate_size": 64, "n_shared_experts": 1, "n_group": 1,
      "topk_group": 1, "vocab_size": 128, "tie_word_embeddings": False,
      "mlp_hidden_act": "relu2", "max_position_embeddings": 1024, "rope_theta": 10000,
      "time_step_min": 0.001, "time_step_max": 0.1, "num_experts_held": 4,
      "expert_first": 0, "expert_buffer_factor": 2.0, "bias_update_speed": 0.001,
      "aux_loss_alpha": 1e-4, "seq_aux": True}
SEQ, BATCH, BIAS_STD = 48, 2, 0.05
UNUSED = ("ln1_b", "ln2_b", "ln_f_b", "moe_select_bias")


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

def test_config_from_hf_on_the_catalogs_row():
    """The catalog's ``config`` as it is: the published pattern as 29 blocks
    of 52 half-layers, every width, and 31,577,940,288 parameters."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
    cfg = config_from_hf(row["config"])
    assert row["config"]["hybrid_override_pattern"] == PUBLISHED and len(PUBLISHED) == 52
    # not whole periods: ONE unrolled period of 29 blocks
    assert (cfg.n_layers, len(cfg.pattern), cfg.lead_layers, cfg.routed_layers) == (29, 29, 0, 23)
    letters = "".join({"ssm": "M", "attn": "*"}[m] + {"moe": "E", "none": ""}[f]
                      for m, f in cfg.pattern)
    assert letters == PUBLISHED
    assert sum(m == "ssm" for m, _ in cfg.pattern) == 23
    assert sum(m == "attn" for m, _ in cfg.pattern) == 6
    assert sum(f == "none" for _, f in cfg.pattern) == 6
    assert cfg.several_kinds and cfg.recurrent and not cfg.latent
    assert (cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.kv_heads) == (2688, 128, 32, 2)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv_kernel) == (64, 64, 8, 128, 4)
    assert not hasattr(cfg, "ssm_chunk")        # the algorithm's chunk is no model size
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.moe_shared_expert_ff, cfg.moe_shared_gate) == (128, 128, 6, 1856, 3712, "none")
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_weight_scale, cfg.moe_norm_topk,
            cfg.moe_impl, cfg.moe_aux) == ("sigmoid", True, 2.5, True, "ragged", "none")
    assert (cfg.norm, cfg.activation, cfg.position, cfg.norm_eps, cfg.mlp_bias) == (
        "rmsnorm", "relu2", "none", 1e-5, False)
    assert cfg.vocab_size == 131072 and not cfg.tie_embeddings
    flat = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0)))[0]
    n = sum(int(np.prod(x.shape)) for path, x in flat if path[-1].key not in UNUSED[:3])
    assert n == 31_577_940_288
    # beside them, the unused bias leaves of the plain RMSNorms: one a norm
    assert sum(int(np.prod(x.shape)) for path, x in flat
               if path[-1].key in UNUSED[:3]) == (29 + 23 + 1) * 2688


def test_config_from_hf_on_the_cells_cut(case):
    cfg = case["cfg"]
    assert cfg.pattern == (("ssm", "moe"), ("ssm", "moe"), ("ssm", "none"), ("attn", "moe"),
                           ("ssm", "moe"))
    assert (cfg.n_layers, cfg.routed_layers, cfg.experts_held, cfg.moe_held_rows_factor,
            cfg.moe_bias_update_rate, cfg.moe_aux, cfg.aux_loss_coef) == (
        5, 4, 4, 2.0, 0.001, "sequence", 1e-4)
    layers = case["params"]["layers"]
    assert set(layers) == {"ssm_moe", "ssm_none", "attn_moe"}
    assert layers["ssm_moe"]["ssm_w_in"].shape == (1, 3, 64, 2 * 64 + 2 * 2 * 16 + 8)
    assert layers["ssm_moe"]["ssm_conv_w"].shape == (1, 3, 4, 64 + 2 * 2 * 16)
    assert layers["ssm_moe"]["ssm_conv_b"].shape == (1, 3, 64 + 2 * 2 * 16)
    assert layers["ssm_moe"]["ssm_norm_w"].shape == (1, 3, 64)
    assert layers["ssm_moe"]["moe_w_up"].shape == (1, 3, 4, 64, 32)
    assert layers["ssm_moe"]["moe_shared_w_up"].shape == (1, 3, 64, 64)
    assert layers["attn_moe"]["wq"].shape == (1, 1, 64, 64)
    assert layers["attn_moe"]["wk"].shape == (1, 1, 64, 32)
    # ungated: no gate matrix, in the experts or in the shared one
    assert not any("w_gate" in name for kind in layers.values() for name in kind)
    # the (ssm, none) block holds a mixer and ONE norm: no ffn leaf
    alone = set(layers["ssm_none"])
    assert alone == {"ln1_w", "ln1_b"} | set(driver._MIXER["ssm"])
    assert not any(name.startswith(("ln2", "moe_", "w_")) for name in alone)
    assert "unembed" in case["params"] and "pos_embed" not in case["params"]


@pytest.mark.parametrize("pattern, pairs", [
    ("MEMEM*EME", [("ssm", "moe"), ("ssm", "moe"), ("ssm", "none"), ("attn", "moe"),
                   ("ssm", "moe")]),
    ("M", [("ssm", "none")]),
    ("*M*E", [("attn", "none"), ("ssm", "none"), ("attn", "moe")]),
    ("MEM", [("ssm", "moe"), ("ssm", "none")])])
def test_the_letters_pair_into_blocks(pattern, pairs):
    """An odd number of half-layers too: a mixer with nothing after it is a
    block alone."""
    assert _nemotron_h_pairs(pattern) == pairs


@pytest.mark.parametrize("key, value, match", [
    ("n_group", 2, "n_group"),
    ("topk_group", 2, "topk_group"),
    ("hybrid_override_pattern", "MEM-M*EME" + PUBLISHED[9:], "'-'"),
    ("hybrid_override_pattern", "MEMXM*EME" + PUBLISHED[9:], "'X'"),
    ("hybrid_override_pattern", "EMEMEM*EM" + PUBLISHED[9:], "no mixer"),
    ("hybrid_override_pattern", "MEEMEM*EM" + PUBLISHED[9:], "no mixer"),
    ("num_hidden_layers", 53, "num_hidden_layers"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings")])
def test_what_is_not_written_is_refused_by_name(key, value, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(dict(HF, **{key: value}))


def test_relu2_is_an_activation_of_the_dense_path_too():
    x = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 3.0])
    np.testing.assert_array_equal(activation_fn("relu2")(x), [0.0, 0.0, 0.0, 0.25, 9.0])
    dense = Transformer(dataclasses.replace(
        Transformer(config_from_hf(HF)).config, layer_pattern=(), n_experts=0, n_experts_held=0,
        moe_select_bias=False, moe_shared_expert_ff=0, n_layers=1, position="rope",
        ssm_heads=0, d_ff=96))
    params = dense.init(jax.random.PRNGKey(0))
    assert set(params["layers"]) >= {"w_up", "w_down"} and "w_gate" not in params["layers"]
    assert "b_up" not in params["layers"]
    ids = np.zeros((1, 9), np.int32)
    assert np.isfinite(float(dense.loss(params, {"input_ids": ids})))


# -- the program against the reference -------------------------------------------------

def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    # the counters are over the ROUTED blocks: the mixer alone has no row
    assert stats["moe_expert_tokens"].shape == (4, 8)
    np.testing.assert_array_equal(stats["moe_expert_tokens"], case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    assert int(np.asarray(stats["moe_overflow_rows"]).sum()) == 0
    assert stats["moe_visited_rows"].shape == (4,)
    assert np.all(np.asarray(stats["moe_expert_tokens"]).sum(axis=1) == BATCH * SEQ * 2)
    np.testing.assert_allclose(stats["moe_expert_weight"], case["ref"]["expert_weight"],
                               rtol=1e-4, atol=1e-5)
    # 48 positions in one chunk of 128, 2 sequences, 4 state-space layers
    assert int(stats["ssm_scan_chunks"]) == 1 * 2 * 4


def test_logits_of_the_untied_sliced_head(case):
    logits = jax.jit(case["model"].apply)(case["params"], case["ids"][:, :-1])
    want = np.asarray(case["ref"]["logits"])
    assert logits.shape == (BATCH, SEQ, 128)
    assert np.max(np.abs(np.asarray(logits) - want)) < 1e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat_halves"])
def test_every_gradient_leaf(case, remat):
    """Every leaf of every kind of block against the reference's, with and
    without per-half remat (the mixer alone is then ONE checkpointed half)."""
    model = case["model"] if not remat else Transformer(
        dataclasses.replace(case["cfg"], remat=True, remat_policy="full"))
    loss, grad = jax.jit(jax.value_and_grad(model.loss))(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    got = driver.flat_tree(grad)
    unused = {k for k in got if k.endswith(UNUSED)}
    assert set(got) - unused == set(case["ref_grads"])
    assert {"layers/ssm_none/ssm_w_in", "layers/ssm_moe/ssm_conv_b", "layers/ssm_moe/ssm_A_log",
            "layers/ssm_moe/ssm_dt_bias", "layers/ssm_moe/ssm_D", "layers/ssm_moe/ssm_norm_w",
            "layers/attn_moe/wq", "layers/ssm_moe/moe_w_down", "layers/ssm_moe/moe_shared_w_up",
            "layers/attn_moe/moe_gate", "embed", "unembed"} <= set(got)
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 2e-3, worst
    # the selection bias is a buffer: no gradient reaches it
    assert all(float(jnp.abs(got[k]).max()) == 0.0 for k in got if k.endswith("moe_select_bias"))


@pytest.mark.parametrize("wrong", nemotron3_band.WRONG)
def test_the_nearest_wrong_models_read_far(case, wrong, monkeypatch):
    """What the comparison is FOR: each of the nearest wrong models, as the
    band script builds it for the chip (``chipbench/nemotron3_band.variants``:
    one piece of the benchmark's copy of the reference replaced), is far from
    the program on the first loss, where the program itself sits at 1e-5
    (float32 both sides)."""
    for name, fn in nemotron3_band.variants(HF)[wrong].items():
        if name != "loss_parts":                      # the band's bf16 base
            monkeypatch.setattr(bench_ref, name, fn)
    got = jax.jit(lambda w, i: bench_ref.loss(w, HF, i))(dict(case["weights"]), case["ids"])
    want = float(case["ref"]["loss"])
    assert abs(float(got) - want) > 2e-5, (wrong, float(got), want)


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
        engine.state.master, HF).items() if k.endswith("e_score_correction_bias")}
    before = bias()
    loss = float(engine.train_batch({"input_ids": ids}))
    assert abs(loss - float(want["loss"])) < 2e-5
    stats = engine.last_step_stats()
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], want["held_rows"])
    assert int(stats["ssm_scan_chunks"]) == 1 * rows * 4
    moment = driver.first_moment(engine.state.opt_state)
    got = {k: np.asarray(v) * 10.0 for k, v in moment.items()}     # / (1 - beta1)
    worst = gaps(got, {k: v for k, v in want_grads.items()
                       if not k.endswith("/moe_select_bias")})
    assert max(worst.values()) < 2e-3, worst
    # the source's layers 1, 3, 6, 8 (the E's) are the counters' rows 0..3
    after = bias()
    for row, i in enumerate((1, 3, 6, 8)):
        name = f"backbone.layers.{i}.mixer.gate.e_score_correction_bias"
        np.testing.assert_allclose(after[name], ref.bias_update(
            before[name], np.asarray(want["expert_tokens"])[row], 0.001), atol=1e-7)
        assert np.abs(after[name] - before[name]).max() > 5e-4


# -- the guide's tie ------------------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """At the published router (128 wide, top 6, the bias selecting) cut to a
    small width: the parts of one routed layer's result that the 16 ranks'
    shares of 8 experts give, with the shared expert (which every rank
    computes alike) counted once, add up to the uncut reference's layer."""
    whole_src = {**{k: v for k, v in HF.items() if k not in (
        "num_experts_held", "expert_first", "expert_buffer_factor")},
        "n_routed_experts": 128, "num_experts_per_tok": 6, "moe_intermediate_size": 8}
    whole = config_from_hf(whole_src)
    assert (whole.n_experts, whole.experts_held, whole.moe_top_k) == (128, 128, 6)
    model = Transformer(whole)
    params = driver.initial_params(model, 11, BIAS_STD)
    weights = driver.to_source_names(params, whole_src)
    y = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        prefix = "backbone.layers.1.mixer."
        want = ref.experts(weights, prefix, y.reshape(-1, 64), whole_src)[0]
        shared = ref.mlp(weights, prefix + "shared_experts.", y.reshape(-1, 64))
        row = jax.tree.map(lambda a: a[0, 0], params["layers"]["ssm_moe"])
        total = 0.0
        for r in range(16):
            cfg = dataclasses.replace(whole, n_experts_held=8, expert_first=r * 8,
                                      moe_held_rows_factor=16.0)
            lw = {k: (v[r * 8:(r + 1) * 8] if k.startswith("moe_w_") else v)
                  for k, v in row.items()}
            h, _, stats = Transformer(cfg)._ffn(lw, y, None, "moe")
            assert int(stats["overflow_rows"]) == 0
            # a rank's result = its experts' part + the shared expert
            total = total + h.reshape(-1, 64) - shared
        total = total + shared                      # counted once
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err


# -- the mixers alone --------------------------------------------------------------------

def test_the_state_space_mixer_alone_and_its_scopes(case):
    """``Transformer._ssm`` on one layer's leaves against the reference's
    ``mamba``, and the six scopes it opens inside the attention layer's."""
    lw = jax.tree.map(lambda a: a[0, 1], {k: case["params"]["layers"]["ssm_moe"][k]
                                          for k in driver._MIXER["ssm"]})
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, SEQ, 64), jnp.float32)
    fn = lambda lw, x: case["model"]._ssm(lw, x, None)
    got = jax.jit(fn)(lw, x)
    want = driver.reference_mixer(HF)(lw, x)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
    text = jax.jit(fn).lower(lw, x).as_text(debug_info=True)
    for outer, own in (("attn_qkv", "ssm_in"), ("attn_qkv", "ssm_conv"),
                       ("attn_qkv", "ssm_gates"), ("attn_core", "ssm_scan"),
                       ("attn_out", "ssm_out_norm"), ("attn_out", "ssm_out")):
        assert f"{outer}/{own}" in text, (outer, own)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("state, scan", [(64, "xla"), (128, "interpret")],
                         ids=["xla_scan", "scan_kernels"])
def test_the_mixers_kernels_are_the_xla_forms(remat, state, scan, monkeypatch):
    """The state-space mixer alone on XLA's bodies and on the interpreted
    kernels: the convolution (``ops/ssm_conv.py``), the epilogue
    (``ops/ssm_gate_norm.py``: the skip, the gate, the grouped norm) and, at a
    state of 128, the scan (``ops/ssd.py``, called without its skip); 4 heads
    of 64 and 2 groups put z, x, B and C and a group's 128 channels on whole
    lane tiles, and a state of 64 leaves the scan its XLA form, so that the
    convolution and the epilogue are all that differs. The same output and
    the same gradient of every leaf and of the input, with the mixer replayed
    under ``jax.checkpoint`` (what per-half remat does to it) and without.
    200 tokens: ONE ragged block of rows."""
    from shuffle_exchange_tpu.ops import ssd
    from shuffle_exchange_tpu.ops import ssm_conv as sc
    from shuffle_exchange_tpu.ops import ssm_gate_norm as gn
    from tests.test_ssm_conv import calls

    hf = dict(HF, num_hidden_layers=5, mamba_num_heads=4, mamba_head_dim=64,
              ssm_state_size=state)
    model = Transformer(config_from_hf(hf))
    params = driver.initial_params(model, 9, BIAS_STD)
    lw = jax.tree.map(lambda a: a[0, 1], {k: params["layers"]["ssm_moe"][k]
                                          for k in driver._MIXER["ssm"]})
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, 200, 64), jnp.float32)
    push = jax.random.normal(jax.random.PRNGKey(5), x.shape, jnp.float32)

    def answers():
        mixer = lambda lw, x: model._ssm(lw, x, None)
        if remat:
            mixer = jax.checkpoint(mixer)
        # (a new function each time: the route is chosen while tracing)
        out, back = jax.vjp(lambda lw, x: mixer(lw, x), lw, x)
        dlw, dx = back(push)
        return dict(dlw, out=out, x=dx)

    wide = 256 + 2 * 2 * state
    zxbcdt = jnp.zeros((BATCH, 200, 256 + wide + 4))
    routes = lambda: (
        sc.ssm_conv_route(zxbcdt, lw["ssm_conv_w"], 256, (256, 2 * state, 2 * state)),
        gn.ssm_gate_norm_route(zxbcdt[..., :256], 2),
        ssd.ssd_route(jnp.zeros((BATCH, 200, 4, 64)), jnp.zeros((BATCH, 200, 2, state))))
    assert routes() == ("xla",) * 3
    want = answers()
    launches = lambda: [name for name, _ in calls(lambda lw, x: model._ssm(lw, x, None), lw, x)]
    assert launches() == []
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    assert routes() == ("interpret", "interpret", scan)
    got = answers()
    assert launches() == (
        ["ssm_conv_fwd"] * 3 + ["ssd_fwd"] * (scan != "xla") + ["ssm_gate_norm_fwd"])
    worst = gaps(got, want)
    assert len(worst) == len(driver._MIXER["ssm"]) + 2
    assert max(worst.values()) < (1e-5 if scan == "xla" else 2e-4), worst


def test_a_block_without_an_ffn_opens_no_ffn_scope(case):
    lw = jax.tree.map(lambda a: a[0, 0], case["params"]["layers"]["ssm_none"])
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, 16, 64), jnp.float32)
    fn = lambda lw, x: case["model"].layer_apply(lw, x, (None, None), kind=("ssm", "none"))
    out, (aux, stats) = jax.jit(fn)(lw, x)
    assert out.shape == x.shape and float(aux) == 0.0 and stats is None
    text = jax.jit(fn).lower(lw, x).as_text(debug_info=True)
    assert "attn_core/ssm_scan" in text and "attn_norm" in text
    assert not any(scope in text for scope in ("mlp_norm", "/moe", "/mlp"))


def test_attention_among_the_kinds_rotates_nothing(case):
    """Position "none": the program's attention equals the reference's, which
    applies no rotation; moving a query's position without moving what comes
    before it changes nothing but that row's causal reach."""
    lw = jax.tree.map(lambda a: a[0, 0], {k: case["params"]["layers"]["attn_moe"][k]
                                          for k in driver._MIXER["attn"]})
    y = jax.random.normal(jax.random.PRNGKey(7), (1, 12, 64), jnp.float32)
    named = {"a." + driver._MIXER["attn"][k]: driver._relaid((k,), lw[k]) for k in lw}
    got = case["model"]._gqa(lw, y, (None, None), mixer="attn")
    want = ref.attention(named, "a.", y, HF)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
    # no position signal: the last row's output is invariant under a
    # permutation of the rows before it
    perm = jnp.asarray([3, 0, 2, 1] + list(range(4, 12)))
    moved = case["model"]._gqa(lw, y[:, perm], (None, None), mixer="attn")
    np.testing.assert_allclose(moved[:, -1], got[:, -1], rtol=1e-5, atol=1e-6)
    assert case["model"].embed(case["params"], case["ids"][:, :-1])[1] == (None, None)
    # ALiBi's slopes mark the positions: the invariance goes
    sloped = Transformer(dataclasses.replace(case["cfg"], position="alibi"))
    a, b = sloped._gqa(lw, y, (None, None)), sloped._gqa(lw, y[:, perm], (None, None))
    assert float(jnp.max(jnp.abs(a[:, -1] - b[:, -1]))) > 1e-4


def test_the_trainer_runs_the_scans_kernels_where_the_heads_fill_lane_tiles(monkeypatch):
    """Mamba heads of 64 over a state of 128 (the published sizes): under ``SXT_FUSED_INTERPRET=1`` the train step's scan is the Pallas
    kernels (interpreted), inside the unrolled period, the half-block's remat
    (the forward that keeps nothing, then the one that keeps the chunks'
    states), ZeRO-3 and the 8-device mesh's ``shard_kernel``; its first loss
    and first gradient are the XLA form's. 200 tokens: a ragged second chunk."""
    hf = dict(HF, num_hidden_layers=5, mamba_num_heads=4, mamba_head_dim=64,
              ssm_state_size=128)
    ids = np.random.default_rng(11).integers(0, HF["vocab_size"], (8, 201)).astype(np.int32)

    def first_step():
        model = Transformer(config_from_hf(hf))
        engine = sxt.initialize(
            model=model, params=driver.initial_params(model, 7, BIAS_STD),
            config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                    "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
                    "activation_checkpointing": {"enabled": True, "policy": "full"},
                    "zero_optimization": {"stage": 3}}, seed=0)[0]
        text = engine.compile({"input_ids": ids}).as_text()
        scopes = {op.scope for op in trace.registered_ops("train_step").values()}
        loss = float(engine.train_batch({"input_ids": ids}))
        return loss, driver.first_moment(engine.state.opt_state), text, scopes

    xla_loss, xla_moment, xla_text, xla_scopes = first_step()
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    loss, moment, text, scopes = first_step()
    assert "ssd_bwd" in text and "ssd_fwd_keep" in text and "ssd_bwd" not in xla_text
    # the convolution's two kernels too (``ops/ssm_conv.py``) and the
    # epilogue's (``ops/ssm_gate_norm.py``), under the mixer's scopes
    # ``ssm_conv`` and ``ssm_out_norm``, the backward's in the backward pass
    for own, kernel in (("ssm_conv", "ssm_conv_fwd"), ("ssm_conv", "ssm_conv_bwd"),
                        ("ssm_out_norm", "ssm_gate_norm_fwd"),
                        ("ssm_out_norm", "ssm_gate_norm_bwd")):
        under = [s for s in scopes if f"/{own}/" in s and f"/{kernel}/" in s]
        assert under and not any(kernel in s for s in xla_scopes), kernel
    # (behind ``optimize_remat`` one interpreted op of a backward launch
    # carries the launch's own name and no path: read those under the scope)
    for own, fwd, bwd in (("/ssm_conv/", "ssm_conv_fwd", "ssm_conv_bwd"),
                          ("/ssm_out_norm/", "ssm_gate_norm_fwd", "ssm_gate_norm_bwd")):
        assert any("rematted_computation" in s for s in scopes if f"/{fwd}/" in s)
        assert all("transpose(" in s for s in scopes if own in s and f"/{bwd}/" in s)
    assert abs(loss - xla_loss) < 1e-5
    # (a leaf no token reached has a zero gradient in both)
    worst = {k: v for k, v in gaps(moment, xla_moment).items() if v == v}
    assert len(worst) > 20 and max(worst.values()) < 1e-3, worst
    assert all(not np.any(np.asarray(moment[k])) for k in set(moment) - set(worst))


# -- refusals ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
@pytest.mark.parametrize("pattern", [(("ssm", "moe"),), (("attn", "none"), ("attn", "moe"))],
                         ids=["mixer_ssm", "ffn_none"])
def test_the_inference_engines_refuse_the_kinds_by_name(case, engine, pattern):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    model = Transformer(dataclasses.replace(case["cfg"], layer_pattern=pattern,
                                            n_layers=len(pattern)))
    with pytest.raises(NotImplementedError, match="mixer 'ssm'.*ffn 'none'"):
        cls(model, {})
    with pytest.raises(NotImplementedError, match="ssm_conv_kernel - 1 rows"):
        cls(case["model"], case["params"])


def test_a_sequence_parallel_mesh_refuses_the_scan_by_name(case, monkeypatch):
    monkeypatch.setattr(Transformer, "_sp_mesh", staticmethod(lambda: (2, None)))
    lw = jax.tree.map(lambda a: a[0, 0], case["params"]["layers"]["ssm_none"])
    with pytest.raises(NotImplementedError, match="mixer 'ssm'.*sequence-parallel"):
        case["model"]._ssm(lw, jnp.zeros((1, 8, 64)), None)


def test_checkpoint_import_is_not_written():
    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    with pytest.raises(NotImplementedError, match="several layer kinds"):
        params_from_state_dict({}, config_from_hf(HF), "nemotronh")


def test_the_two_reference_copies_agree():
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_nemotron3.py") == body(
        "shuffle_exchange_tpu/models/reference_nemotron3.py")
    assert "shuffle_exchange_tpu" not in "".join(
        line for line in body("chipbench/reference_nemotron3.py").splitlines()
        if line.startswith(("import", "from")))


# -- the reference's state-space layer against an independent implementation ------------

@pytest.mark.parametrize("groups", [1, 2], ids=lambda g: f"G{g}")
def test_the_references_mamba_layer_is_transformers_mamba2_mixer(groups, monkeypatch):
    """No modelling code of ``nemotron_h`` is here, but transformers' Mamba-2
    is (``Mamba2Mixer.torch_forward``, its plain path): the same projection
    split, convolution with bias and SiLU, unclamped softplus step, scan with
    B and C shared by consecutive heads of a group, skip, gate BEFORE the norm,
    and the same leaf names. Its gated norm is one over the whole inner width,
    so at 2 groups the reference is read with one group in the norm (the
    grouped norm is the family's own: ``chipbench/NEMOTRON3.md``)."""
    torch = pytest.importorskip("torch")
    mamba2 = pytest.importorskip("transformers.models.mamba2.modeling_mamba2")
    from transformers.models.mamba2.configuration_mamba2 import Mamba2Config

    H, P, N, D, K = 8, 8, 16, 48, 4
    config = Mamba2Config(num_heads=H, head_dim=P, hidden_size=D, state_size=N, n_groups=groups,
                          conv_kernel=K, expand=H * P / D, use_conv_bias=True, use_bias=False,
                          chunk_size=16, time_step_limit=(0.0, float("inf")),
                          layer_norm_epsilon=1e-5, hidden_act="silu")
    torch.manual_seed(groups)
    mixer = mamba2.Mamba2Mixer(config, 0).float()
    with torch.no_grad():
        mixer.D.copy_(torch.rand(H) + 0.5)
        mixer.norm.weight.copy_(torch.rand(H * P) + 0.5)
        mixer.conv1d.bias.copy_(torch.randn(H * P + 2 * groups * N) * 0.1)
        x = torch.randn(2, 40, D)
        want = mixer.torch_forward(x).numpy()
    weights = {"a." + k: jnp.asarray(v.detach().numpy()) for k, v in mixer.state_dict().items()}
    assert set(weights) == {"a." + name for name in driver._MIXER["ssm"].values()}
    src = {"mamba_num_heads": H, "mamba_head_dim": P, "n_groups": groups, "ssm_state_size": N,
           "layer_norm_epsilon": 1e-5}
    if groups > 1:
        plain = ref.gated_norm
        monkeypatch.setattr(ref, "gated_norm",
                            lambda o, z, gain, g, eps: plain(o, z, gain, 1, eps))
    got = np.asarray(ref.mamba(weights, "a.", jnp.asarray(x.numpy()), src))
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
