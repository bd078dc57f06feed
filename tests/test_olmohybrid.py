"""Olmo Hybrid (``model_type: olmo_hybrid``) through the normal path against
the plain reference (``models/reference_olmohybrid.py``), at a tiny size on
the CPU: ONE period of [gdn, gdn, gdn, full attention] (the cell's depth),
hidden 64, DeltaNet heads of 12 / 24, attention heads of 16, FFN 96,
vocabulary 256, 64 positions. The weights are drawn by ``Transformer.init``
(with the gains redrawn, as the cell's driver does) and reach the reference
through the driver's own mapping (``chipbench/drivers/train_steps_gdn.py``),
so that mapping is part of what is compared.

Tolerances, float32 against float32. The two sides compute the same equations;
they differ in the order of float32 additions and, for the DeltaNet layers, in
the derivation (chunked matrix form here, one token at a time there). Loss
1e-5; gradients 3e-3 of each leaf's norm at ONE period (measured 1e-4 to 5e-4;
the reference itself in float32 sits 4e-4 from its float64 self there). At TWO
periods of these random weights float32 rounding alone moves the reference's
own gradient by 0.7-1.5% of a leaf's norm (the output norm of a DeltaNet layer
whose state forgets within a token divides by |k . q|, which is near 0 for
some tokens): a property of the stack, not of either side, and the reason the
tight comparison is made at the cell's depth. The same amplification makes
bf16 compute at this size (a hundred tokens a leaf, 2 heads) a test of the
loss alone: the loss sits within 0.03 of the float32 reference's (measured
0.004) while a leaf's gradient is off by 0.2 to 1.6 of its norm (median 0.5),
which the test only bounds (median under 1, none over 4). What bf16 reads at
the published widths is the chip's to say (``chipbench/OLMOHYBRID.md``).
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
from chipbench.drivers import train_steps_gdn as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_olmohybrid as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402
from shuffle_exchange_tpu.ops import gated_delta as gd  # noqa: E402

HF = {"model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 4, "num_attention_heads": 4,
      "num_key_value_heads": 4, "hidden_act": "silu", "max_position_embeddings": 256,
      "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
      "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
      "linear_num_key_heads": 2, "linear_num_value_heads": 2,
      "linear_key_head_dim": 12, "linear_value_head_dim": 24,
      "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
      "rope_parameters": {"rope_theta": None}}
SEQ, BATCH = 64, 2
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
       "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
       "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
       "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
       "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
       "linear_num_key_heads": 30, "linear_num_value_heads": 30,
       "linear_key_head_dim": 96, "linear_value_head_dim": 192,
       "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
       "rope_parameters": {"rope_theta": None}}


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


# -- the importer ------------------------------------------------------------

def test_the_catalog_rows_config_is_the_one_written_here():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(CATALOG) if '"Olmo-Hybrid-7B"' in line)
    assert row["config"] == ROW


EXPECT = {"vocab_size": ("vocab_size", 100352), "hidden_size": ("d_model", 3840),
          "intermediate_size": ("ff_dim", 11008), "num_hidden_layers": ("n_layers", 32),
          "num_attention_heads": ("n_heads", 30), "num_key_value_heads": ("kv_heads", 30),
          "hidden_act": ("activation", "swiglu"),
          "max_position_embeddings": ("max_seq_len", 65536),
          "attention_bias": ("attn_qkv_bias", False), "rms_norm_eps": ("norm_eps", 1e-6),
          "tie_word_embeddings": ("tie_embeddings", False),
          "layer_types": ("pattern", (("gdn", "mlp"),) * 3 + (("attn", "mlp"),)),
          "linear_num_key_heads": ("gdn_key_heads", 30),
          "linear_num_value_heads": ("gdn_value_heads", 30),
          "linear_key_head_dim": ("gdn_key_dim", 96),
          "linear_value_head_dim": ("gdn_value_dim", 192),
          "linear_conv_kernel_dim": ("gdn_conv_kernel", 4),
          "linear_allow_neg_eigval": ("gdn_beta_scale", 2.0),
          "rope_parameters": ("position", "none"), "model_type": ("norm_order", "output")}


@pytest.mark.parametrize("key", sorted(ROW))
def test_config_from_hf_reads_the_rows_key(key):
    field, want = EXPECT[key]
    assert getattr(config_from_hf(ROW), field) == want


def test_config_from_hf_what_the_family_settles():
    cfg = config_from_hf(ROW)
    assert (cfg.head_dim, cfg.qk_norm, cfg.norm, cfg.n_experts) == (128, True, "rmsnorm", 0)
    assert cfg.several_kinds and cfg.recurrent and cfg.gdn_layers == 24
    assert cfg.routed_layers == 0
    assert config_from_hf(dict(ROW, linear_allow_neg_eigval=False)).gdn_beta_scale == 1.0
    # a cut in depth reads the first entries of the published list
    cut = config_from_hf(dict(ROW, num_hidden_layers=4))
    assert cut.n_layers == 4 and cut.pattern == cfg.pattern and cut.gdn_layers == 3


@pytest.mark.parametrize("change, named", [
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_parameters": {"rope_theta": 500000}}, "rope_parameters"),
    ({"rope_parameters": {"rope_theta": None, "rope_type": "yarn"}}, "rope_parameters"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"num_experts": 8}, "num_experts"),
    ({"layer_types": ["sliding_attention"] * 32}, "layer_types"),
    ({"layer_types": ["linear_attention"] * 32}, "both kinds"),
    ({"num_hidden_layers": 33}, "num_hidden_layers"),
    ({"linear_num_value_heads": 45}, "linear_num_value_heads"),
])
def test_config_from_hf_refuses_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        config_from_hf(dict(ROW, **change))


def test_the_cells_file_counts_the_issues_parameters():
    from chipbench import arith_olmohybrid, harness

    src = harness.load_cell("olmohybrid-zero3-x4")["config"]
    cfg = config_from_hf(src)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # plus the plain-gain norms' unused bias leaves (two a layer, one final)
    assert n == 928_862_196 + (2 * 4 + 1) * 3840
    assert arith_olmohybrid.parameters(src) == 928_862_196
    assert arith_olmohybrid.parameters(src, 32, 100352) == 7_430_870_688
    layers = shapes["layers"]
    assert layers["gdn_mlp"]["w_qkvz"].shape == (1, 3, 3840, 2 * 2880 + 2 * 5760)
    assert layers["gdn_mlp"]["conv_w"].shape == (1, 3, 4, 2 * 2880 + 5760)
    assert layers["gdn_mlp"]["gdn_norm_w"].shape == (1, 3, 192)
    assert layers["attn_mlp"]["q_norm_w"].shape == (1, 1, 3840)
    assert layers["attn_mlp"]["w_up"].shape == (1, 1, 3840, 11008)
    assert {k: tuple(v.shape) for k, v in jax.eval_shape(
        lambda p: driver.to_source_names(p, src), shapes).items()} == ref.weight_shapes(src)


def test_the_two_copies_of_the_reference_agree():
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_olmohybrid.py") == body(
        "shuffle_exchange_tpu/models/reference_olmohybrid.py")


# -- the program against the reference ---------------------------------------

def test_first_loss_and_counter(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    # no expert anywhere: no routing stats; the rule's chunks are counted
    assert not any(k.startswith("moe_") for k in stats)
    assert int(stats["gdn_scan_chunks"]) == 1 * BATCH * 3
    assert case["model"].update_buffers({}, {"x": 1}, stats) == {"x": 1}


def test_logits(case):
    logits = jax.jit(case["model"].apply)(case["params"], case["ids"][:, :-1])
    want = np.asarray(case["ref"]["logits"])
    assert np.max(np.abs(np.asarray(logits) - want)) < 1e-4 * np.max(np.abs(want))


def test_every_gradient_leaf(case):
    got = driver.flat_tree(jax.jit(jax.grad(case["model"].loss))(
        case["params"], {"input_ids": case["ids"]}))
    unused = {f"layers/{kind}/{b}" for kind in ("gdn_mlp", "attn_mlp")
              for b in ("ln1_b", "ln2_b")} | {"ln_f_b"}
    assert set(got) == set(case["ref_grads"]) | unused
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 3e-3, worst


def test_bf16_compute_sits_in_its_stated_band(case):
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), case["params"])
    loss, grads = jax.jit(jax.value_and_grad(case["model"].loss))(
        low, {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 0.03
    got = {k: np.asarray(v, np.float32) for k, v in driver.flat_tree(grads).items()}
    worst = gaps(got, case["ref_grads"])
    assert np.median(list(worst.values())) < 1.0 and max(worst.values()) < 4.0, worst


def test_the_mapping_to_the_sources_names_is_its_own_inverse(case):
    back = driver.from_source_names(case["weights"], HF)
    flat = driver.flat_tree(case["params"])
    assert set(back) <= set(flat)
    for leaf, x in back.items():
        np.testing.assert_array_equal(x, np.asarray(flat[leaf]), err_msg=leaf)


@pytest.mark.parametrize("wrong", ["beta_1x", "norm_on_input", "per_head_qk_norm",
                                   "rope_on", "q_scale_dv", "no_decay", "no_l2norm",
                                   "norm_after_gate"])
def test_a_wrong_model_is_off(case, wrong, monkeypatch):
    """Each wrong model of the band's list moves the float32 loss by 3e-4 or
    more, or some leaf's gradient by 10% or more."""
    from chipbench import olmohybrid_band as band
    from chipbench import reference_olmohybrid as bench_ref

    fns, keys, _ = band.variants(jnp.float32)[wrong]
    for name, fn in fns.items():
        monkeypatch.setattr(bench_ref, name, fn)
    src = {**HF, **keys}
    loss = jax.jit(lambda w, i: bench_ref.loss(w, src, i))(case["weights"], case["ids"])
    grads = driver.from_source_names(
        jax.jit(lambda w, i: bench_ref.grads(w, src, i))(case["weights"], case["ids"]), HF)
    off = max(gaps(grads, case["ref_grads"]).values())
    assert abs(float(loss) - float(case["ref"]["loss"])) > 3e-4 or off > 0.1


# -- the block's norm order --------------------------------------------------

@pytest.mark.parametrize("kind", ["gdn_mlp", "attn_mlp"])
def test_the_block_norms_each_sublayers_output(case, kind):
    """``layer_apply`` against a hand-written two-line block over the same
    mixer and feed-forward functions."""
    from shuffle_exchange_tpu.models.transformer import _norm

    model, cfg = case["model"], case["cfg"]
    lw = jax.tree.map(lambda x: x[0, 0], case["params"]["layers"][kind])
    h = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, cfg.d_model), jnp.float32)
    mix = model._gdn if kind == "gdn_mlp" else model._gqa
    rope = (None, None)
    norm = lambda x, w: _norm(x, w, 0, "rmsnorm", eps=cfg.norm_eps)
    h1 = h + norm(mix(lw, h, rope), lw["ln1_w"])
    want = h1 + norm((jax.nn.silu(h1 @ lw["w_gate"]) * (h1 @ lw["w_up"])) @ lw["w_down"],
                     lw["ln2_w"])
    for halves in (False, True):
        got, (aux, stats) = model.layer_apply(lw, h, rope, kind=tuple(kind.split("_")),
                                              remat_halves=halves)
        assert stats is None and float(aux) == 0.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    pre = Transformer(dataclasses.replace(cfg, norm_order="input")).layer_apply(
        lw, h, rope, kind=tuple(kind.split("_")))[0]
    assert float(jnp.max(jnp.abs(pre - want))) > 0.1


def test_a_one_kind_model_takes_the_output_order():
    """The stack cut to its attention layers (the Olmo 2 / 3 block without
    DeltaNet layers) is ONE kind of layer: it norms its sublayers' output and
    the whole q and k projections as the hybrid's attention layers do. Loss
    and every gradient against the reference on the same two layers, and not
    what the input order reads; a bad value is refused where the model is
    made."""
    # (the family's importer reads a stack of both kinds only)
    hf = dict(HF, num_hidden_layers=2, layer_types=["full_attention"] * 2)
    cfg = dataclasses.replace(config_from_hf(HF), layer_pattern=(), n_layers=2)
    assert not cfg.several_kinds and (cfg.norm_order, cfg.qk_norm) == ("output", True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(5))
    assert params["layers"]["wq"].shape[0] == 2             # flat, [L, ...]
    # the driver's mapping reads a kind's leaves [periods, layers a period, ...]
    by_kind = lambda tree: dict(tree, layers={"attn_mlp": jax.tree.map(
        lambda a: a[:, None], tree["layers"])})
    weights = driver.to_source_names(by_kind(params), hf)
    ids = np.random.default_rng(3).integers(0, 256, (BATCH, SEQ + 1)).astype(np.int32)
    loss, grad = jax.jit(jax.value_and_grad(model.loss))(params, {"input_ids": ids})
    want = float(jax.jit(lambda w, i: ref.loss_parts(w, hf, i))(weights, ids)["loss"])
    assert abs(float(loss) - want) < 1e-5
    want_grad = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, hf, i))(weights, ids), hf)
    assert {"layers/attn_mlp/q_norm_w", "layers/attn_mlp/ln2_w", "embed"} <= set(want_grad)
    worst = gaps(driver.flat_tree(by_kind(grad)), want_grad)
    assert max(worst.values()) < 3e-3, worst
    pre = Transformer(dataclasses.replace(cfg, norm_order="input"))
    assert abs(float(jax.jit(pre.loss)(params, {"input_ids": ids})) - want) > 1e-4
    with pytest.raises(ValueError, match="norm_order"):
        Transformer(dataclasses.replace(config_from_hf(HF), norm_order="between"))
    with pytest.raises(ValueError, match="one of them.*post_ln and norm_order='output'"):
        Transformer(dataclasses.replace(cfg, post_ln=True))


def test_the_whole_projection_qk_norm_among_several_kinds(case):
    """``_gqa`` with ``qk_norm`` True: the statistic runs over ALL heads'
    channels (scaling one head's q moves every head's), the gains are a
    column's own, and the window kind takes it too."""
    model, cfg = case["model"], case["cfg"]
    lw = jax.tree.map(lambda x: x[0, 0], case["params"]["layers"]["attn_mlp"])
    assert lw["q_norm_w"].shape == (cfg.n_heads * cfg.head_dim,)
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 16, cfg.d_model), jnp.float32)
    out = model._gqa(lw, y, (None, None))
    want = ref.attention(
        {"a.q_proj.weight": lw["wq"].T, "a.k_proj.weight": lw["wk"].T,
         "a.v_proj.weight": lw["wv"].T, "a.o_proj.weight": lw["wo"].T,
         "a.q_norm.weight": lw["q_norm_w"], "a.k_norm.weight": lw["k_norm_w"]},
        "a.", y, HF)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-5)
    # the window kind norms the same way: over a window that covers the
    # sequence it is the full layer
    windowed = Transformer(dataclasses.replace(cfg, swa_window=16, position="rope"))
    rope = windowed.rope_for("swa", 16)
    over_window = windowed._gqa(lw, y, rope, mixer="swa")
    np.testing.assert_allclose(over_window, windowed._gqa(lw, y, rope), rtol=1e-5, atol=1e-6)
    bare = Transformer(dataclasses.replace(windowed.config, qk_norm=False))
    assert float(jnp.max(jnp.abs(bare._gqa(lw, y, rope, mixer="swa") - over_window))) > 1e-3


# -- the rule at 96 / 192 with beta in (0, 2) --------------------------------

def rule_case(B=1, T=160, H=6, dk=96, dv=192, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32)
    q = (gd.l2norm(n(ks[0], B, T, H, dk)) * dk ** -0.5).astype(dtype)
    k = gd.l2norm(n(ks[1], B, T, H, dk)).astype(dtype)
    v = jax.nn.silu(n(ks[2], B, T, H, dv)).astype(dtype)
    beta = 2.0 * jax.nn.sigmoid(n(ks[3], B, T, H))
    g = -jax.nn.softplus(n(ks[4], B, T, H)) / 20.0
    return (q, k, v, g, beta), n(ks[5], B, T, H, dv)


def rule_answers(rule, args, ct):
    o, back = jax.vjp(lambda *a: rule(*a).astype(jnp.float32), *args)
    return (o,) + back(ct)


@pytest.mark.parametrize("route", ["xla", "interpret_padded"])
def test_the_chunked_rule_at_96_192_is_the_recurrence(route, monkeypatch):
    """beta in (0, 2) (eigenvalues of I - beta k k^T down to -1), 160 tokens
    (a ragged third chunk): output and all five gradients, on the XLA body
    and on the kernels over zero-padded lanes (interpreted)."""
    if route != "xla":
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    args, ct = rule_case()
    assert gd.kernel_route(*args[:3]) == route
    assert float(args[4].max()) > 1.5
    got = rule_answers(gd.gated_delta_chunked, args, ct)
    want = rule_answers(gd.gated_delta_recurrent, args, ct)
    for name, a, b in zip("o dq dk dv dg dbeta".split(), got, want):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-5, name


@pytest.mark.parametrize("dk, dv, H, route", [
    (96, 192, 30, "interpret_padded"), (128, 128, 32, "interpret"),
    (16, 16, 4, "xla"), (64, 128, 4, "xla"), (96, 80, 4, "xla")])
def test_kernel_route_pads_only_what_pads_cheaply(dk, dv, H, route, monkeypatch):
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    shape = lambda d: jax.ShapeDtypeStruct((2, 128, H, d), jnp.bfloat16)
    assert gd.kernel_route(shape(dk), shape(dk), shape(dv)) == route
    assert gd.kernel_route(shape(dk), shape(dk), shape(dv), chunk=32) == "xla"
    monkeypatch.delenv("SXT_FUSED_INTERPRET")
    assert gd.kernel_route(shape(dk), shape(dk), shape(dv)) == "xla"   # no TPU here
    assert next(n for n in gd._HEAD_GROUPS if 30 % n == 0) == 6
    assert next(n for n in gd._HEAD_GROUPS if 32 % n == 0) == 8


# -- the trainer, one device and ZeRO-3 over fsdp 4 ---------------------------

def first_step(hf, ids, mesh=None, params_seed=7):
    model = Transformer(config_from_hf(hf))
    config = {"train_batch_size": len(ids), "steps_per_print": 10 ** 9,
              "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
              "activation_checkpointing": {"enabled": True, "policy": "full"},
              "zero_optimization": {"stage": 3}}
    if mesh:
        config["mesh"] = mesh
    engine = sxt.initialize(model=model, params=driver.initial_params(model, params_seed),
                            config=config, seed=0)[0]
    loss = float(engine.train_batch({"input_ids": ids}))
    moment = {k: np.asarray(v) for k, v in
              driver.first_moment(engine.state.opt_state).items()}
    return loss, moment, engine


def test_the_trainer_through_initialize(case):
    """``sxt.initialize(...).train_batch`` in float32: the first loss, the
    counter it hands out, and the first gradient out of Adam's moment."""
    ids = np.random.default_rng(9).integers(0, 256, (8, SEQ + 1)).astype(np.int32)
    model = Transformer(case["cfg"])
    weights = driver.to_source_names(driver.initial_params(model, 7), HF)
    want = float(jax.jit(lambda w, i: ref.loss(w, HF, i))(weights, ids))
    want_grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(weights, ids), HF)
    loss, moment, engine = first_step(HF, ids)
    assert abs(loss - want) < 1e-5
    assert int(engine.last_step_stats()["gdn_scan_chunks"]) > 0
    worst = gaps({k: 10.0 * v for k, v in moment.items()}, want_grads)
    # (these 8 rows read 1e-2 on dt_bias, 2e-3 elsewhere: float32 rounding
    # through the DeltaNet output norms, as the module's docstring says; a
    # wrong model reads 0.1 or more)
    assert max(worst.values()) < 2e-2, worst


def test_zero3_over_fsdp_4_is_the_one_device_step():
    """On the 8-device CPU mesh: ``fsdp: 4`` (x data 2) ZeRO-3 over the period
    scan's leaves stacked by kind, the rule and the prologue per device
    through ``shard_kernel``, against the same step with every device's state
    whole (mesh data 8, ZeRO off the fsdp axis): loss and every leaf of the
    first gradient."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    ids = np.random.default_rng(11).integers(0, 256, (8, SEQ + 1)).astype(np.int32)
    loss, moment, engine = first_step(HF, ids, mesh={"fsdp": 4, "data": 2})
    assert engine.topology.axis_sizes["fsdp"] == 4 and engine.zero_stage == 3
    model = Transformer(config_from_hf(HF))
    params = driver.initial_params(model, 7)
    want_loss, want = jax.jit(jax.value_and_grad(model.loss))(params, {"input_ids": ids})
    want = {k: np.asarray(v) for k, v in driver.flat_tree(want).items()}
    assert abs(loss - float(want_loss)) < 1e-5
    worst = {k: v for k, v in gaps({k: 10.0 * v for k, v in moment.items()},
                                   {k: v for k, v in want.items() if np.any(v)}).items()}
    assert len(worst) > 20 and max(worst.values()) < 1e-3, worst


def test_the_trainer_runs_the_rules_and_the_prologues_kernels_at_96_192(monkeypatch):
    """DeltaNet heads of 96 / 192 (the published widths): under
    ``SXT_FUSED_INTERPRET=1`` the train step's rule is the Pallas kernels over
    padded lanes and its prologue the two Pallas kernels on a group of two
    key heads (1152 channels = 9 lane tiles; both interpreted) inside the
    period scan, the half-block's remat, ZeRO-3 over fsdp 4 and
    ``shard_kernel``. Its first loss and first gradient are the XLA form's.
    80 tokens: a ragged second chunk of the rule, a padded block of the
    prologue's rows."""
    hf = dict(HF, linear_num_key_heads=2, linear_num_value_heads=2,
              linear_key_head_dim=96, linear_value_head_dim=192)
    ids = np.random.default_rng(11).integers(0, 256, (8, 81)).astype(np.int32)

    def step():
        loss, moment, engine = first_step(hf, ids, mesh={"fsdp": 4, "data": 2})
        return loss, moment, engine.compile({"input_ids": ids}).as_text()

    xla_loss, xla_moment, xla_text = step()
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    loss, moment, text = step()
    for kernel in ("gdn_rule_bwd", "gdn_prologue_fwd", "gdn_prologue_bwd"):
        assert kernel in text and kernel not in xla_text, kernel
    assert abs(loss - xla_loss) < 1e-5
    worst = {k: v for k, v in gaps(moment, xla_moment).items() if v == v}
    assert len(worst) > 20 and max(worst.values()) < 1e-3, worst


# -- serving -----------------------------------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_family_by_name(engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    model = Transformer(config_from_hf(HF))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="Olmo Hybrid"):
        cls(model, params)
