"""Qwen3-Next (``model_type: qwen3_next``) through the normal path against the
plain reference (``models/reference_qwen3next.py``), at a tiny size on the
CPU: two periods of [gdn, gdn, gdn, full attention], hidden 64, 8 experts of
which 4 are held here, top 3, a shared expert, vocabulary 256, 64 positions.
The weights are drawn by ``Transformer.init`` (with the gains redrawn, as the
cell's driver does) and reach the reference through the driver's own mapping
(``chipbench/drivers/train_steps_hybrid.py``), so that mapping is part of what
is compared.

Tolerances, float32 against float32. The two sides compute the same equations;
they differ in the order of float32 additions and, for the DeltaNet layers, in
the derivation (chunked matrix form here, one token at a time there). Loss
1e-5; routing exact; gradients 5e-3 of each leaf's norm (measured 1e-4 for the
attention layers' leaves, 1.4e-3 for the DeltaNet layers'). Each wrong model of
the band's list moves the loss by 1e-3 or more, or some leaf's gradient by 5%
or more; the test takes 3e-4 and 2e-2 as "off".
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
from chipbench.drivers import train_steps_hybrid as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_qwen3next as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402

HF = {"model_type": "qwen3_next", "architectures": ["Qwen3NextForCausalLM"],
      "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
      "num_key_value_heads": 2, "num_hidden_layers": 8,
      "full_attention_interval": 4, "partial_rotary_factor": 0.25,
      "rope_theta": 10000000, "rms_norm_eps": 1e-6,
      "linear_num_key_heads": 2, "linear_num_value_heads": 4,
      "linear_key_head_dim": 16, "linear_value_head_dim": 16,
      "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_held": 4,
      "expert_first": 0, "expert_buffer_factor": 2.0, "num_experts_per_tok": 3,
      "norm_topk_prob": True, "moe_intermediate_size": 32,
      "shared_expert_intermediate_size": 32, "intermediate_size": 128,
      "vocab_size": 256, "max_position_embeddings": 128,
      "tie_word_embeddings": False, "router_aux_loss_coef": 0.001,
      "hidden_act": "silu", "decoder_sparse_step": 1, "mlp_only_layers": []}
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


def test_config_from_hf_on_the_rows_own_keys():
    """The catalog row's keys, as the cell's configuration file has them."""
    from chipbench import harness

    src = harness.load_cell("qwen3next-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("gdn", "moe"),) * 3 + (("gated_attn", "moe"),)
    assert (cfg.head_dim, cfg.rotary_dims, cfg.n_heads, cfg.kv_heads) == (256, 64, 16, 2)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_conv_kernel) == (16, 32, 128, 128, 4)
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.moe_shared_expert_ff) == (512, 32, 10, 512, 512)
    assert cfg.norm == "rmsnorm_zc" and cfg.moe_norm_topk and cfg.recurrent
    assert (cfg.n_layers, cfg.vocab_size, cfg.d_model) == (4, 18992, 2048)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's table, plus the final norm's unused bias leaf
    assert n == 547_873_856 + 77_793_280 + 2048
    layers = shapes["layers"]
    assert layers["gdn_moe"]["w_qkvz"].shape == (1, 3, 2048, 12288)
    assert layers["gated_attn_moe"]["wq"].shape == (1, 1, 2048, 8192)
    assert layers["gdn_moe"]["moe_w_up"].shape == (1, 3, 32, 2048, 512)


def test_a_share_states_its_own_buffer():
    """``num_experts_held`` without ``expert_buffer_factor`` is refused: a
    share's configuration says how many rows it leaves room for."""
    cut = {k: v for k, v in HF.items() if k != "expert_buffer_factor"}
    with pytest.raises(ValueError, match="expert_buffer_factor"):
        config_from_hf(cut)
    whole = config_from_hf({k: v for k, v in cut.items() if k != "num_experts_held"})
    assert whole.experts_held == whole.n_experts == 8
    assert config_from_hf(HF).moe_held_rows_factor == 2.0


def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    np.testing.assert_array_equal(stats["moe_expert_tokens"],
                                  case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    assert int(np.asarray(stats["moe_overflow_rows"]).sum()) == 0
    assert stats["moe_expert_tokens"].shape == (8, 8)


def test_logits(case):
    logits = jax.jit(case["model"].apply)(case["params"], case["ids"][:, :-1])
    want = np.asarray(case["ref"]["logits"])
    assert np.max(np.abs(np.asarray(logits) - want)) < 1e-4 * np.max(np.abs(want))


def test_every_gradient_leaf(case):
    got = driver.flat_tree(jax.jit(jax.grad(case["model"].loss))(
        case["params"], {"input_ids": case["ids"]}))
    assert set(got) == set(case["ref_grads"]) | {"ln_f_b"}
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 5e-3, worst


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
        model=model, params=jax.tree.map(jnp.array, case["params"]),
        config={"train_batch_size": rows, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "zero_optimization": {"stage": 3}}, seed=0)[0]
    assert model.config.remat and model.config.remat_policy == "full"
    loss = engine.train_batch({"input_ids": ids})
    assert abs(float(loss) - float(want["loss"])) < 1e-5
    stats = engine.last_step_stats()
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], want["held_rows"])
    moment = driver.first_moment(engine.state.opt_state)
    worst = gaps({k: 10.0 * np.asarray(v) for k, v in moment.items()}, want_grads)
    assert max(worst.values()) < 5e-3, worst


def test_the_trainer_runs_the_rules_kernels_where_the_heads_are_lane_wide(monkeypatch):
    """DeltaNet heads of 128 (the published width): under
    ``SXT_FUSED_INTERPRET=1`` the train step's prologue and rule are the
    Pallas kernels (interpreted), inside the period scan, the half-block's
    remat, ZeRO-3 and the 8-device mesh's ``shard_kernel``; its first loss
    and first gradient are the XLA form's. 80 tokens: a ragged second chunk
    of the rule, a padded block of the prologue's rows."""
    hf = dict(HF, num_hidden_layers=4, linear_num_key_heads=1,
              linear_num_value_heads=2, linear_key_head_dim=128,
              linear_value_head_dim=128)
    ids = np.random.default_rng(11).integers(0, 256, (8, 81)).astype(np.int32)

    def first_step():
        model = Transformer(config_from_hf(hf))
        engine = sxt.initialize(
            model=model, params=driver.initial_params(model, 7),
            config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                    "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
                    "activation_checkpointing": {"enabled": True, "policy": "full"},
                    "zero_optimization": {"stage": 3}}, seed=0)[0]
        text = engine.compile({"input_ids": ids}).as_text()
        loss = float(engine.train_batch({"input_ids": ids}))
        return loss, driver.first_moment(engine.state.opt_state), text

    xla_loss, xla_moment, xla_text = first_step()
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    loss, moment, text = first_step()
    assert "gdn_rule_bwd" in text and "gdn_rule_bwd" not in xla_text
    assert "gdn_prologue_bwd" in text and "gdn_prologue_bwd" not in xla_text
    assert abs(loss - xla_loss) < 1e-5
    # (a leaf no token reached has a zero gradient in both)
    worst = {k: v for k, v in gaps(moment, xla_moment).items() if v == v}
    assert len(worst) > 20 and max(worst.values()) < 1e-3, worst
    assert all(not np.any(np.asarray(moment[k])) for k in set(moment) - set(worst))


def test_the_prologues_kernels_give_the_xla_routes_loss_and_gradients(monkeypatch):
    """The same stack on one device, no remat: ``Transformer.loss`` and every
    gradient leaf with the mixer's prologue and rule as kernels (interpreted)
    against the XLA route. 80 tokens: a ragged second chunk of the rule, a
    padded block of the prologue's rows."""
    hf = dict(HF, num_hidden_layers=4, linear_num_key_heads=1,
              linear_num_value_heads=2, linear_key_head_dim=128,
              linear_value_head_dim=128)
    ids = np.random.default_rng(12).integers(0, 256, (2, 81)).astype(np.int32)
    model = Transformer(config_from_hf(hf))
    params = driver.initial_params(model, 8)

    def first_step():
        # traced anew each time: the route is chosen while tracing
        step = jax.value_and_grad(lambda p: model.loss(p, {"input_ids": ids}))
        loss, grads = jax.jit(step)(params)
        return float(loss), driver.flat_tree(grads), str(jax.make_jaxpr(step)(params))

    xla_loss, xla_grads, xla_text = first_step()
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    loss, grads, text = first_step()
    for kernel in ("gdn_prologue_fwd", "gdn_prologue_bwd", "gdn_rule_fwd_keep",
                   "gdn_rule_bwd"):
        assert kernel in text and kernel not in xla_text, kernel
    assert abs(loss - xla_loss) < 1e-5
    worst = {k: v for k, v in gaps(grads, xla_grads).items() if v == v}
    assert len(worst) > 20 and max(worst.values()) < 1e-3, worst


def _patched(monkeypatch, name, fn):
    monkeypatch.setattr(ref, name, fn)


WRONG = {
    "no_decay": lambda mp: _patched(mp, "log_decay", lambda a, A, dt: jnp.zeros(
        a.shape, jnp.float32)),
    "no_beta": lambda mp: _patched(mp, "write_strength", lambda b: jnp.ones(
        b.shape, jnp.float32)),
    "no_l2norm": lambda mp: _patched(mp, "l2norm", lambda x, eps=1e-6: x),
    "plain_gain": lambda mp: _patched(mp, "rms_norm", lambda x, g, eps: (
        x.astype(jnp.float32) * jax.lax.rsqrt(jnp.mean(
            x.astype(jnp.float32) ** 2, axis=-1, keepdims=True) + eps)
        * g.astype(jnp.float32)).astype(x.dtype)),
    "rope_on_every_dim": lambda mp: None,          # a key of the configuration
    "no_attention_gate": lambda mp: _patched(mp, "output_gate", lambda o, g: o),
    "no_shared_gate": lambda mp: _patched(mp, "shared_gate", lambda w, p, y: 1.0),
    "weights_over_held": lambda mp: None,          # see below
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_model_is_off(case, monkeypatch, name):
    """The reference with one piece of the mathematics left out or changed:
    the system, which has it, is then off by more than the tolerances."""
    cfg = dict(HF)
    WRONG[name](monkeypatch)
    if name == "rope_on_every_dim":
        cfg["partial_rotary_factor"] = 1.0
    if name == "weights_over_held":
        first, end = ref.held_range(HF)
        plain = ref.route

        def route(w, prefix, y, c):
            p, chosen, weight = plain(w, prefix, y, dict(c, norm_topk_prob=False))
            here = (chosen >= first) & (chosen < end)
            held = jnp.where(here, weight, 0.0)
            return p, chosen, held / jnp.maximum(held.sum(-1, keepdims=True), 1e-9)

        monkeypatch.setattr(ref, "route", route)
    loss = float(jax.jit(lambda w, i: ref.loss(w, cfg, i))(case["weights"], case["ids"]))
    grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, cfg, i))(case["weights"], case["ids"]), HF)
    loss_off = abs(loss - float(case["ref"]["loss"]))
    grad_off = max(gaps(grads, case["ref_grads"]).values())
    assert loss_off > 3e-4 or grad_off > 2e-2, (loss_off, grad_off)


def test_the_shares_add_up_to_the_whole_layer():
    """The guide's share test: one layer's routed parts from both halves of
    the experts, with the shared expert counted once, are what the uncut
    reference gives for the layer; so are the system's."""
    whole = dict(HF, num_hidden_layers=4, num_experts_held=8)
    cfg_whole = config_from_hf(whole)
    model = Transformer(cfg_whole)
    params = driver.initial_params(model, 11)
    w = driver.to_source_names(params, whole)
    y = jax.random.normal(jax.random.PRNGKey(2), (BATCH * SEQ, HF["hidden_size"]))
    prefix = "model.layers.0.mlp."
    full, _, _ = ref.experts(w, prefix, y, whole)
    shared = (ref.shared_gate(w, prefix, y)
              * ref.mlp(w, prefix + "shared_expert.", y).astype(jnp.float32))
    parts = [ref.experts(w, prefix, y, dict(whole, num_experts_held=4,
                                            expert_first=first))[0] - shared
             for first in (0, 4)]
    np.testing.assert_allclose(parts[0] + parts[1] + shared, full, atol=2e-6)
    # and the system's two shares, through Transformer._ffn
    lw = jax.tree.map(lambda a: a[0, 0], params["layers"]["gdn_moe"])

    def ffn(cfg, lw):
        return Transformer(cfg)._ffn(lw, y[None], None, "moe")[0][0]

    want = ffn(cfg_whole, lw)
    np.testing.assert_allclose(want, full, atol=2e-5)
    got = 0.0
    for first in (0, 4):
        cut = {k: (v[first:first + 4] if k in ("moe_w_gate", "moe_w_up", "moe_w_down")
                   else v) for k, v in lw.items()}
        cfg = dataclasses.replace(cfg_whole, n_experts_held=4, expert_first=first)
        got = got + ffn(cfg, cut) - shared
    np.testing.assert_allclose(got + shared, want, atol=2e-5)


def test_a_row_that_does_not_fit_is_dropped_and_counted():
    from shuffle_exchange_tpu.moe.layer import init_expert_mlp, moe_layer

    E, D, F, k, S = 8, 32, 16, 3, 64
    ep = init_expert_mlp(jax.random.PRNGKey(0), E, D, F)
    gw = jax.random.normal(jax.random.PRNGKey(1), (D, E))
    x = jax.random.normal(jax.random.PRNGKey(2), (S, D))
    half = {n: v[:4] for n, v in ep.items()}
    fit = moe_layer(gw, half, x, k=k, impl="ragged", buffer_rows=S * k)
    cut = moe_layer(gw, half, x, k=k, impl="ragged", buffer_rows=16)
    held = int(fit.metadata["held_rows"])
    assert int(fit.metadata["overflow_rows"]) == 0
    assert held == int(fit.metadata["expert_counts"][:4].sum())
    assert int(cut.metadata["held_rows"]) == 16
    assert int(cut.metadata["overflow_rows"]) == held - 16
    assert float(cut.metadata["drop_fraction"]) == pytest.approx((held - 16) / (S * k))
    with pytest.raises(ValueError, match="ragged"):
        moe_layer(gw, half, x, k=k, impl="capacity", buffer_rows=16)


@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_configuration(engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    model = Transformer(config_from_hf(HF))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="recurrent"):
        cls(model, params)


def test_a_held_share_alone_is_refused_by_the_engines():
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.models.transformer import tiny_moe

    model = Transformer(tiny_moe(experts=8, n_experts_held=4, moe_impl="ragged"))
    with pytest.raises(NotImplementedError, match="share"):
        InferenceEngine(model, jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def test_the_two_reference_copies_are_the_same_below_their_docstrings():
    def body(path):
        text = open(os.path.join(ROOT, path)).read()
        return text[text.index("# Everything below is written"):]

    assert body("chipbench/reference_qwen3next.py") == \
        body("shuffle_exchange_tpu/models/reference_qwen3next.py")
    assert "shuffle_exchange_tpu" not in body("chipbench/reference_qwen3next.py").replace(
        "nothing imported from\n# shuffle_exchange_tpu", "")
