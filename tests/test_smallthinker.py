"""SmallThinker-21BA3B-Instruct (``model_type: smallthinker``) through the
normal path against the plain reference (``models/reference_smallthinker.py``),
at a tiny size on the CPU: the cell's own period (a full layer that rotates
nothing, three window layers that rotate by the model's table), hidden 64, 14
query heads over 2 KV heads of 16 (groups of 7, as the model's 28 over 4),
window 16, 16 ReLU-gated experts of width 32 of which 8 are held here, top 3
by a softmax over the chosen logits, a router that reads the block's INPUT,
vocabulary 256, 64 positions. The weights are drawn by ``Transformer.init``
(gains redrawn, as the cell's driver does) and reach the reference through the
driver's own mapping (``chipbench/drivers/train_steps_prerouter.py``), so that
mapping is part of what is compared.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions. Loss 1e-5; routing exact; gradients 2e-3 of
each leaf's norm.
"""

import dataclasses
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

import shuffle_exchange_tpu as sxt  # noqa: E402
from chipbench import arith_smallthinker  # noqa: E402
from chipbench.drivers import train_steps_prerouter as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_smallthinker as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402
from shuffle_exchange_tpu.moe.layer import (expert_mlp, init_expert_mlp,  # noqa: E402
                                            moe_layer)

fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")

HF = {"model_type": "smallthinker", "hidden_size": 64, "num_attention_heads": 14,
      "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
      "moe_num_primary_experts": 16, "moe_num_active_primary_experts": 3,
      "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
      "num_hidden_layers": 4, "vocab_size": 256, "max_position_embeddings": 1024,
      "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": None,
      "tie_word_embeddings": False, "sliding_window_size": 16,
      # whole lists, as the published file has them: the first
      # num_hidden_layers entries are the layers here
      "rope_layout": [0, 1, 1, 1] * 3, "sliding_window_layout": [0, 1, 1, 1] * 3,
      "num_experts_held": 8, "expert_first": 0, "expert_buffer_factor": 2.0,
      "router_aux_loss_coef": 0.01}
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


# -- the configuration -------------------------------------------------------------

def test_config_from_hf_on_the_cells_own_file():
    """The catalog row's keys, as the cell's configuration file has them: the
    published widths, the cut, and the counts of what is held and of the whole."""
    from chipbench import harness

    src = harness.load_cell("smallthinker-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("attn", "moe"),) + (("swa", "moe"),) * 3
    assert (cfg.lead_layers, cfg.n_layers, cfg.routed_layers) == (0, 4, 4)
    assert cfg.several_kinds and not cfg.recurrent and not cfg.latent
    assert (cfg.d_model, cfg.head_dim, cfg.kv_heads) == (2560, 128, 4)
    assert (cfg.heads_of("attn"), cfg.heads_of("swa"), cfg.swa_window) == (28, 28, 4096)
    assert (cfg.position, cfg.unrotated_mixers, cfg.rope_theta, cfg.rope_yarn) == (
        "rope", ("attn",), 1.5e6, ())
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.moe_shared_expert_ff, cfg.activation) == (64, 16, 6, 768, 0, "reglu")
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_weight_scale, cfg.moe_aux,
            cfg.moe_norm_topk, cfg.moe_impl, cfg.aux_loss_coef, cfg.moe_router_input) == (
        "softmax", False, 1.0, "all_choices", True, "ragged", 0.01, "block")
    assert cfg.vocab_size == 37984 and not cfg.tie_embeddings and cfg.norm_eps == 1e-6
    model = Transformer(cfg)
    assert model.rope_for("attn", 8) == (None, None)
    assert model.rope_for("swa", 8)[0].shape == (8, 64)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's count (656,529,920), plus the unused bias leaves of the
    # plain RMSNorms (two a layer, one for the final norm)
    assert n == 656_529_920 + (2 * 4 + 1) * 2560
    assert n - (2 * 4 + 1) * 2560 == src["counts"]["parameters"]
    assert src["counts"]["parameters"] == arith_smallthinker.parameters(src, experts=16)
    assert src["counts"]["held_layer"] == arith_smallthinker.layer_parameters(src, 16)
    assert shapes["layers"]["attn_moe"]["wq"].shape == (1, 1, 2560, 28 * 128)
    assert shapes["layers"]["swa_moe"]["wk"].shape == (1, 3, 2560, 4 * 128)
    assert shapes["layers"]["swa_moe"]["moe_w_gate"].shape == (1, 3, 16, 2560, 768)
    assert shapes["layers"]["attn_moe"]["moe_gate"].shape == (1, 1, 2560, 64)
    assert not any(k.startswith("moe_shared") for k in shapes["layers"]["swa_moe"])


def test_the_uncut_model_is_thirteen_periods_and_21_5_billion():
    from chipbench import harness

    src = harness.load_cell("smallthinker-train")["config"]
    whole = {k: v for k, v in src.items()
             if k not in ("num_experts_held", "expert_first", "expert_buffer_factor")}
    whole.update(src["published"], num_experts_held=None)
    whole = {k: v for k, v in whole.items() if v is not None}
    cfg = config_from_hf(whole)
    assert (cfg.n_layers, len(cfg.pattern), cfg.experts_held, cfg.vocab_size) == (
        52, 4, 64, 151936)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n - (2 * 52 + 1) * 2560 == 21_506_562_560 == src["published"]["parameters"]
    assert arith_smallthinker.parameters(whole) == 21_506_562_560
    assert shapes["layers"]["swa_moe"]["wq"].shape == (13, 3, 2560, 3584)


@pytest.mark.parametrize("key, value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("moe_enable_early_router", False), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("moe_num_secondary_experts", 4), ("rope_layout", [0, 1, 2, 1] * 3),
    ("sliding_window_layout", [0, 1, 1])])
def test_what_is_not_written_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value})


@pytest.mark.parametrize("ropes, windows, names", [
    ([1, 1, 1, 1] * 3, [0, 1, 1, 1] * 3, "rope_layout=1 and sliding_window_layout=0"),
    ([0, 0, 1, 1] * 3, [0, 1, 1, 1] * 3, "rope_layout=0 and sliding_window_layout=1")])
def test_a_rotated_full_or_an_unrotated_window_layer_is_refused_by_name(ropes, windows, names):
    with pytest.raises(ValueError, match=names):
        config_from_hf({**HF, "rope_layout": ropes, "sliding_window_layout": windows})


def test_a_stack_that_ends_inside_a_period_runs_as_one_period_of_its_length():
    """As laguna's, lfm2's and nemotron's (``_lead_and_period``), with no option."""
    six = config_from_hf({**HF, "num_hidden_layers": 6})
    assert len(six.pattern) == 6 and six.pattern[4] == ("attn", "moe")
    eight = config_from_hf({**HF, "num_hidden_layers": 8})
    assert (len(eight.pattern), eight.n_layers) == (4, 8)


def test_a_share_states_its_own_buffer():
    cut = {k: v for k, v in HF.items() if k != "expert_buffer_factor"}
    with pytest.raises(ValueError, match="expert_buffer_factor"):
        config_from_hf(cut)
    whole = config_from_hf({k: v for k, v in cut.items() if k != "num_experts_held"})
    assert whole.experts_held == whole.n_experts == 16


# -- the program against the reference -----------------------------------------------

def test_first_loss_and_expert_counts(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - float(case["ref"]["loss"])) < 1e-5
    assert stats["moe_expert_tokens"].shape == (4, 16)
    np.testing.assert_array_equal(stats["moe_expert_tokens"],
                                  case["ref"]["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], case["ref"]["held_rows"])
    assert int(np.asarray(stats["moe_overflow_rows"]).sum()) == 0
    assert np.all(np.asarray(stats["moe_expert_tokens"]).sum(axis=1) == BATCH * SEQ * 3)


def test_the_balancing_loss_is_in_the_loss(case):
    bare = Transformer(dataclasses.replace(case["cfg"], moe_aux="none", aux_loss_coef=0.0))
    batch = {"input_ids": case["ids"]}
    with_it = float(jax.jit(case["model"].loss)(case["params"], batch))
    without = float(jax.jit(bare.loss)(case["params"], batch))
    want = HF["router_aux_loss_coef"] * float(case["ref"]["aux"])
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
    assert {"layers/attn_moe/wq", "layers/swa_moe/wq", "layers/attn_moe/moe_gate",
            "layers/swa_moe/moe_w_gate", "layers/swa_moe/moe_w_down"} <= set(got)
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 2e-3, worst


def _variant(wrong, monkeypatch):
    """The reference with one piece of the mathematics changed; returns the
    source config to run it with."""
    src = dict(HF)
    if wrong.startswith("window_1"):
        src["sliding_window_size"] = int(wrong[-2:])
    elif wrong == "window_ignored":
        src["sliding_window_size"] = SEQ + 1
    elif wrong == "router_reads_y2":
        monkeypatch.setattr(ref, "router_reads", lambda x, y, y2: y2)
    elif wrong == "router_reads_normed_input":
        monkeypatch.setattr(ref, "router_reads", lambda x, y, y2: y)
    elif wrong == "no_renormalisation":
        def choose(logits, cfg):
            p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            weight, chosen = jax.lax.top_k(p, cfg["moe_num_active_primary_experts"])
            return p, chosen.astype(jnp.int32), weight
        monkeypatch.setattr(ref, "choose", choose)
    elif wrong == "sigmoid_scores":
        def choose(logits, cfg):
            s = jax.nn.sigmoid(logits.astype(jnp.float32))
            weight, chosen = jax.lax.top_k(s, cfg["moe_num_active_primary_experts"])
            return (jax.nn.softmax(logits, axis=-1), chosen.astype(jnp.int32),
                    weight / weight.sum(axis=-1, keepdims=True))
        monkeypatch.setattr(ref, "choose", choose)
    elif wrong == "silu_gate":
        monkeypatch.setattr(ref, "gate_act", jax.nn.silu)
    elif wrong == "ungated_relu":
        monkeypatch.setattr(ref, "mlp", lambda w, name, y: ref.linear(
            jax.nn.relu(ref.linear(y, w[name + "up.weight"])), w[name + "down.weight"]))
    elif wrong == "full_layers_rotated":
        monkeypatch.setattr(ref, "rotated", lambda i, cfg: True)
    elif wrong == "window_layers_unrotated":
        monkeypatch.setattr(ref, "rotated", lambda i, cfg: False)
    elif wrong == "heads_7x4":
        monkeypatch.setattr(ref, "kv_head", lambda h, H, KV: h % KV)
    else:
        raise AssertionError(wrong)
    return src


WRONG = ["window_15", "window_17", "window_ignored", "router_reads_y2",
         "router_reads_normed_input", "no_renormalisation", "sigmoid_scores",
         "silu_gate", "ungated_relu", "full_layers_rotated",
         "window_layers_unrotated", "heads_7x4"]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_nearest_wrong_models_read_far(case, wrong, monkeypatch):
    """What the comparison is FOR: each of the nearest wrong models, as the
    reference computes it, is far from the program on the first loss, where
    the program itself sits at 1e-5 (float32 both sides)."""
    src = _variant(wrong, monkeypatch)
    want = float(case["ref"]["loss"])
    got = float(jax.jit(lambda w, i: ref.loss(w, src, i))(dict(case["weights"]), case["ids"]))
    assert abs(got - want) > 2e-5, (wrong, got, want)


def test_the_softmax_over_the_chosen_is_the_softmax_over_all_renormalised():
    logits = jax.random.normal(jax.random.PRNGKey(0), (512, 64), jnp.float32) * 3.0
    p, chosen, weight = ref.choose(logits, {"moe_num_active_primary_experts": 6})
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    np.testing.assert_allclose(weight, picked / picked.sum(axis=-1, keepdims=True),
                               rtol=2e-6)
    np.testing.assert_allclose(weight.sum(axis=-1), 1.0, rtol=1e-6)
    # and the program's router (softmax over all, top k, renormalised) agrees
    from shuffle_exchange_tpu.moe.gating import topk_select

    idx, w, *_ = topk_select(logits, 6, normalize_weights=True, aux="all_choices")
    np.testing.assert_array_equal(np.sort(idx, axis=-1), np.sort(chosen, axis=-1))
    order = lambda i, x: jnp.take_along_axis(x, jnp.argsort(i, axis=-1), axis=-1)
    np.testing.assert_allclose(order(idx, w), order(chosen, weight), rtol=5e-6)


# -- the router's input ---------------------------------------------------------------

@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_a_router_x_equal_to_x_is_todays_moe_layer_bit_for_bit(share):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (2, 32, 64), jnp.float32)
    gate = jax.random.normal(keys[1], (64, 16), jnp.float32) / 8.0
    experts = init_expert_mlp(keys[2], 8 if share else 16, 64, 32, "reglu")
    kw = dict(k=3, activation="reglu", impl="ragged", aux="all_choices",
              **(dict(expert_first=4, buffer_rows=128) if share else {}))
    both = jax.jit(lambda x: (moe_layer(gate, experts, x, **kw),
                              moe_layer(gate, experts, x, router_x=x, **kw)))
    plain, routed = both(x)
    np.testing.assert_array_equal(plain.output, routed.output)
    np.testing.assert_array_equal(plain.aux_loss, routed.aux_loss)
    for key in plain.metadata:
        np.testing.assert_array_equal(plain.metadata[key], routed.metadata[key])
    # a router input of its own moves the choice and leaves the experts' rows
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)
    apart = moe_layer(gate, experts, x, router_x=r, **kw)
    swapped = moe_layer(gate, experts, r, **kw)
    np.testing.assert_array_equal(apart.metadata["expert_counts"],
                                  swapped.metadata["expert_counts"])
    assert float(jnp.abs(apart.output - plain.output).max()) > 1e-3
    # the gradient reaches router_x through the weights, and x through the experts
    gr, gx = jax.grad(lambda r, x: moe_layer(gate, experts, x, router_x=r, **kw)
                      .output.sum(), argnums=(0, 1))(r, x)
    assert float(jnp.abs(gr).max()) > 0 and float(jnp.abs(gx).max()) > 0


def test_a_router_input_of_its_own_runs_the_ragged_impl_only():
    x = jnp.zeros((1, 8, 16))
    experts = init_expert_mlp(jax.random.PRNGKey(0), 4, 16, 8)
    with pytest.raises(ValueError, match="router_x"):
        moe_layer(jnp.zeros((16, 4)), experts, x, impl="capacity", router_x=x)
    with pytest.raises(ValueError, match="is not x's shape"):
        moe_layer(jnp.zeros((16, 4)), experts, x, impl="ragged", router_x=x[:, :4])


def test_the_routers_scopes_and_the_unrotated_layers_open_where_they_run(case):
    """``pre_router`` around ``moe_router`` in the routed halves, ``nope_*`` in
    the full layer's mixer, ``swa_*`` in the window layers', and no ``pre_router``
    in a program whose router reads what its experts read."""
    text = jax.jit(case["model"].loss).lower(
        case["params"], {"input_ids": case["ids"]}).as_text(debug_info=True)
    for scope in ("pre_router/moe_router", "attn_qkv/nope_qkv", "attn_core/nope_core",
                  "attn_out/nope_out", "attn_qkv/swa_qkv/", "attn_qkv/swa_rope",
                  "attn_core/swa_core"):
        assert scope in text, scope
    from shuffle_exchange_tpu.profiling import trace

    assert {"nope_qkv", "nope_core", "nope_out"} <= set(trace.SCOPES["attn"])
    assert "pre_router" in trace.SCOPES["mlp"]
    plain = Transformer(dataclasses.replace(case["cfg"], moe_router_input="ffn"))
    text = jax.jit(plain.loss).lower(
        case["params"], {"input_ids": case["ids"]}).as_text(debug_info=True)
    assert "pre_router" not in text and "moe_router" in text


def test_the_program_with_the_router_on_y2_is_another_model(case):
    """The config field is what moves the router: with "ffn" the same weights
    give the reference's ``router_reads_y2`` variant."""
    plain = Transformer(dataclasses.replace(case["cfg"], moe_router_input="ffn"))
    batch = {"input_ids": case["ids"]}
    got = float(jax.jit(plain.loss)(case["params"], batch))
    assert abs(got - float(case["ref"]["loss"])) > 2e-5
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ref, "router_reads", lambda x, y, y2: y2)
        want = float(jax.jit(lambda w, i: ref.loss(w, HF, i))(
            dict(case["weights"]), case["ids"]))
    assert abs(got - want) < 1e-5


def test_a_one_kind_model_takes_the_block_router(case):
    """The stack cut to its full layers is ONE kind of layer (a flat
    ``params["layers"]``): its router reads the block's input as the
    two-kind stack's does. Loss, the experts' counts and every gradient
    against the reference on the same two layers, and not what a router on
    y2 reads; a bad value is refused where the model is made."""
    hf = dict(HF, num_hidden_layers=2, rope_layout=[0] * 2, sliding_window_layout=[0] * 2)
    cfg = config_from_hf(hf)
    assert not cfg.several_kinds and cfg.moe_router_input == "block"
    model = Transformer(cfg)
    params = driver.initial_params(model, 5)
    assert params["layers"]["wq"].shape[0] == 2             # flat, [L, ...]
    weights = driver.to_source_names(params, hf)
    batch = {"input_ids": case["ids"]}
    want = jax.jit(lambda w, i: ref.loss_parts(w, hf, i))(weights, case["ids"])
    loss, stats = jax.jit(model.loss_and_stats)(params, batch)
    assert abs(float(loss) - float(want["loss"])) < 1e-5
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    want_grad = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, hf, i))(weights, case["ids"]), hf)
    assert {"layers/moe_gate", "layers/wq", "layers/moe_w_down"} <= set(want_grad)
    worst = gaps(driver.flat_tree(jax.jit(jax.grad(model.loss))(params, batch)), want_grad)
    assert max(worst.values()) < 2e-3, worst
    on_y2 = Transformer(dataclasses.replace(cfg, moe_router_input="ffn"))
    assert abs(float(jax.jit(on_y2.loss)(params, batch)) - float(want["loss"])) > 2e-5
    with pytest.raises(ValueError, match="'ffn' or 'block'"):
        Transformer(dataclasses.replace(case["cfg"], moe_router_input="mixer"))


# -- the gated ReLU unit ---------------------------------------------------------------

def test_reglu_is_gated_structurally_as_swiglu_is():
    from shuffle_exchange_tpu.models.transformer import TransformerConfig, gate_fn

    assert gate_fn("swiglu") is jax.nn.silu and gate_fn("reglu") is jax.nn.relu
    assert gate_fn("relu") is None and gate_fn("relu2") is None
    p = init_expert_mlp(jax.random.PRNGKey(0), 4, 16, 8, "reglu", bias=True)
    assert set(p) == {"w_gate", "w_up", "w_down", "b_gate", "b_up", "b_down"}
    assert "w_gate" not in init_expert_mlp(jax.random.PRNGKey(0), 4, 16, 8, "relu")
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 5, 16), jnp.float32)
    want = jnp.einsum("ecf,efm->ecm",
                      jax.nn.relu(jnp.einsum("ecm,emf->ecf", x, p["w_gate"]))
                      * jnp.einsum("ecm,emf->ecf", x, p["w_up"]), p["w_down"])
    np.testing.assert_allclose(expert_mlp(p, x, "reglu"), want, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(expert_mlp(p, x, "swiglu") - want).max()) > 1e-3
    # a dense model's gated MLP takes the name too
    dense = Transformer(TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                                          d_ff=48, activation="reglu", norm="rmsnorm",
                                          position="rope", max_seq_len=16))
    leaves = dense.init(jax.random.PRNGKey(0))["layers"]
    assert {"w_gate", "w_up", "w_down"} <= set(leaves)
    ids = np.arange(10, dtype=np.int32)[None]
    assert np.isfinite(float(dense.loss(dense.init(jax.random.PRNGKey(0)),
                                        {"input_ids": ids})))


# -- remat, the trainer, the shares ---------------------------------------------------

def test_remat_halves_give_the_same_loss_and_gradients(case):
    """Per-half remat, the feed-forward half checkpointed on (x, h): the values
    of the program without it."""
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
    program = driver.reference_program(HF)
    want = driver.reference_first_step(program, case["weights"], jnp.asarray(ids))
    engine = sxt.initialize(
        model=model, params=driver.initial_params(model, 5),
        config={"optimizer": {"type": "FusedAdam",
                              "params": {"lr": 1e-4, "weight_decay": 0.1}},
                "zero_optimization": {"stage": 3},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "train_batch_size": rows, "steps_per_print": 10 ** 9}, seed=5)[0]
    assert model.config.remat
    loss = float(engine.train_batch({"input_ids": ids}))
    assert abs(loss - want["loss"]) < 2e-5
    stats = engine.last_step_stats()
    np.testing.assert_array_equal(stats["moe_expert_tokens"], want["expert_tokens"])
    np.testing.assert_array_equal(stats["moe_held_rows"], want["held_rows"])
    moment = driver.first_moment(engine.state.opt_state)
    got = {k: np.asarray(v) * 10.0 for k, v in moment.items()}     # / (1 - beta1)
    worst = gaps(got, want["grads"])
    assert max(worst.values()) < 2e-3, worst


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's tie, at the published router (64 wide, top 6) cut to a
    small width: the parts of one layer's result that ranks 0-3 give (16
    experts each), with what every rank computes alike (attention, router,
    norms: the residual h) counted once, are the uncut reference's layer."""
    whole_src = {**{k: v for k, v in HF.items() if k not in (
        "num_experts_held", "expert_first", "expert_buffer_factor")},
        "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6,
        "moe_ffn_hidden_size": 8}
    whole = config_from_hf(whole_src)
    assert (whole.n_experts, whole.experts_held, whole.moe_top_k) == (64, 64, 6)
    model = Transformer(whole)
    params = driver.initial_params(model, 11)
    weights = driver.to_source_names(params, whole_src)
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(weights, 1, x, whole_src)[0]                  # a window layer
        row = jax.tree.map(lambda a: a[0, 0], params["layers"]["swa_moe"])
        rope = model.rope_for("swa", SEQ)
        total, residual = 0.0, None
        for r in range(4):
            cfg = dataclasses.replace(whole, n_experts_held=16, expert_first=16 * r,
                                      moe_held_rows_factor=4.0)
            lw = {k: (v[16 * r:16 * (r + 1)] if k.startswith("moe_w_") else v)
                  for k, v in row.items()}
            out, (_, stats) = Transformer(cfg).layer_apply(lw, x, rope, kind=("swa", "moe"))
            assert int(stats["overflow_rows"]) == 0
            # every rank computes the same h = x + attention: once
            bare = {k: (jnp.zeros_like(v) if k == "moe_w_down" else v) for k, v in lw.items()}
            h = Transformer(cfg).layer_apply(bare, x, rope, kind=("swa", "moe"))[0]
            residual = h if residual is None else residual
            np.testing.assert_allclose(h, residual, atol=1e-6)
            total = total + (out - h)
        total = total + residual
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err


# -- the kernels, interpreted -------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 256], ids=["full", "window"])
def test_seven_query_heads_a_kv_head_through_the_kernel_route(window):
    """The splash kernels, interpreted, at groups of 7 (14 query heads over 2
    KV heads), causal and under a window that spans block boundaries: forward
    and both gradients against the dense reference attention."""
    ks = jax.random.split(jax.random.PRNGKey(7 + window), 3)
    q = jax.random.normal(ks[0], (1, 512, 14, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 64), jnp.float32)
    got = fa.splash_attention_gqa(q, k, v, interpret=True, window=window)
    want = fa.reference_attention(q, k, v, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-5
    f = lambda q, k, v: jnp.sum(fa.splash_attention_gqa(
        q, k, v, interpret=True, window=window) ** 2)
    g = lambda q, k, v: jnp.sum(fa.reference_attention(q, k, v, window=window) ** 2)
    for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v), jax.grad(g, (0, 1, 2))(q, k, v)):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-4


def test_the_window_reaches_the_kernels_as_blocks_to_skip():
    """The engagement counter at the cell's shapes: ``window_block`` takes the
    largest block the window holds, 1024, so a query block visits its own and
    the four before it: 70 of the 136 causal pairs (51.5%; at 512-blocks it
    would be 243 of 528, 46.0%; the window's own share of the pairs is 43.75%)."""
    assert fa.window_block(16384, 4096) == 1024
    assert fa.block_visit_share(16384, 4096) == pytest.approx(100.0 * 70 / 136)
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    assert fa.attention_route(q, kv, kv, impl="pallas", window=4096) == "splash_window"
    assert fa.attention_route(q, kv, kv, impl="pallas") == "splash"
    assert fa.attention_backward_route(q, kv, kv, True, 4096) == "fused_resident_dkv"


def test_the_window_edge_reading_tells_one_key_off(case):
    """``driver.edge_gaps`` on the reference's window mixer: right at the
    model's window, and wrong one key short or long."""
    params = case["params"]
    lw, x, _ = driver.mixer_inputs(params, HF, "swa", 5, 1, SEQ, 6.0, jnp.float32)
    W = HF["sliding_window_size"]
    right = driver.edge_gaps(driver.reference_mixer(HF, "swa"), lw, x, W, 5)
    assert right["inside"] > 0.01 and right["outside"] == 0.0
    program = driver.edge_gaps(driver.program_mixer(case["model"], "swa", SEQ), lw, x, W, 5)
    assert program["inside"] > 0.01 and program["outside"] == 0.0
    tol = {"edge_min": 0.01, "edge_outside_tol": 1e-6}
    base = {"losses": [5.0], "reference_loss": 5.0, "route_gap": 0.0, "held_gap": 0.0,
            "counters_add_up": True, "overflow": [0, 0], "grad_gaps": {"embed": 0.0},
            "router_gaps": {"choice": 0.0}, "mixer_gaps": {"swa/y": 0.0},
            "rotated": (3, 3, 4)}
    limits = {"loss_tol": 1e-4, "route_tol": 1e-3, "grad_tol": 1e-2, "router_tol": 1e-5,
              "mixer_tol": 1e-3, **tol}
    assert driver.failed_checks({**base, "edge_gaps": right}, limits) == []
    for wrong, said in ((W - 1, "one key short"), (W + 1, "one key long")):
        off = driver.edge_gaps(driver.reference_mixer({**HF, "sliding_window_size": wrong},
                                                      "swa"), lw, x, W, 5)
        failed = driver.failed_checks({**base, "edge_gaps": off}, limits)
        assert len(failed) == 1 and said in failed[0], (wrong, off, failed)
    assert any("the router's input" in m for m in driver.failed_checks(
        {**base, "router_input": ("ffn", "block")}, limits))
    good = {"pre_router": 9, "nope_core": 2, "swa_rope": 5, "rope_under_nope": 0}
    assert driver.failed_checks({**base, "scopes": good}, limits) == []
    assert any("pre_router" in m for m in driver.failed_checks(
        {**base, "scopes": {**good, "pre_router": 0}}, limits))
    assert any("the rotation by kind" in m for m in driver.failed_checks(
        {**base, "rotated": (4, 3, 4)}, limits))
    # the compiled step's own scopes: a full kind that took a table, a window
    # kind that lost its own, a rotation inside an unrotated layer
    for fault in ({"nope_core": 0}, {"swa_rope": 0}, {"rope_under_nope": 3}):
        failed = driver.failed_checks({**base, "scopes": {**good, **fault}}, limits)
        assert len(failed) == 1 and "the compiled step holds" in failed[0], (fault, failed)


# -- serving refuses ---------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_stack_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="router reads the block's input"):
        cls(case["model"], case["params"])
    with pytest.raises(NotImplementedError, match="full layers rotate nothing"):
        cls(case["model"], case["params"])


def test_checkpoint_import_is_not_written():
    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    with pytest.raises(NotImplementedError, match="several layer kinds"):
        params_from_state_dict({}, config_from_hf(HF), "smallthinker")


def test_the_two_reference_copies_are_byte_identical():
    read = lambda path: open(os.path.join(ROOT, path), "rb").read()
    body = read("chipbench/reference_smallthinker.py")
    assert body == read("shuffle_exchange_tpu/models/reference_smallthinker.py")
    code = body.decode().split('"""', 2)[2]
    assert "import shuffle_exchange_tpu" not in code and "from shuffle_exchange_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
