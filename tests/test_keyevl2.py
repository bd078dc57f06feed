"""Keye-VL-2.0-30B-A3B's language model (``model_type: KeyeVL2``) through the
normal path against the plain reference (``models/reference_keyevl2.py``), at a
tiny size on the CPU: hidden 64, 8 query heads over 2 KV heads of 16 (groups of
4), an indexer of 4 heads of 8 over one key head that keeps 16 keys a query, a
per-head q/k norm, M-RoPE with sections [2, 3, 3], 16 SiLU-gated experts of
width 32 of which 8 are held here, top 4 of a softmax renormalised, vocabulary
256, 64 positions in chunks of 16 queries. The weights are drawn by
``Transformer.init`` (gains and the indexer's bias redrawn, as the cell's driver
does) and reach the reference through the driver's own mapping
(``chipbench/drivers/train_steps_dsa.py``), so that mapping is part of what is
compared.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions. Losses 1e-5; routing and the selection exact;
gradients 2e-3 of each leaf's norm.
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

from chipbench import arith_dsa  # noqa: E402
from chipbench.drivers import train_steps_dsa as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_keyevl2 as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402
from shuffle_exchange_tpu.models.transformer import (apply_rope, mrope_table,  # noqa: E402
                                                     rope_table)
from shuffle_exchange_tpu.ops import dsa  # noqa: E402

HF = {"model_type": "KeyeVL2", "hidden_size": 64, "num_attention_heads": 8,
      "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
      "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
      "num_hidden_layers": 2, "vocab_size": 256, "max_position_embeddings": 1024,
      "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
      "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                       "type": "default"},
      "tie_word_embeddings": False, "hidden_act": "silu", "attention_bias": False,
      "decoder_sparse_step": 1, "mlp_only_layers": [], "use_sliding_window": False,
      "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                    "indexer_num_kv_heads": 1, "q_chunk_size": 512,
                    "kv_chunk_size": 512, "topk": 16},
      "num_experts_held": 8, "expert_first": 0, "expert_buffer_factor": 2.0,
      "router_aux_loss_coef": 0.01}
SEQ, BATCH = 64, 2


def gaps(ours, theirs):
    """{leaf: |ours - theirs| / |theirs|} (a leaf no gradient reaches: the
    plain RMSNorms' unused biases, is 0 on both sides and reads 0)."""
    far = lambda a, b: float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    return {k: far(np.asarray(ours[k]), np.asarray(theirs[k])) for k in theirs}


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """Several chunks of queries in a sequence of 64."""
    monkeypatch.setattr(dsa, "CHUNK", 16)


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

def cell_source():
    from chipbench import harness

    return harness.load_cell("keyevl2-train")["config"]


def test_config_from_hf_on_the_cells_own_file():
    """The catalog row's keys, as the cell's configuration file has them: the
    published widths, the cut, and ``counts`` against the program's tree."""
    src = cell_source()
    cfg = config_from_hf(src)
    assert cfg.pattern == (("dsa", "moe"),) and not cfg.several_kinds
    assert (cfg.n_layers, cfg.routed_layers, cfg.lead_layers) == (5, 5, 0)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.dsa_topk, cfg.dsa_index_heads, cfg.dsa_index_dim) == (2048, 16, 64)
    assert (cfg.position, cfg.rope_theta, cfg.mrope_section, cfg.qk_norm) == (
        "rope", 1e7, (16, 24, 24), "head")
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.ff_dim,
            cfg.moe_shared_expert_ff, cfg.activation) == (128, 16, 8, 768, 0, "swiglu")
    assert (cfg.moe_score, cfg.moe_aux, cfg.moe_norm_topk, cfg.moe_impl,
            cfg.aux_loss_coef) == ("softmax", "all_choices", True, "ragged", 0.01)
    assert cfg.vocab_size == 18992 and not cfg.tie_embeddings and cfg.norm_eps == 1e-6
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's count, plus the unused bias leaves of the plain RMSNorms (two
    # a layer, one for the final norm)
    counts = src["counts"]
    assert n - (2 * 5 + 1) * 2048 == 562_290_560 == counts["parameters"]
    layer = {k: int(np.prod(v.shape[1:])) for k, v in shapes["layers"].items()}
    assert sum(layer[k] for k in ("wq", "wk", "wv", "wo")) == counts["attention"] == 18_874_368
    assert layer["q_norm_w"] + layer["k_norm_w"] == counts["qk_gains"] == 256
    assert sum(v for k, v in layer.items() if k.startswith("dsa_")) == counts["indexer"] == 2_261_120
    assert layer["moe_gate"] == counts["router"] == 262_144
    held = sum(v for k, v in layer.items() if k.startswith("moe_w_"))
    assert held == counts["held_experts"] == 16 * counts["expert"] == 75_497_472
    assert layer["ln1_w"] + layer["ln2_w"] == counts["block_norms"] == 4096
    assert sum(layer.values()) - 4096 == counts["held_layer"] == 96_899_456
    assert counts["layers"] == 5 * counts["held_layer"]
    assert counts["embedding_head_final_norm"] == 2 * 18992 * 2048 + 2048
    assert counts["parameters"] == arith_dsa.parameters(src)
    assert counts["held_layer"] == arith_dsa.layer_parameters(src, 16)
    assert shapes["layers"]["dsa_wq"].shape == (5, 2048, 1024)
    assert shapes["layers"]["moe_w_gate"].shape == (5, 16, 2048, 768)


def test_the_uncut_model_is_48_layers_and_30_6_billion():
    src = cell_source()
    whole = {k: v for k, v in src.items()
             if k not in ("num_experts_held", "expert_first", "expert_buffer_factor")}
    whole.update({k: v for k, v in src["published"].items() if k != "num_experts_held"})
    cfg = config_from_hf(whole)
    assert (cfg.n_layers, cfg.experts_held, cfg.vocab_size) == (48, 128, 151936)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n - (2 * 48 + 1) * 2048 == 30_640_656_384 == src["published"]["parameters"]
    assert arith_dsa.parameters(whole, 128) == 30_640_656_384


@pytest.mark.parametrize("key, value", [
    ("vision_config", {"depth": 27}), ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("attention_bias", True), ("tie_word_embeddings", True),
    ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "rope_type": "yarn", "factor": 4.0}),
    ("sa_config", {**HF["sa_config"], "indexer_num_kv_heads": 2}),
    ("sa_config", {**HF["sa_config"], "block_topk": 4}),
    ("sa_config", None)])
def test_what_is_not_written_is_refused_by_name(key, value):
    name = "sa_config" if key == "sa_config" else key
    with pytest.raises(ValueError, match=name):
        config_from_hf({**HF, key: value})


def test_an_unknown_sa_config_key_is_named():
    with pytest.raises(ValueError, match="block_topk"):
        config_from_hf({**HF, "sa_config": {**HF["sa_config"], "block_topk": 4}})


# -- program against reference ---------------------------------------------------------

def test_losses_routing_and_selection_equal_the_reference(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    want = case["ref"]
    assert abs(float(loss) - float(want["loss"])) < 1e-5
    assert abs(float(stats["dsa_kl"].mean()) - float(want["kl"])) < 1e-5 * float(want["kl"]) + 1e-7
    ce = float(loss) - 0.01 * float(want["aux"]) - float(stats["dsa_kl"].mean())
    assert abs(ce - float(want["ce"])) < 1e-5
    assert np.array_equal(np.asarray(stats["moe_expert_tokens"]),
                          np.asarray(want["expert_tokens"]))
    assert np.array_equal(np.asarray(stats["moe_held_rows"]), np.asarray(want["held_rows"]))
    # every query holds min(t + 1, 16) keys, in program and reference alike
    held = np.minimum(np.arange(SEQ) + 1, 16)
    assert np.array_equal(np.asarray(want["held_keys"]),
                          np.broadcast_to(held, (2, BATCH, SEQ)))
    assert np.array_equal(np.asarray(stats["dsa_pairs"]), [BATCH * held.sum()] * 2)
    assert np.asarray(stats["dsa_selected_min"]).tolist() == [16, 16]
    assert np.asarray(stats["dsa_selected_max"]).tolist() == [16, 16]
    # the chunks (of 4 a sequence a layer) whose tie rule searched: a count of
    # the whole step; the relu's exact zeros tie at this size
    assert stats["dsa_tied_chunks"].shape == () and stats["dsa_tied_chunks"].dtype == jnp.int32
    assert 0 <= int(stats["dsa_tied_chunks"]) <= 2 * BATCH * 3
    assert BATCH * held.sum() == BATCH * arith_dsa.selected_pairs(SEQ, 16)


def test_every_leafs_gradient_equals_the_reference(case):
    model, params = case["model"], case["params"]
    g = driver.flat_tree(jax.jit(jax.grad(
        lambda p: model.loss(p, {"input_ids": case["ids"]})))(params))
    got = gaps(g, case["ref_grads"])
    assert set(got) == set(case["ref_grads"]) and len(got) == 20
    assert max(got.values()) < 2e-3, got


def test_per_half_remat_computes_the_same_gradient(case):
    model, params = case["model"], case["params"]
    batch = {"input_ids": case["ids"]}
    again = Transformer(dataclasses.replace(case["cfg"], remat=True, remat_policy="full"))
    a = jax.jit(jax.grad(lambda p: model.loss(p, batch)))(params)
    b = jax.jit(jax.grad(lambda p: again.loss(p, batch)))(params)
    assert max(gaps(driver.flat_tree(b), driver.flat_tree(a)).values()) < 1e-5


def test_per_half_remats_replay_neither_scores_nor_searches(monkeypatch, devices8):
    """The train step lowered for the TPU with the kernel routes chosen
    (``testing/program_text``; heads of 128, three chunks of 128, bf16,
    per-half remat): ONE search a layer a step. The forward's layer scan holds the
    selection's kernel, both readings of the scores, their backward, the
    target and the core's forward; the backward's holds the fused backward
    and nothing of the selection: its replay unpacks the mask it kept (the
    tie rule's rounds sit behind a ``cond`` on the selection kernel's counts:
    where that kernel is not, they are not)."""
    import collections
    import re

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.ops import dispatch
    from shuffle_exchange_tpu.testing import program_text

    monkeypatch.setattr(dsa, "CHUNK", 128)
    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devices8[:1])
    src = {**HF, "hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 1,
           "head_dim": 128, "moe_intermediate_size": 128,
           "rope_scaling": {**HF["rope_scaling"], "mrope_section": [16, 24, 24]},
           "sa_config": {**HF["sa_config"], "indexer_head_dim": 16, "indexer_num_heads": 2,
                         "topk": 48}}
    engine = sxt.initialize(model=Transformer(config_from_hf(src)), seed=7, config={
        "train_batch_size": 1, "steps_per_print": 10 ** 9,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}, "bf16": {"enabled": True},
        "activation_checkpointing": {"enabled": True, "policy": "full"}})[0]
    assert dsa.select_route(jnp.float32, 384, 128) == "pallas"
    text = program_text.train_step_lowered(
        engine, {"input_ids": np.zeros((1, 385), np.int32)}, ("tpu",)).as_text()
    launches = collections.Counter(re.findall(r'kernel_name = "(sxt_(?:dsa|splash)\w+)"', text))
    assert launches == {"sxt_dsa_select": 1, "sxt_dsa_index_fwd": 2, "sxt_dsa_index_bwd": 1,
                        "sxt_dsa_attention_fwd": 1, "sxt_dsa_attention_head_mean": 1,
                        "sxt_splash_bwd_fused": 1}, launches
    # no chunk's scores outlive its trip of the loop: nothing [chunks, S, C]
    # is stacked for a later pass
    assert "tensor<384x128xf32>" in text and "tensor<3x384x128xf32>" not in text


def test_the_engine_hands_out_the_steps_tied_chunks(case, monkeypatch, devices8):
    """``dsa_tied_chunks`` rides with the selection's other counters into
    ``last_step_stats()``: one int32 for the whole step."""
    import shuffle_exchange_tpu as sxt

    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devices8[:1])
    engine = sxt.initialize(model=case["model"], seed=7, config={
        "train_batch_size": BATCH, "steps_per_print": 10 ** 9,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})[0]
    engine.train_batch({"input_ids": case["ids"]})
    stats = engine.last_step_stats()
    assert {"dsa_selected_min", "dsa_pairs", "dsa_tied_chunks"} <= set(stats)
    assert stats["dsa_tied_chunks"].shape == () and stats["dsa_tied_chunks"].dtype == jnp.int32
    assert stats["dsa_pairs"].shape == (2,)


def test_each_loss_reaches_its_own_leaves_and_no_other(case):
    """The indexer's loss moves the indexer's five leaves and NOTHING else; the
    language-modelling and balancing losses move every other leaf and nothing
    of the indexer: exactly zero, not small."""
    model, params, batch = case["model"], case["params"], {"input_ids": case["ids"]}

    def parts(p):
        loss, stats = model.loss_and_stats(p, batch)
        li = stats["dsa_kl"].mean()
        return li, loss - li

    g_kl = driver.flat_tree(jax.jit(jax.grad(lambda p: parts(p)[0]))(params))
    g_lm = driver.flat_tree(jax.jit(jax.grad(lambda p: parts(p)[1]))(params))
    for leaf in g_kl:
        mine = driver.is_indexer(leaf)
        assert (float(jnp.abs(g_kl[leaf]).max()) > 0) == mine, leaf
        assert (float(jnp.abs(g_lm[leaf]).max()) == 0) == (
            mine or leaf.endswith(("ln1_b", "ln2_b", "ln_f_b"))), leaf


def test_the_eight_ranks_shares_add_up_to_the_uncut_layer():
    """The guide's tie, at the published router (128 wide, top 8) cut to a
    small width: the parts of one layer's result that ranks 0-7 give (16
    experts each), with what every rank computes alike (attention, the
    indexer, the router, the norms: the residual h) counted once, are the
    uncut reference's layer; and every rank chooses the same keys."""
    whole_src = {**{k: v for k, v in HF.items() if k not in (
        "num_experts_held", "expert_first", "expert_buffer_factor")},
        "num_experts": 128, "num_experts_per_tok": 8, "moe_intermediate_size": 8}
    whole = config_from_hf(whole_src)
    assert (whole.n_experts, whole.experts_held, whole.moe_top_k) == (128, 128, 8)
    model = Transformer(whole)
    params = driver.initial_params(model, 11)
    weights = driver.to_source_names(params, whole_src)
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(weights, 1, x, whole_src)[0]
        row = jax.tree.map(lambda a: a[1], params["layers"])
        rope = model.rope_for("dsa", SEQ)
        total, residual, pairs = 0.0, None, set()
        for r in range(8):
            cfg = dataclasses.replace(whole, n_experts_held=16, expert_first=16 * r,
                                      moe_held_rows_factor=4.0)
            lw = {k: (v[16 * r:16 * (r + 1)] if k.startswith("moe_w_") else v)
                  for k, v in row.items()}
            out, (_, stats) = Transformer(cfg).layer_apply(lw, x, rope, kind=("dsa", "moe"))
            assert int(stats["overflow_rows"]) == 0
            pairs.add((int(stats["dsa_pairs"]), float(stats["dsa_kl"])))
            # every rank computes the same h = x + attention: once
            bare = {k: (jnp.zeros_like(v) if k == "moe_w_down" else v) for k, v in lw.items()}
            h = Transformer(cfg).layer_apply(bare, x, rope, kind=("dsa", "moe"))[0]
            residual = h if residual is None else residual
            np.testing.assert_allclose(h, residual, atol=1e-6)
            total = total + (out - h)
        total = total + residual
    assert len(pairs) == 1
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err


# -- M-RoPE --------------------------------------------------------------------------------

def test_equal_streams_are_plain_rope_bit_for_bit():
    T, Dh = 48, 16
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, 2, T))
    cos, sin = mrope_table(pos, Dh, 1e4, (2, 3, 3))
    plain = rope_table(T, Dh, 1e4)
    assert np.array_equal(np.asarray(cos[0]), np.asarray(plain[0]))
    assert np.array_equal(np.asarray(sin[1]), np.asarray(plain[1]))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, 3, Dh), jnp.float32)
    assert np.array_equal(np.asarray(apply_rope(x, cos, sin)),
                          np.asarray(apply_rope(x, *plain)))
    model = Transformer(config_from_hf(HF))
    own = model.rope_for("dsa", T)
    assert np.array_equal(np.asarray(own[0][0]), np.asarray(plain[0]))
    assert np.array_equal(np.asarray(own[2][0]), np.asarray(rope_table(T, 8, 1e4)[0]))


def test_three_different_streams_rotate_as_the_reference(case):
    """Temporal, height and width streams that differ: the program's table by
    section against the reference's rotation, and the whole model's loss and
    gradients under ``position_ids``."""
    T, Dh = SEQ, 16
    rng = np.random.default_rng(7)
    pos = jnp.asarray(np.stack([np.arange(T) + rng.integers(0, 5, (BATCH, T)).cumsum(axis=1) * s
                                for s in (0, 1, 2)]), jnp.int32)
    assert not np.array_equal(np.asarray(pos[0]), np.asarray(pos[1]))
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, T, 3, Dh), jnp.float32)
    cos, sin = mrope_table(pos, Dh, 1e4, (2, 3, 3))
    want = ref.rope(x, 1e4, pos, [2, 3, 3])
    assert float(jnp.abs(apply_rope(x, cos, sin) - want).max()) < 1e-5
    # pair 1 turns by stream 0, pair 2 by stream 1, pair 5 by stream 2
    freqs = 1.0 / (1e4 ** (np.arange(0, Dh, 2) / Dh))
    for pair, stream in ((1, 0), (2, 1), (4, 1), (5, 2), (7, 2)):
        assert np.allclose(np.asarray(cos[..., pair]),
                           np.cos(np.asarray(pos[stream], np.float32) * np.float32(freqs[pair])),
                           atol=1e-5)
    batch = {"input_ids": case["ids"],
             "position_ids": jnp.pad(pos, ((0, 0), (0, 0), (0, 1)))}
    model = case["model"]
    loss, stats = jax.jit(model.loss_and_stats)(case["params"], batch)
    want = jax.jit(lambda w, i, p: ref.loss_parts(w, HF, i, positions=p))(
        case["weights"], case["ids"], pos)
    assert abs(float(loss) - float(want["loss"])) < 1e-5
    assert abs(float(loss) - float(case["ref"]["loss"])) > 5e-5      # the streams matter
    g = driver.flat_tree(jax.jit(jax.grad(lambda p: model.loss(p, batch)))(case["params"]))
    theirs = driver.from_source_names(jax.jit(
        lambda w, i, p: ref.grads(w, HF, i, positions=p))(case["weights"], case["ids"], pos), HF)
    assert max(gaps(g, theirs).values()) < 2e-3


def test_a_section_that_does_not_fill_the_head_is_refused():
    with pytest.raises(ValueError, match="mrope_section"):
        mrope_table(jnp.zeros((3, 1, 4), jnp.int32), 16, 1e4, (2, 3, 2))


# -- the selection -------------------------------------------------------------------------

def lax_topk_mask(scores, valid, k):
    """The issue's rule by ``jax.lax.top_k`` itself, on [queries, keys]."""
    k = min(k, scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & valid


@pytest.mark.parametrize("craft", ["random", "all_equal", "blocks_of_ties", "zeros_and_negatives",
                                   "threshold_shared_by_many", "k_over_valid"])
def test_exactly_min_t_plus_1_k_keys_a_query_with_crafted_ties(craft):
    C, S, k = 24, 40, 7
    rng = np.random.default_rng(0)
    scores = {
        "random": rng.normal(size=(C, S)),
        "all_equal": np.full((C, S), 0.25),
        "blocks_of_ties": np.repeat(rng.normal(size=(C, S // 4)), 4, axis=1),
        "zeros_and_negatives": np.where(rng.random((C, S)) < 0.6, 0.0, -rng.random((C, S))),
        "threshold_shared_by_many": np.where(rng.random((C, S)) < 0.1, 1.0, 0.5),
        "k_over_valid": rng.normal(size=(C, S)),
    }[craft].astype(np.float32)
    if craft == "zeros_and_negatives":
        scores[:, ::7] = -0.0                     # -0.0 and 0.0 are ONE value to top_k
    rows = np.arange(C)[:, None] + (0 if craft == "k_over_valid" else 10)
    valid = jnp.asarray(np.arange(S)[None, :] <= rows)
    # the program's is keys-major: keys along rows, a query a column
    got = jax.jit(lambda s, v: dsa.topk_mask(s.T, v.T, k).T)(jnp.asarray(scores), valid)
    want = lax_topk_mask(jnp.asarray(scores), valid, k)
    held = np.asarray(got).sum(axis=1)
    assert np.array_equal(held, np.minimum(np.asarray(valid).sum(axis=1), k))
    assert np.array_equal(np.asarray(got), np.asarray(want)), craft


def test_minus_zero_and_zero_are_one_value():
    """``jax.lax.top_k`` orders -0.0 and 0.0 as equal (ties to the earlier
    key); the bit order alone would put 0.0 first."""
    scores = jnp.asarray([[-0.0, 0.0, -0.0, 0.0, -1.0]], jnp.float32)
    valid = jnp.ones((1, 5), bool)
    got = dsa.topk_mask(scores.T, valid.T, 2).T
    assert np.array_equal(np.asarray(got), np.asarray(lax_topk_mask(scores, valid, 2)))


def test_select_counts_and_the_chunks_agree_with_one_pass(monkeypatch):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, T, Hi, Di, k = 2, 64, 4, 8, 16
    qi = jax.random.normal(ks[0], (B, T, Hi, Di))
    ki = jax.random.normal(ks[1], (B, T, Di))
    w = jax.random.normal(ks[2], (B, T, Hi))
    mask_t, found = dsa.select(qi, ki, w, k, 0.1)
    monkeypatch.setattr(dsa, "CHUNK", 64)
    whole, _ = dsa.select(qi, ki, w, k, 0.1)
    assert np.array_equal(np.asarray(mask_t), np.asarray(whole)) and mask_t.dtype == jnp.int8
    # keys-major: a query is a column
    assert np.array_equal(np.asarray(mask_t).sum(1), np.broadcast_to(
        np.minimum(np.arange(T) + 1, k), (B, T)))
    assert (int(found["selected_min"]), int(found["selected_max"])) == (k, k)
    assert int(found["pairs"]) == B * arith_dsa.selected_pairs(T, k)
    assert float(found["block_visit_share"]) == 100.0
    assert not np.asarray(mask_t)[:, np.tril_indices(T, -1)[0], np.tril_indices(T, -1)[1]].any()
    # the reference's choice, pair for pair
    scores = jnp.stack([dsa.index_scores(qi[b], ki[b], w[b], 0.1).T for b in range(B)])
    want = ref.choose_keys(scores, ref.causal(jnp.arange(T), T), k)
    assert np.array_equal(np.asarray(mask_t).swapaxes(1, 2) != 0, np.asarray(want))
    # a sequence no longer than topk: the causal mask
    short, found = dsa.select(qi[:, :12], ki[:, :12], w[:, :12], k, 0.1)
    assert np.array_equal(np.asarray(short[0]), np.triu(np.ones((12, 12), np.int8)))
    assert int(found["selected_max"]) == 12
    # what a layer keeps of it between its passes: a bit a pair
    assert dsa._packed(mask_t).shape == (B, T, T // 8)
    assert np.array_equal(np.asarray(dsa._unpacked(dsa._packed(mask_t), T)), np.asarray(mask_t))


def steer_to_the_kernel_route(monkeypatch):
    """The selection's kernel route on the CPU: ``select_route`` answers
    "pallas" and ``sxt_dsa_select`` runs in the interpreter (the indexer's
    scores stay XLA's: float32 inputs)."""
    import functools

    from shuffle_exchange_tpu.ops import dsa_kernels

    monkeypatch.setattr(dsa, "CHUNK", 128)
    monkeypatch.setattr(dsa, "select_route", lambda *a: "pallas")
    for kernel in ("select_chunk", "unwritten"):
        monkeypatch.setattr(dsa_kernels, kernel,
                            functools.partial(getattr(dsa_kernels, kernel), interpret=True))
    return dsa_kernels


@pytest.fixture
def kernel_route(monkeypatch):
    return steer_to_the_kernel_route(monkeypatch)


def crafted_scores(craft, S, C, rng):
    """[S, C] float32, keys-major: ``test_exactly_min_t_plus_1_k_keys...``'s
    crafts and the values the bit order has to get right."""
    normal = rng.normal(size=(S, C))
    scores = {
        "all_equal": np.full((S, C), 0.25),
        "blocks_of_ties": np.repeat(rng.normal(size=(S // 4, C)), 4, axis=0),
        "zeros_and_negatives": np.where(rng.random((S, C)) < 0.6, 0.0, -rng.random((S, C))),
        "threshold_shared_by_many": np.where(rng.random((S, C)) < 0.1, 1.0, 0.5),
        "minus_zero": np.where(rng.random((S, C)) < 0.5, 0.0, -0.0),
        "nan": np.where(rng.random((S, C)) < 0.02, np.nan, normal),
    }.get(craft, normal).astype(np.float32)
    if craft == "zeros_and_negatives":
        scores[::7] = -0.0
    if craft == "nan":
        scores[::5] = np.where(np.isnan(scores[::5]), -np.float32(np.nan), scores[::5])
        assert np.isnan(scores).any() and (np.signbit(scores) & np.isnan(scores)).any()
    return scores


@pytest.mark.parametrize("craft, S, first, k, tied", [
    ("random", 256, 128, 48, False), ("all_equal", 256, 128, 7, True),
    ("blocks_of_ties", 256, 128, 7, True), ("zeros_and_negatives", 256, 128, 7, True),
    ("threshold_shared_by_many", 256, 128, 7, True), ("minus_zero", 256, 128, 48, True),
    ("nan", 256, 128, 48, False), ("dead_rows", 512, 128, 48, False),
    ("dead_rows_tied", 512, 256, 48, True), ("first_chunk", 256, 0, 48, False),
    ("k_over_live", 512, 128, 300, False), ("k_is_the_live_rows", 256, 128, 256, False)])
def test_the_selections_kernel_is_topk_mask_and_lax_top_k(kernel_route, craft, S, first, k, tied):
    """``sxt_dsa_select`` in the interpreter with the tie rule behind it
    (``_search_pallas``), a chunk of 128 queries from ``first`` over S keys in
    row blocks of 128: the mask pair for pair ``topk_mask``'s and
    ``lax.top_k``'s, the rows past the chunk written 0, the other chunks'
    columns left alone, and the counts and the tie flag the mask's own."""
    C, rows = 128, 128
    scores = crafted_scores("all_equal" if craft == "dead_rows_tied" else craft, S, C,
                            np.random.default_rng(1))
    valid = jnp.arange(S)[:, None] <= first + jnp.arange(C)[None, :]
    before = jnp.full((2, S, S), 7, jnp.int8)
    mask_t, held, was_tied = jax.jit(
        lambda s, m: dsa._search_pallas(s, m, 1, first, k))(jnp.asarray(scores), before)
    got = np.asarray(mask_t[1, :, first:first + C])
    oracle = np.asarray(dsa.topk_mask(jnp.asarray(scores), valid, k))
    want = np.asarray(lax_topk_mask(jnp.asarray(scores).T, valid.T, k).T)
    assert np.array_equal(oracle, want), craft
    assert set(np.unique(got)) <= {0, 1} and np.array_equal(got != 0, want), craft
    assert np.array_equal(want.sum(axis=0), np.minimum(np.asarray(valid).sum(axis=0), k))
    assert not got[first + C:].any()                      # rows no query of the chunk sees
    untouched = np.ones(S, bool)
    untouched[first:first + C] = False
    assert (np.asarray(mask_t[0]) == 7).all() and (np.asarray(mask_t[1])[:, untouched] == 7).all()
    assert held.shape == (S // rows, C) and held.dtype == jnp.int32
    assert np.array_equal(np.asarray(held), want.reshape(S // rows, rows, C).sum(axis=1))
    key = dsa._keys(jnp.asarray(scores), valid)
    assert bool(was_tied) == tied == bool(dsa._at_threshold(key, dsa._kth_largest(key, k),
                                                             valid, k)[1])


def test_the_kernels_threshold_is_the_kth_largest_key(kernel_route):
    """What the tie rule starts from: the kernel's threshold in
    ``_sort_key``'s bits, the k-th largest of a query's valid keys (key 0
    where fewer are valid), on scores that hold NaNs of both signs."""
    S, C, first, k = 256, 128, 128, 48
    scores = jnp.asarray(crafted_scores("nan", S, C, np.random.default_rng(2)))
    valid = jnp.arange(S)[:, None] <= first + jnp.arange(C)[None, :]
    _, _, thr = kernel_route.select_chunk(scores, jnp.zeros((1, S, S), jnp.int8), 0, first, k)
    assert thr.dtype == jnp.uint32 and np.array_equal(
        np.asarray(thr), np.asarray(dsa._kth_largest(dsa._keys(scores, valid), k)))
    _, held, thr = kernel_route.select_chunk(scores, jnp.zeros((1, S, S), jnp.int8), 0, first, 300)
    assert not np.asarray(thr).any()
    assert np.array_equal(np.asarray(held).sum(axis=0), np.asarray(valid).sum(axis=0))


@pytest.mark.parametrize("scores", ["continuous", "tied"])
def test_select_on_the_kernel_route_is_select_on_the_xla_route(monkeypatch, scores):
    """Array for array, with ``found`` equal; ``tied_chunks`` counts the
    chunks whose tie rule searched: none on continuous scores, some where the
    indexer's inputs are whole numbers."""
    monkeypatch.setattr(dsa, "CHUNK", 128)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    B, T, Hi, Di, k = 2, 384, 4, 8, 48
    qi = jax.random.normal(ks[0], (B, T, Hi, Di))
    ki = jax.random.normal(ks[1], (B, T, Di))
    w = 1.0 + jax.random.uniform(ks[2], (B, T, Hi))
    if scores == "tied":
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(w)
    else:
        qi = jnp.abs(qi)                       # no score is the relu's exact 0
        ki = jnp.abs(ki)
    run = lambda: jax.jit(lambda *a: dsa.select(*a, k, 0.1))(qi, ki, w)
    assert dsa.select_route(jnp.float32, T, 128) == "xla"            # no TPU here
    mask_x, found_x = run()
    steer_to_the_kernel_route(monkeypatch)
    mask_p, found_p = run()
    assert mask_p.dtype == mask_x.dtype == jnp.int8
    assert np.array_equal(np.asarray(mask_p), np.asarray(mask_x))
    assert set(found_p) == set(found_x) == {"selected_min", "selected_max", "pairs",
                                            "tied_chunks", "block_visit_share"}
    for name in found_x:
        assert found_p[name].dtype == found_x[name].dtype, name
        assert np.array_equal(np.asarray(found_p[name]), np.asarray(found_x[name])), name
    assert int(found_p["pairs"]) == B * arith_dsa.selected_pairs(T, k)
    assert (int(found_p["tied_chunks"]) > 0) == (scores == "tied")
    assert found_p["tied_chunks"].dtype == jnp.int32


def test_the_routes_blocks_and_what_the_kernel_declines():
    from shuffle_exchange_tpu.ops import dsa_kernels

    # the cell's: 256 queries a grid step, 58.7 MB of VMEM, loops of 512 keys
    assert dsa_kernels.select_lanes(16384, 512) == 256
    assert 14 * 16384 * 256 <= dsa_kernels.VMEM_BUDGET_BYTES < dsa_kernels.VMEM_LIMIT_BYTES
    assert dsa_kernels.select_rows(16384, 512) == 512
    assert dsa_kernels.select_lanes(32768, 512) == 128 and dsa_kernels.select_lanes(65536, 512) == 0
    assert dsa_kernels.select_lanes(256, 128) == 128 and dsa_kernels.select_rows(256, 128) == 128
    assert dsa_kernels.select_lanes(64, 16) == 0 and dsa_kernels.select_lanes(16384, 192) == 0


def test_the_packed_copy_holds_query_b_x_t_over_8_plus_j_in_bit_b_of_byte_j():
    """The bit layout of what a layer keeps: eight whole slices of the lane
    axis OR-ed together, no last axis of 8."""
    B, T = 2, 64
    mask = jnp.asarray(np.random.default_rng(5).integers(0, 2, (B, T, T)), jnp.int8)
    packed = dsa._packed(mask)
    assert packed.shape == (B, T, T // 8) and packed.dtype == jnp.int8
    bits = np.asarray(packed).view(np.uint8)
    for b in range(8):
        assert np.array_equal((bits >> b) & 1, np.asarray(mask)[..., b * 8:(b + 1) * 8])
    again = dsa._unpacked(packed, T)
    assert again.dtype == jnp.int8 and np.array_equal(np.asarray(again), np.asarray(mask))
    odd = mask[..., :61]                               # not whole bytes: as it is
    assert dsa._packed(odd) is odd and dsa._unpacked(odd, 61) is odd


def test_the_mixer_alone_on_the_drivers_reading(case):
    """``mechanism_gaps``, the reading the cell's ``correct`` takes of the
    mechanism alone, in float32 at the tiny size: scores, selection, the core
    and the loss under ONE selection, and nothing where no gradient may go."""
    inputs = driver.mixer_inputs(case["params"], HF, 5, BATCH, SEQ, 6.0, jnp.float32)
    got = driver.mechanism_gaps(case["model"], HF, *inputs)
    assert got.pop("leak") == 0.0 and got.pop("select") == 0.0
    assert max(got.values()) < 1e-4, got
    assert {"index", "y", "dx", "kl", "dwq", "dkl_dsa_wq", "dkl_dsa_k_norm_b"} <= set(got)


# -- kernels in the interpreter ------------------------------------------------------------

def test_the_kernels_are_the_xla_forms(monkeypatch):
    """The three Pallas kernels in the interpreter, at blocks of 128 over 256
    positions in bf16, against the chunked XLA forms under one mask: output,
    logsumexp, the three gradients and the head-averaged probabilities."""
    from shuffle_exchange_tpu.ops import dsa_kernels

    monkeypatch.setattr(dsa, "CHUNK", 128)
    monkeypatch.setattr(dsa_kernels, "_pick_block", lambda n, itemsize=2: 128)
    B, T, H, KV, D, k = 1, 256, 4, 2, 128, 48
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    dt = jnp.bfloat16
    q = (3 * jax.random.normal(ks[0], (B, T, H, D))).astype(dt)
    kk, v = (jax.random.normal(key, (B, T, KV, D)).astype(dt) for key in ks[1:3])
    qi, ki, w = (jax.random.normal(ks[3], (B, T, 2, 16)), jax.random.normal(ks[4], (B, T, 16)),
                 jax.random.normal(ks[5], (B, T, 2)))
    mask, _ = dsa.select(qi, ki, w, k, 0.1)
    cot = jax.random.normal(ks[6], (B, T, H, D)).astype(dt)
    (ox, lx), back_x = jax.vjp(lambda *a: dsa.core_xla(*a, mask), q, kk, v)
    (op, lp), back_p = jax.vjp(lambda *a: dsa_kernels.core(*a, mask, interpret=True), q, kk, v)
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    assert rel(op, ox) < 5e-3 and rel(lp, lx) < 1e-6
    for a, b in zip(back_p((cot, jnp.zeros_like(lp))), back_x((cot, jnp.zeros_like(lx)))):
        assert rel(a, b) < 8e-3
    p = dsa_kernels.head_mean(q, kk, lx, mask, interpret=True)
    want = dsa.head_mean_xla(q[0], kk[0], lx[0], 0, T)[None]
    assert rel(jnp.where(mask != 0, p, 0.0), jnp.where(mask != 0, want, 0.0)) < 1e-5
    # the indexer's scores of a chunk of 128 queries and their backward
    qc, wc = qi[0, 128:].astype(dt), w[0, 128:].astype(dt)
    kc, g = ki[0].astype(dt), jax.random.normal(ks[6], (T, 128))
    xla = lambda q, k, w: dsa.index_scores(q, k, w, 0.1)
    pal = lambda q, k, w: dsa_kernels.index_scores(q, k, w, 0.1, 128, interpret=True)
    (sx, back_x), (sp, back_p) = jax.vjp(xla, qc, kc, wc), jax.vjp(pal, qc, kc, wc)
    assert sp.shape == (T, 128) and rel(sp, sx) < 1e-6
    for a, b in zip(back_p(g), back_x(g)):
        assert rel(a, b) < 8e-3
    # keys past the chunk's last query are not scored
    early = dsa_kernels.index_scores(qi[0, :128].astype(dt), kc, w[0, :128].astype(dt), 0.1, 0,
                                     interpret=True)
    assert float(jnp.abs(early[128:]).max()) == 0.0 and rel(early[:128], dsa.index_scores(
        qi[0, :128].astype(dt), kc, w[0, :128].astype(dt), 0.1)[:128]) < 1e-6
    assert dsa.route(q, kk, T) == "xla"                 # no TPU here
    assert dsa_kernels.fits(16384, 128) and not dsa_kernels.fits(16384 + 64, 128)


# -- serving refuses, and what else is not written -----------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_kind_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="learned sparse attention .mixer 'dsa'"):
        cls(case["model"], case["params"])


def test_a_dense_or_parallel_block_around_the_mixer_is_refused():
    cfg = dataclasses.replace(config_from_hf(HF), layer_pattern=(("dsa", "mlp"),), n_experts=0)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="routed experts"):
        model.loss(params, {"input_ids": jnp.zeros((1, 9), jnp.int32)})


def test_position_ids_without_sections_are_refused():
    model = Transformer(dataclasses.replace(config_from_hf(HF), mrope_section=(),
                                            layer_pattern=(("attn", "moe"),)))
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="position_ids"):
        model.loss(params, {"input_ids": jnp.zeros((1, 9), jnp.int32),
                            "position_ids": jnp.zeros((3, 1, 9), jnp.int32)})


def test_the_two_reference_copies_are_byte_identical():
    read = lambda path: open(os.path.join(ROOT, path), "rb").read()
    body = read("chipbench/reference_keyevl2.py")
    assert body == read("shuffle_exchange_tpu/models/reference_keyevl2.py")
    code = body.decode().split('"""', 2)[2]
    assert "import shuffle_exchange_tpu" not in code and "from shuffle_exchange_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    assert "top_k" in code and "stop_gradient" in code


def test_the_catalog_rows_numbers_are_in_the_file():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Keye-VL-2.0-30B-A3B"' in line)
    src = cell_source()
    assert src["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
