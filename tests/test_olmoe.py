"""OLMoE (``model_type: olmoe``) through the normal path against the plain
reference (``models/reference_olmoe.py``), at a tiny OLMoE-shaped size on the
CPU: 2 layers, hidden 128, 4 heads, 8 experts top-3 of width 64, vocabulary
256, 32 positions. The weights are seeded under the SOURCE's names and reach
the system through ``params_from_state_dict``, so the state-dict mapping is
part of what is compared.

Tolerances. float32 against float32: 1e-4 of the reference's largest
magnitude (per tensor). Both sides compute the same equations in float32; they
differ only in the order of float32 additions (stacked einsums and a scan here,
a Python loop over experts with masks there; iterative argmax against top_k),
which measures under 1e-6 at this size, while each of the three wrong models
below (no q/k norm, renormalised routing weights, first-choice aux) is off by
0.2 or more on what it changes (the logits, or the aux loss). bf16 against float32: within twice the band the REFERENCE itself
moves when it is run in bf16, measured in the test, not written down.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models import reference_olmoe as ref
from shuffle_exchange_tpu.models.hf import config_from_hf, params_from_state_dict

TOL = 1e-4          # float32 reorderings only, see the module docstring
MARGIN = 1e-3       # routing is compared where the k-th and (k+1)-th differ by more
HF = {"model_type": "olmoe", "architectures": ["OlmoeForCausalLM"],
      "hidden_size": 128, "intermediate_size": 64, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
      "num_experts_per_tok": 3, "norm_topk_prob": False, "vocab_size": 256,
      "max_position_embeddings": 64, "rms_norm_eps": 1e-5, "rope_theta": 10000,
      "tie_word_embeddings": False, "clip_qkv": None, "attention_bias": False,
      "router_aux_loss_coef": 0.01}
SEQ, BATCH = 32, 2


def to_system(weights, cfg):
    """Source-named weights (or gradients: the mapping is linear) -> the
    system's stacked tree."""
    return params_from_state_dict({k: np.asarray(v) for k, v in weights.items()},
                                  cfg, "olmoe")


@pytest.fixture(scope="module")
def case():
    cfg = config_from_hf(HF)
    weights = ref.init_weights(HF, seed=7)
    ids = np.random.default_rng(3).integers(0, HF["vocab_size"],
                                            (BATCH, SEQ + 1)).astype(np.int32)
    parts = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(weights, ids)
    return {"cfg": cfg, "weights": weights, "params": to_system(weights, cfg),
            "ids": ids, "ref": parts}


def rel_err(got, want):
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / max(np.max(np.abs(want)), 1e-30))


def system_parts(cfg, params, ids, dtype=None):
    """(logits, ce, aux, expert_tokens) of the system, CE and aux separated
    by running the loss with the aux coefficient at 0 and at 1."""
    model = Transformer(cfg)
    if dtype is not None:
        params = jax.tree.map(lambda p: jnp.asarray(p, dtype), params)
    batch = {"input_ids": ids}
    logits = jax.jit(model.apply)(params, ids[:, :-1])
    ce = jax.jit(Transformer(dataclasses.replace(cfg, aux_loss_coef=0.0)).loss)(
        params, batch)
    both, stats = jax.jit(Transformer(dataclasses.replace(
        cfg, aux_loss_coef=1.0)).loss_and_stats)(params, batch)
    return (np.asarray(logits, np.float32), float(ce), float(both) - float(ce),
            np.asarray(stats["moe_expert_tokens"]))


# -- the family's configuration --------------------------------------------


def test_config_from_hf_reads_the_family():
    cfg = config_from_hf(HF)
    assert (cfg.qk_norm, cfg.moe_aux) == (True, "all_choices")
    assert (cfg.n_experts, cfg.moe_top_k, cfg.ff_dim) == (8, 3, 64)
    assert cfg.moe_norm_topk is False and cfg.aux_loss_coef == 0.01
    assert cfg.norm == "rmsnorm" and cfg.position == "rope" and not cfg.tie_embeddings
    # the source routes without drops: the family, not a user, picks the
    # dropless path, which resolve_moe_impl passes through in every context
    assert cfg.moe_impl == "ragged"


@pytest.mark.parametrize("key,value", [("clip_qkv", 8.0), ("attention_bias", True)])
def test_config_from_hf_refuses_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value})


@pytest.mark.parametrize("rows,k", [(16, 1), (16, 3), (40, 8), (1, 8)])
def test_the_hand_written_backward_of_the_row_permutation_is_the_gathers_own(rows, k):
    """``moe/layer._permuted_rows`` replaces XLA's scatter-add transpose of
    the dispatch gather by a gather through the inverse permutation and a sum
    over each row's k copies: same cotangent, float32, to the last bit but
    for the order of the k-term sum."""
    from shuffle_exchange_tpu.moe.layer import _permuted_rows

    rng = np.random.default_rng(rows * 31 + k)
    x = jnp.asarray(rng.standard_normal((rows, 8)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((rows * k, 8)), jnp.float32)
    perm = jnp.asarray(rng.permutation(rows * k), jnp.int32)
    inverse = jnp.argsort(perm).astype(jnp.int32)
    ours, vjp = jax.vjp(lambda v: _permuted_rows(v, perm, inverse, k), x)
    plain, plain_vjp = jax.vjp(lambda v: jnp.take(v, perm // k, axis=0), x)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(plain))
    np.testing.assert_allclose(np.asarray(vjp(cot)[0]), np.asarray(plain_vjp(cot)[0]),
                               rtol=1e-6, atol=1e-6)


# -- float32 against the reference -------------------------------------------


def test_logits_match_the_reference_in_float32(case):
    logits, *_ = system_parts(case["cfg"], case["params"], case["ids"])
    assert rel_err(logits, case["ref"]["logits"]) < TOL


def test_ce_and_aux_match_the_reference_separately(case):
    _, ce, aux, _ = system_parts(case["cfg"], case["params"], case["ids"])
    assert abs(ce - float(case["ref"]["ce"])) < TOL * float(case["ref"]["ce"])
    assert abs(aux - float(case["ref"]["aux"])) < TOL * float(case["ref"]["aux"])
    # two layers: the cross-layer product of means is NOT the mean of the
    # layers' own products, so this also holds the all-layers form
    per_layer = np.mean([float(ref.balancing_loss([r], HF))
                         for r in case["ref"]["routing"]])
    assert abs(per_layer - float(case["ref"]["aux"])) > 10 * TOL * aux


@pytest.mark.parametrize("remat", [False, True], ids=["scan", "scan+remat"])
def test_loss_and_gradients_match_the_reference(case, remat):
    cfg = dataclasses.replace(case["cfg"], remat=remat)
    batch = {"input_ids": case["ids"]}
    loss, grads = jax.jit(jax.value_and_grad(Transformer(cfg).loss))(
        case["params"], batch)
    assert abs(float(loss) - float(case["ref"]["loss"])) < TOL * float(case["ref"]["loss"])
    want = to_system(jax.jit(lambda w, i: ref.grads(w, HF, i))(
        case["weights"], case["ids"]), cfg)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    checked = 0
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        if path not in flat_want or not np.any(flat_want[path]):
            continue                    # rmsnorm's unused bias slot
        assert rel_err(g, flat_want[path]) < TOL, jax.tree_util.keystr(path)
        checked += 1
    assert checked == 15     # embed, head, final norm, 12 stacked layer leaves


def test_expert_counts_match_and_nothing_is_dropped(case):
    *_, counts = system_parts(case["cfg"], case["params"], case["ids"])
    want = np.asarray(case["ref"]["expert_tokens"])
    tokens = BATCH * SEQ
    assert counts.shape == (2, 8) and counts.dtype == np.int32
    assert (counts.sum(axis=1) == tokens * 3).all()          # dropless
    # a token whose 3rd and 4th probabilities are within MARGIN may choose
    # either under float32 reordering: each moves two counts by one
    close = sum(int(np.sum(np.sort(np.asarray(r["p"]), axis=1)[:, -3]
                           - np.sort(np.asarray(r["p"]), axis=1)[:, -4] < MARGIN))
                for r in case["ref"]["routing"])
    assert np.abs(counts - want).sum() <= 2 * close


def test_the_routing_rule_picks_the_references_experts(case):
    """The system's one selection rule (iterative argmax) on the reference's
    own router probabilities: identical expert SETS wherever the reference's
    k-th and (k+1)-th probabilities differ by more than MARGIN, and the raw
    (not renormalised) probabilities as weights."""
    from shuffle_exchange_tpu.moe.gating import topk_select

    compared = 0
    for r in case["ref"]["routing"]:
        p = np.asarray(r["p"])
        idx, w, _, _ = topk_select(jnp.log(p), 3, normalize_weights=False)
        srt = np.sort(p, axis=1)
        clear = srt[:, -3] - srt[:, -4] > MARGIN
        got = np.sort(np.asarray(idx), axis=1)[clear]
        assert (got == np.sort(np.asarray(r["chosen"]), axis=1)[clear]).all()
        np.testing.assert_allclose(np.sort(np.asarray(w), axis=1)[clear],
                                   srt[clear][:, -3:], rtol=1e-5)
        compared += int(clear.sum())
    assert compared > 100


# -- bf16 against the band of the reference in bf16 ---------------------------


def test_bf16_system_stays_inside_the_references_own_bf16_band(case):
    low = jax.jit(lambda w, i: ref.loss_parts(w, HF, i, jnp.bfloat16))(
        case["weights"], case["ids"])
    band_logits = rel_err(low["logits"], case["ref"]["logits"])
    band_loss = abs(float(low["loss"]) - float(case["ref"]["loss"]))
    assert band_logits > 10 * TOL        # the band is real: bf16 is not float32
    logits, ce, aux, _ = system_parts(case["cfg"], case["params"], case["ids"],
                                      jnp.bfloat16)
    assert rel_err(logits, case["ref"]["logits"]) < 2 * band_logits
    assert abs(ce + 0.01 * aux - float(case["ref"]["loss"])) < max(2 * band_loss, 5e-3)


# -- a skewed router -----------------------------------------------------------


@pytest.fixture(scope="module")
def skewed():
    """Feature 0 of the residual stream is one large constant for every token
    (set in the embedding, written by no projection) and expert 0's router
    row reads it: in both layers over half of the tokens choose expert 0."""
    cfg = config_from_hf(HF)
    weights = dict(ref.init_weights(HF, seed=11))
    embed = weights["model.embed_tokens.weight"] * 50.0          # O(1) rows
    weights["model.embed_tokens.weight"] = embed.at[:, 0].set(3.0)
    for name in list(weights):
        if name.endswith(("o_proj.weight", "down_proj.weight")):
            weights[name] = weights[name].at[0, :].set(0.0)
        if name.endswith("mlp.gate.weight"):
            weights[name] = weights[name].at[0, 0].set(2.0)
    ids = np.random.default_rng(5).integers(0, 256, (BATCH, SEQ + 1)).astype(np.int32)
    parts = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(weights, ids)
    return {"cfg": cfg, "weights": weights, "params": to_system(weights, cfg),
            "ids": ids, "ref": parts}


def test_a_skewed_router_still_drops_nothing_and_still_matches(skewed):
    want = np.asarray(skewed["ref"]["expert_tokens"])
    assert (want[:, 0] > BATCH * SEQ // 2).all(), want      # the skew is real
    logits, ce, aux, counts = system_parts(skewed["cfg"], skewed["params"],
                                           skewed["ids"])
    assert (counts.sum(axis=1) == BATCH * SEQ * 3).all()
    assert np.abs(counts - want).sum() <= 4
    assert rel_err(logits, skewed["ref"]["logits"]) < TOL
    assert abs(ce - float(skewed["ref"]["ce"])) < TOL * ce
    assert abs(aux - float(skewed["ref"]["aux"])) < TOL * aux


def test_dropping_at_capacity_under_the_skew_fails_the_comparison(skewed):
    """What the model would compute on the capacity path (GShard, factor
    1.25): expert 0 overflows, tokens drop, the logits leave the tolerance."""
    cfg = dataclasses.replace(skewed["cfg"], moe_impl="capacity",
                              capacity_factor=1.25)
    logits, _, _, counts = system_parts(cfg, skewed["params"], skewed["ids"])
    assert (counts.sum(axis=1) < BATCH * SEQ * 3).all()
    assert rel_err(logits, skewed["ref"]["logits"]) > 100 * TOL


# -- three wrong models, each of which must fail -------------------------------


@pytest.mark.parametrize("change,what", [
    ({"qk_norm": False}, "logits"),
    ({"moe_norm_topk": True}, "logits"),
    ({"moe_aux": "first_choice"}, "aux"),
], ids=["no-qk-norm", "renormalised-weights", "first-choice-aux"])
def test_a_wrong_model_fails_the_comparison(case, change, what):
    cfg = dataclasses.replace(case["cfg"], **change)
    logits, _, aux, _ = system_parts(cfg, case["params"], case["ids"])
    if what == "logits":
        assert rel_err(logits, case["ref"]["logits"]) > 100 * TOL
    else:
        assert rel_err(logits, case["ref"]["logits"]) < TOL    # same forward
        assert abs(aux - float(case["ref"]["aux"])) > 100 * TOL * float(case["ref"]["aux"])


@pytest.mark.parametrize("factor", [0.05, 1.25, 8.0])
def test_capacity_factor_has_no_effect(case, factor):
    batch = {"input_ids": case["ids"]}
    base = jax.jit(Transformer(case["cfg"]).loss)(case["params"], batch)
    other = jax.jit(Transformer(dataclasses.replace(
        case["cfg"], capacity_factor=factor)).loss)(case["params"], batch)
    assert float(base) == float(other)


# -- the trainer ----------------------------------------------------------------


def test_the_trainer_runs_it_and_hands_out_the_expert_counts(case):
    """config_from_hf -> Transformer -> sxt.initialize(...).train_batch: the
    step's expert counts come out as a device array (no sync in the step),
    are the reference's on the first step, and the loss falls."""
    rows = 8                                  # one per device of the test mesh
    ids = np.random.default_rng(9).integers(0, 256, (rows, SEQ + 1)).astype(np.int32)
    want = jax.jit(lambda w, i: ref.loss_parts(w, HF, i))(case["weights"], ids)
    engine = sxt.initialize(
        model=Transformer(case["cfg"]), params=case["params"],
        config={"train_batch_size": rows, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}}, seed=0)[0]
    assert engine.last_step_stats() == {}
    batch = {"input_ids": ids}
    first = float(engine.train_batch(batch))
    counts = engine.last_step_stats()["moe_expert_tokens"]
    assert isinstance(counts, jax.Array) and counts.dtype == jnp.int32
    assert np.abs(np.asarray(counts) - np.asarray(want["expert_tokens"])).sum() <= 4
    assert abs(first - float(want["loss"])) < TOL * first
    for _ in range(4):
        last = float(engine.train_batch(batch))
    assert last < first
    assert int(np.asarray(engine.last_step_stats()["moe_expert_tokens"]).sum()) \
        == 2 * rows * SEQ * 3


def test_serving_refuses_a_qk_norm_model_instead_of_being_wrong(case):
    """Serving OLMoE is not this PR's: the inference engines' attention has
    no q/k norm, so they must refuse the model, not serve it without."""
    from shuffle_exchange_tpu.inference import InferenceConfig, InferenceEngine

    with pytest.raises(NotImplementedError, match="q/k RMSNorm"):
        InferenceEngine(Transformer(case["cfg"]), case["params"], InferenceConfig())


# -- the benchmark's copy --------------------------------------------------------


def test_the_benchmarks_reference_is_this_one_byte_for_byte():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        with open(os.path.join(root, path), "rb") as f:
            src = f.read()
        end = src.index(b'"""', src.index(b'"""') + 3) + 3     # the docstring
        return src[end:]

    mine = body("shuffle_exchange_tpu/models/reference_olmoe.py")
    theirs = body("chipbench/reference_olmoe.py")
    assert mine == theirs and len(mine) > 5000
    assert b"shuffle_exchange_tpu" not in mine.replace(
        b"nothing imported from shuffle_exchange_tpu", b"")
