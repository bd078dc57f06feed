"""Ouro-2.6B (``model_type: ouro``, a looped language model) through the normal
path against the plain reference (``models/reference_ouro.py``), at a tiny size
on the CPU: hidden 64, 4 heads of 16, 3 sandwich-normed layers run 4 times over
the same weights, the final norm inside the loop, the exit gate, the loss at
all four exits, vocabulary 256, 32 positions. The weights are drawn by
``Transformer.init`` (gains and gate redrawn, as the cell's driver does) and
reach the reference through the driver's own mapping
(``chipbench/drivers/train_steps_loop.py``), so that mapping is part of what is
compared.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions. Loss and the exits' numbers 1e-5; gradients
2e-3 of each leaf's norm.
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import shuffle_exchange_tpu as sxt  # noqa: E402
from chipbench import arith_loop  # noqa: E402
from chipbench.drivers import train_steps_loop as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import hf  # noqa: E402
from shuffle_exchange_tpu.models import reference_ouro as ref  # noqa: E402
from shuffle_exchange_tpu.models import transformer as tr  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402
from shuffle_exchange_tpu.profiling import trace  # noqa: E402

HF = {"model_type": "ouro", "hidden_size": 64, "num_attention_heads": 4,
      "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
      "hidden_act": "silu", "num_hidden_layers": 3, "vocab_size": 256,
      "max_position_embeddings": 1024, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
      "rope_scaling": None, "tie_word_embeddings": False, "total_ut_steps": 4,
      "early_exit_threshold": 1, "use_sliding_window": False, "sliding_window": None,
      # whole, as the published file has it: the first num_hidden_layers count
      "layer_types": ["full_attention"] * 6}
SEQ, BATCH = 32, 2


def gaps(ours, theirs):
    return {k: float(np.linalg.norm(np.asarray(ours[k]) - np.asarray(theirs[k]))
                     / np.linalg.norm(np.asarray(theirs[k]))) for k in theirs}


def cell_source():
    from chipbench import harness

    return harness.load_cell("ouro-train")["config"]


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


def chunked(model, positions=8):
    """The same model with a loss that chunks (``_head_scan``'s path)."""
    return Transformer(dataclasses.replace(model.config, loss_chunk=positions))


# -- the configuration -------------------------------------------------------------

def test_config_from_hf_on_the_cells_own_file():
    """The catalog row's keys, as the cell's configuration file has them: the
    published widths, the cut, and the counts re-derived from shapes."""
    src = cell_source()
    cfg = config_from_hf(src)
    assert cfg.pattern == (("attn", "mlp"),) and not cfg.several_kinds
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.ff_dim) == (
        2048, 16, 16, 128, 5632)
    assert (cfg.n_layers, cfg.vocab_size, cfg.loop_steps) == (12, 12288, 4)
    assert (cfg.norm, cfg.norm_order, cfg.norm_eps, cfg.activation) == (
        "rmsnorm", "sandwich", 1e-6, "swiglu")
    assert (cfg.position, cfg.rope_theta, cfg.rotary_dims) == ("rope", 1e6, 128)
    assert cfg.exit_gate and cfg.exit_entropy_coef == 0.1 and not cfg.tie_embeddings
    model = Transformer(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the ISSUE's count, plus the unused bias leaves of the plain RMSNorms
    # (ln1_b and ln2_b a layer, ln_f_b)
    counts = src["counts"]
    assert n == 666_996_737 + (2 * 12 + 1) * 2048
    assert counts["parameters"] == 666_996_737 == arith_loop.parameters(src)
    assert counts["block"] == arith_loop.block_parameters(src) == 51_388_416
    assert counts["block_matmul"] + counts["block_norms"] == counts["block"]
    assert counts["layers"] + counts["embedding_and_head"] + counts["final_norm"] \
        + counts["exit_gate"] == counts["parameters"]
    assert src["published"]["parameters"] == 2_667_974_657 == arith_loop.parameters(
        src, layers=48, vocab=49152)
    assert counts["layer_visits"] == arith_loop.layer_visits(cfg, 4) == 48
    assert counts["matmul_parameters_per_token"] == arith_loop.matmul_params_per_token(cfg, 4)
    # the issue's arithmetic: 15.40 + 4.83 GFLOP a token at 8,192
    flops = arith_loop.train_flops_per_token(cfg, 8192, 4)
    assert abs(flops - 20.2e9) < 0.05e9
    assert abs(arith_loop.core_flops_per_step(cfg, 4, 1, 8192) / 8192 - 4.83e9) < 0.01e9


def test_the_catalog_rows_numbers_are_in_the_file():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Ouro-2.6B"' in line)
    src = cell_source()
    assert src["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if src.get(k) != v}
    assert differ == {"num_hidden_layers", "vocab_size"}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b-train")
    assert set(entry["reduced"]) == differ and entry["source"] == row["source_url"]
    assert {"sandwich_norms", "attention_bias", "exit_gate", "loss", "exit_entropy_beta",
            "final_norm_in_loop", "early_exit_threshold", "sequence_length", "lr_schedule",
            "weights", "deployment"} <= set(src["assumed"])


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("layer_types", ["full_attention", "sliding_attention", "full_attention"])])
def test_what_is_not_written_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value})


def test_the_name_map_goes_both_ways(case):
    """``ouro_state_dict`` -> ``params_from_state_dict`` is the identity, and
    the driver's own mapping gives the same names and tensors."""
    named = hf.ouro_state_dict(case["params"], case["cfg"])
    assert set(named) == set(ref.weight_shapes(HF))
    assert all(named[k].shape == shape for k, shape in ref.weight_shapes(HF).items())
    assert all(np.array_equal(named[k], case["weights"][k]) for k in named)
    back = hf.params_from_state_dict(named, case["cfg"], "ouro")
    flat_back, flat = driver.flat_tree(back), driver.flat_tree(case["params"])
    assert set(flat_back) == set(flat)
    assert all(np.array_equal(flat_back[k], flat[k]) for k in flat)
    model, params = hf.from_hf((HF, named))
    assert model.config == case["cfg"]
    assert float(model.loss(params, {"input_ids": case["ids"]})) == pytest.approx(
        float(case["ref"]["loss"]), abs=1e-5)


# -- program against reference ---------------------------------------------------------

@pytest.mark.parametrize("loss_path", ["full_logits", "chunked"])
def test_the_loss_and_every_exit_equal_the_reference(case, loss_path):
    model = case["model"] if loss_path == "full_logits" else chunked(case["model"])
    loss, stats = jax.jit(model.loss_and_stats)(case["params"], {"input_ids": case["ids"]})
    want = case["ref"]
    assert abs(float(loss) - float(want["loss"])) < 1e-5
    assert np.allclose(stats["loop_exit_ce"], want["exit_ce"], atol=1e-5)
    assert np.allclose(stats["loop_exit_mass"], want["exit_mass"], atol=1e-6)
    assert abs(float(stats["loop_exit_mass"].sum()) - 1.0) < 1e-6
    assert abs(float(stats["loop_exit_entropy"]) - float(want["entropy"])) < 1e-5
    assert abs(float(stats["loop_expected_steps"]) - float(want["expected_steps"])) < 1e-5
    assert int(stats["loop_layer_visits"]) == 4 * 3
    if loss_path == "chunked":
        # the head read every exit: T x B x S rows in S / 8 trips
        assert int(stats["loss_rows"]) == 4 * BATCH * SEQ
        assert int(stats["loss_chunks"]) == SEQ // 8
    # the gate is not idle at these weights: every exit holds mass
    assert float(stats["loop_exit_mass"].min()) > 0.02


@pytest.mark.parametrize("loss_path", ["full_logits", "chunked"])
def test_every_leafs_gradient_equals_the_reference(case, loss_path):
    model = case["model"] if loss_path == "full_logits" else chunked(case["model"])
    g = driver.flat_tree(jax.jit(jax.grad(model.loss))(case["params"],
                                                      {"input_ids": case["ids"]}))
    found = gaps(g, case["ref_grads"])
    assert set(found) == {"embed", "ln_f_w", "unembed", "exit_gate_w", "exit_gate_b"} | {
        "layers/" + leaf for leaf in driver._BLOCK}
    assert max(found.values()) < 2e-3, found


def test_remat_changes_no_gradient(case):
    cfg = dataclasses.replace(case["cfg"], remat=True, remat_policy="full", loss_chunk=8)
    batch = {"input_ids": case["ids"]}
    g = driver.flat_tree(jax.jit(jax.grad(Transformer(cfg).loss))(case["params"], batch))
    assert max(gaps(g, case["ref_grads"]).values()) < 2e-3


def test_without_a_gate_the_loss_is_the_last_exits(case):
    cfg = dataclasses.replace(case["cfg"], exit_gate=False, exit_entropy_coef=0.0)
    params = {k: v for k, v in case["params"].items() if not k.startswith("exit_gate")}
    batch = {"input_ids": case["ids"]}
    loss, stats = Transformer(cfg).loss_and_stats(params, batch)
    assert abs(float(loss) - float(case["ref"]["exit_ce"][-1])) < 1e-5
    assert "loop_exit_mass" not in stats and int(stats["loop_layer_visits"]) == 12
    looped = Transformer(dataclasses.replace(cfg, loss_chunk=8)).loss_and_stats(params, batch)
    assert abs(float(looped[0]) - float(loss)) < 1e-6
    assert int(looped[1]["loss_rows"]) == BATCH * SEQ       # one exit's rows
    assert "exit_gate_w" not in jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))


def test_one_step_without_a_gate_is_the_plain_model_bit_for_bit(case):
    """``loop_steps`` 1 is every model before the loop existed: the same
    traced program as a configuration that never names the fields."""
    fields = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=96,
                  activation="swiglu", norm="rmsnorm", position="rope",
                  tie_embeddings=False, loss_chunk=8)
    plain = Transformer(tr.TransformerConfig(**fields))
    named = Transformer(tr.TransformerConfig(**fields, loop_steps=1, exit_gate=False,
                                             exit_entropy_coef=0.0))
    params = plain.init(jax.random.PRNGKey(0))
    batch = {"input_ids": case["ids"]}
    text = lambda m: str(jax.make_jaxpr(jax.value_and_grad(m.loss))(params, batch))
    assert text(plain) == text(named)
    assert "loop" not in text(plain)
    assert float(plain.loss(params, batch)) == float(named.loss(params, batch))


def test_two_steps_are_a_plain_stack_of_twice_the_layers_with_the_norm_between(case):
    """T = 2 over L layers = a plain 2L-layer model whose halves hold the same
    weights, with the final norm inserted between: the loop is the unlooped
    mathematics."""
    cfg = dataclasses.replace(case["cfg"], loop_steps=2, exit_gate=False,
                              exit_entropy_coef=0.0)
    params = {k: v for k, v in case["params"].items() if not k.startswith("exit_gate")}
    ids = case["ids"][:, :-1]
    looped = Transformer(cfg).apply(params, ids)
    plain = Transformer(dataclasses.replace(cfg, loop_steps=1, n_layers=6))
    twice = {**params, "layers": jax.tree.map(lambda a: jnp.concatenate([a, a]),
                                              params["layers"])}
    x, rope = plain.embed(twice, ids)
    half = lambda x, rows: plain.stack_apply(
        jax.tree.map(lambda a: a[rows], twice["layers"]), x, rope)[0]
    x = half(x, slice(0, 3))
    x = tr._norm(x, params["ln_f_w"], params["ln_f_b"], "rmsnorm", eps=cfg.norm_eps)
    logits = plain.head(twice, half(x, slice(3, 6)))
    assert np.allclose(looped, logits, atol=1e-5)
    # and the exits: the first is the first half's, normed
    exits, _ = Transformer(cfg).loop_apply(params, plain.embed(twice, ids)[0], rope)
    assert exits.shape == (2,) + x.shape and np.allclose(exits[0], x, atol=1e-5)


def test_the_sandwich_has_four_gains_and_each_one_counts(case):
    layers = case["params"]["layers"]
    assert {"ln1_w", "ln1_post_w", "ln2_w", "ln2_post_w"} <= set(layers)
    assert "ln1_post_b" not in layers
    base = float(case["model"].loss(case["params"], {"input_ids": case["ids"]}))
    for name in ("ln1_w", "ln1_post_w", "ln2_w", "ln2_post_w"):
        neutral = {**case["params"], "layers": {**layers, name: jnp.ones_like(layers[name])}}
        assert abs(float(case["model"].loss(neutral, {"input_ids": case["ids"]})) - base) > 2e-6


def test_a_saturated_gate_gives_a_finite_loss_and_gradient(case):
    params = {**case["params"], "exit_gate_b": jnp.asarray(200.0)}
    batch = {"input_ids": case["ids"]}
    loss, stats = case["model"].loss_and_stats(params, batch)
    assert np.isfinite(float(loss)) and abs(float(stats["loop_exit_mass"][0]) - 1.0) < 1e-6
    g = jax.grad(case["model"].loss)(params, batch)
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(g))


# -- the head's scan with weights on its rows --------------------------------------------

@pytest.mark.parametrize("normed", [True, False], ids=["normed_rows", "norm_in_scan"])
@pytest.mark.parametrize("biased", [False, True], ids=["no_bias", "bias"])
def test_the_head_scan_with_row_weights_is_autodiff_of_the_full_logits(normed, biased):
    """(sum of weight x nll, count, the rows' nll) and the gradients of x, the
    norm, the head (through its float32 stand-in), the bias AND the weights
    against plain autodiff of norm -> logits -> log-softmax."""
    n, B, c, D, V = 3, 2, 4, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    xc = jax.random.normal(ks[0], (n, B, c, D))
    lc = jax.random.randint(ks[1], (n, B, c), 0, V).at[0, 0, 1].set(-100)
    wc = jax.random.uniform(ks[2], (n, B, c), minval=0.1, maxval=1.0)
    w = jax.random.normal(ks[3], (D, V)) * 0.3
    ln_w = jax.random.uniform(ks[4], (D,), minval=0.5, maxval=1.5)
    ln_b = jnp.zeros((D,))
    extra = jax.random.normal(ks[5], (V,)) if biased else None
    ln = (None, None) if normed else (ln_w, ln_b)

    def scanned(xc, ln, w_acc, extra, wc):
        total, cnt, rows = tr._head_scan("rmsnorm", 1e-6, biased, *ln, w, w_acc, extra,
                                         xc, lc, wc=wc)
        return total, (cnt, rows)

    def plain(xc, ln, w, extra, wc):
        xn = xc if normed else tr._norm(xc, *ln, "rmsnorm", eps=1e-6)
        logits = xn @ w + (0.0 if extra is None else extra)
        nll, mask = Transformer.token_nll(logits, lc)
        return (wc * nll).sum(), (mask.sum(), nll)

    args = (xc, ln, jnp.zeros_like(w), extra, wc)
    (total, (cnt, rows)), g = jax.value_and_grad(scanned, argnums=(0, 1, 2, 3, 4),
                                                 has_aux=True)(*args)
    (want, (want_cnt, want_rows)), gw = jax.value_and_grad(
        plain, argnums=(0, 1, 2, 3, 4), has_aux=True)(xc, ln, w, extra, wc)
    assert abs(float(total) - float(want)) < 1e-4 and int(cnt) == int(want_cnt) == n * B * c - 1
    assert np.allclose(rows, want_rows, atol=1e-5) and float(rows[0, 0, 1]) == 0.0
    for ours, theirs in zip(jax.tree.leaves(g), jax.tree.leaves(gw)):
        assert np.allclose(ours, theirs, atol=2e-5)
    # the weights' cotangent is the rows' own loss
    assert np.allclose(g[4], want_rows, atol=1e-5)
    # not under grad: the plain scan gives the same three
    assert abs(float(scanned(*args)[0]) - float(want)) < 1e-4


def test_without_row_weights_the_head_scan_is_the_parents_program():
    """The plain path's traced text holds no trace of the new branches: no
    fourth operand, no second output of the scan."""
    n, B, c, D, V = 2, 1, 4, 8, 16
    xc, lc = jnp.ones((n, B, c, D)), jnp.zeros((n, B, c), jnp.int32)
    w, ln = jnp.ones((D, V)), (jnp.ones((D,)), jnp.zeros((D,)))
    plain = lambda xc, w_acc: tr._head_scan("rmsnorm", 1e-6, False, *ln, w, w_acc, None,
                                            xc, lc)[0]
    text = str(jax.make_jaxpr(jax.grad(plain, argnums=(0, 1)))(xc, jnp.zeros_like(w)))
    assert len(plain(xc, jnp.zeros_like(w)).shape) == 0
    assert "loop" not in text and text.count("scan[") == 1


# -- the trainer -------------------------------------------------------------------------

def test_it_trains_through_the_engine_and_reports_its_counters(case):
    """``sxt.initialize(...).train_batch`` in float32 under ZeRO-3 over the 8
    devices of the test mesh (a row each; the four exits' weighted scan runs
    outside the ZeRO region there): the first loss, the counters it hands out,
    the first gradient out of Adam's moment, and a loss that falls."""
    model = Transformer(dataclasses.replace(case["cfg"], loss_chunk=8))
    rows = 8                                  # one per device of the test mesh
    ids = np.random.default_rng(9).integers(0, 256, (rows, SEQ + 1)).astype(np.int32)
    want = driver.reference_first_step(driver.reference_program(HF), case["weights"],
                                       jnp.asarray(ids), HF)
    engine = sxt.initialize(
        model=model, params=driver.initial_params(model, 5),
        config={"optimizer": {"type": "FusedAdam",
                              "params": {"lr": 3e-3, "weight_decay": 0.1}},
                "zero_optimization": {"stage": 3},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "train_batch_size": rows, "steps_per_print": 10 ** 9}, seed=5)[0]
    assert model.config.remat and model.config.remat_policy == "full"
    batch = {"input_ids": ids}
    losses = [float(engine.train_batch(batch))]
    assert abs(losses[0] - want["loss"]) < 2e-5
    stats = driver.step_counters(engine.last_step_stats())
    assert stats["loop_layer_visits"] == 12 and stats["loss_rows"] == 4 * rows * SEQ
    assert np.allclose(stats["loop_exit_ce"], want["exit_ce"], atol=2e-5)
    assert np.allclose(stats["loop_exit_mass"], want["exit_mass"], atol=1e-5)
    assert abs(stats["loop_expected_steps"] - want["expected_steps"]) < 1e-5
    moment = driver.first_moment(engine.state.opt_state)
    found = driver.host_gaps(moment, want["grads"], 10.0)
    assert max(found.values()) < 2e-3, found
    # the two mechanisms the cell reads alone: the master moved by Adam's step
    # (a constant rate here: the FIRST update moves it), the exit block float32
    optimizer = {"lr": 3e-3, "weight_decay": 0.1}
    moved = driver.update_gaps(driver.flat_tree(engine.state.master),
                               driver.flat_tree(driver.initial_params(model, 5)),
                               driver.moments(engine.state.opt_state), 1, 3e-3, optimizer)
    # (every leaf but the gate's one-number bias, which is not read)
    assert set(moved) > set(found) - {"exit_gate_b"} and "exit_gate_b" not in moved
    assert 0.0 < max(moved.values()) < 1e-3, moved
    alone = driver.exit_alone_gaps(driver.program_exit_block(model), driver.exit_inputs(
        5, 4, 2, SEQ, model.config.d_model, jnp.float32))
    assert max(alone.values()) < 1e-6, alone
    losses += [float(engine.train_batch(batch)) for _ in range(3)]
    assert losses[-1] < losses[0]
    # the checks of the cell's driver pass on these readings, and name a fault
    got = {"losses": losses, "reference": want, "grad_gaps": found, "counters": stats,
           "visits_expected": 12, "rows_expected": 4 * rows * SEQ,
           "exit_alone_gaps": alone, "update_gaps": moved}
    limits = {"loss_tol": 1e-4, "grad_tol": 0.01, "gate_tol": 0.01, "exit_tol": 1e-4,
              "pdf_tol": 1e-4, "alone_tol": 1e-5, "update_tol": 0.01}
    assert driver.failed_checks(got, limits) == []
    wrong = {**got, "counters": {**stats, "loop_layer_visits": 3.0,
                                 "loop_exit_mass": [0.25] * 4}}
    assert len(driver.failed_checks(wrong, limits)) == 2
    # a master that stayed where it was reads 1 on every leaf that has a gradient
    stayed = driver.update_gaps(driver.flat_tree(driver.initial_params(model, 5)),
                                driver.flat_tree(driver.initial_params(model, 5)),
                                driver.moments(engine.state.opt_state), 4, 3e-3, optimizer)
    assert all(stayed[leaf] == pytest.approx(1.0) for leaf in set(found) & set(stayed))
    assert all(stayed[leaf] == 0.0 for leaf in set(stayed) - set(found))   # the unused biases
    assert "did not move" in driver.failed_checks({**got, "update_gaps": stayed}, limits)[0]


def test_the_scopes_are_registered_and_opened(case):
    assert "loop" in trace.SCOPES["plumbing"]
    assert {"loop_norm", "loop_exit"} <= set(trace.SCOPES["loss"])
    model, batch = chunked(case["model"]), {"input_ids": case["ids"]}
    text = jax.jit(jax.grad(model.loss)).lower(case["params"], batch).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def opened(*scopes):
        """Some instruction's path holds ``scopes`` as components, in order."""
        pattern = re.compile(".*".join(rf"(?:^|/)(?:\w+\()*{s}\)*/" for s in scopes))
        return any(pattern.search(path) for path in paths)

    assert opened("loop", "layers", "attn_core") and opened("loop", "loop_norm")
    assert opened("loss", "loop_exit") and opened("loss", "head_logits")
    assert any("transpose(jvp(loop))" in path and "loop_norm" in path for path in paths)
    # the head's scan applies no norm of its own: the loop has
    assert not opened("final_norm")


def test_a_visit_keeps_its_input_and_nothing_else_under_full_remat(case):
    """What the two scans stack for the backward under policy "full": ONE
    [T, L, B, S, D] array, a block input a (step, layer) visit."""
    cfg = dataclasses.replace(case["cfg"], remat=True, remat_policy="full", loss_chunk=8)
    jaxpr = jax.make_jaxpr(jax.grad(Transformer(cfg).loss))(
        case["params"], {"input_ids": case["ids"]})
    kept = [v.aval.shape for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"
            for v in eqn.outvars if len(v.aval.shape) == 5]
    assert (4, 3, BATCH, SEQ, 64) in kept


# -- serving and the pipeline refuse ---------------------------------------------------------

@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_loop_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    with pytest.raises(NotImplementedError, match="looped stack .loop_steps 4"):
        cls(case["model"], case["params"])


def test_pipeline_stages_refuse_the_loop_by_name(case):
    x, rope = case["model"].embed(case["params"], case["ids"][:, :-1])
    with pytest.raises(NotImplementedError, match="pipeline stages .layer_ids."):
        case["model"].stack_apply(case["params"]["layers"], x, rope,
                                  layer_ids=jnp.arange(3, dtype=jnp.int32))


@pytest.mark.parametrize("fields,error,match", [
    (dict(loop_steps=0), ValueError, "loop_steps"),
    (dict(loop_steps=1, exit_gate=True), ValueError, "exit_gate"),
    (dict(norm_order="both"), ValueError, "sandwich"),
    (dict(norm_order="sandwich", parallel_block=True), ValueError, "one of them"),
    (dict(loop_steps=2, n_experts=4), NotImplementedError, "routed experts"),
    (dict(loop_steps=2, layer_pattern=(("ssm", "mlp"),)), NotImplementedError, "count one pass"),
    (dict(loop_steps=2, layer_pattern=(("gdn", "mlp"), ("attn", "mlp"))), NotImplementedError,
     "count one pass")])
def test_a_configuration_that_is_not_written_is_refused(fields, error, match):
    with pytest.raises(error, match=match):
        Transformer(tr.tiny(**fields))


def test_random_ltd_and_layer_drop_are_one_passes(case):
    with pytest.raises(NotImplementedError, match="plain stack only"):
        case["model"].apply_with_aux(case["params"], case["ids"][:, :-1],
                                     layer_keep=jnp.ones((3,), bool))


# -- the benchmark's files ---------------------------------------------------------------------

def test_the_two_reference_copies_are_byte_identical():
    read = lambda path: open(os.path.join(ROOT, path), "rb").read()
    body = read("chipbench/reference_ouro.py")
    assert body == read("shuffle_exchange_tpu/models/reference_ouro.py")
    code = body.decode().split('"""', 2)[2]
    assert "import shuffle_exchange_tpu" not in code and "from shuffle_exchange_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    assert "lax.scan" not in code and "pallas" not in code
    assert "for _ in range(cfg[\"total_ut_steps\"])" in code


def test_the_slice_of_the_vocabulary_is_what_ids_and_loss_run_over():
    """12,288 rows of embedding and head: the driver's ids stay inside them and
    the logits have that many columns."""
    from chipbench.drivers.train_steps import batches

    cfg = config_from_hf(cell_source())
    ids = next(batches(cfg.vocab_size, 1, 8192, 2 ** 31 + 7))["input_ids"]
    assert ids.shape == (1, 8193) and 0 <= ids.min() and ids.max() < 12288
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    assert shapes["embed"].shape == (12288, 2048) and shapes["unembed"].shape == (2048, 12288)
    # the loss chunks over the four exits' rows: 1,024 positions x 4 rows
    assert Transformer(cfg)._loss_chunk(4, 8192) == 1024
