"""One rank's share of the experts moves the rows it holds (``moe/layer.py``
``_held_dispatch``, ``_held_combine``): values and gradients against a plain
float32 reference written here as loops over tokens and choices (nothing of the
reference comes from ``moe/layer.py``), and the mechanism itself read off the
traced program: no pass looks up k rows a token, every row pass is a loop
whose trips follow the rows the buffer holds (``_held_blocks``), and the route
that holds every expert has no such loop."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.moe import layer
from shuffle_exchange_tpu.moe.layer import (_held_combine, _held_dispatch,
                                            _held_runs, expert_mlp_ragged,
                                            held_rows_visited, init_expert_mlp)

M = 24


def _explicit():
    """Token 0 holds nothing here, token 1 one choice, token 2 all six."""
    rng = np.random.default_rng(5)
    idx = np.stack([rng.permutation(np.arange(6, 16))[:6] for _ in range(9)])
    idx[1, 3] = 2
    idx[2] = rng.permutation(6)
    idx[5, :2] = (4, 1)
    return idx


def _drawn(seed, S, k, n_experts):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n_experts)[:k] for _ in range(S)])


def _held_by_the_first(tokens):
    """64 tokens of 4 choices over 16 experts, the first 4 held: the first
    ``tokens`` land all four choices on held experts, the others none."""
    idx = np.tile(np.arange(4, 8), (64, 1))
    idx[:tokens] = np.arange(4)
    return idx


def _one_more(idx, token):
    idx = idx.copy()
    idx[token, 0] = 0
    return idx


# name -> (topk_idx [S, k], held experts [first, first + held), buffer rows R[,
# positions a trip of the row passes, for the test: ``layer._ROW_BLOCK``])
CASES = {
    "k6": (_drawn(0, 40, 6, 16), 4, 4, 64),
    "k10": (_drawn(1, 40, 10, 32), 0, 8, 120),
    "none_one_all_k_held": (_explicit(), 0, 6, 40),
    "overflowing_buffer": (_drawn(2, 40, 6, 16), 0, 8, 50),
    "nothing_held": (_drawn(3, 20, 6, 16) % 8, 8, 4, 16),
    "odd_buffer_k10": (_drawn(4, 30, 10, 16), 3, 5, 37),
    "every_choice_held_several_blocks": (_drawn(6, 130, 6, 6), 0, 6, 777),
    "k1": (_drawn(7, 50, 1, 4), 1, 2, 24),
    # a 256-wide router with top 8, rank 2 of the 8 that hold 32 experts each
    "k8_of_256_32_held": (_drawn(8, 64, 8, 256), 64, 32, 96),
    # a 32-wide router with top 4, rank 1 of the 4 that hold 8 experts each: a
    # token lands on one held expert on average (the tie of the four shares to
    # the uncut layer is tests/test_lfm2.py's)
    "k4_of_32_8_held": (_drawn(9, 96, 4, 32), 8, 8, 288),
    # the loops' own edges: a third of a buffer of seven blocks (and of four
    # run blocks, one a trip); 128 held rows = two whole blocks of 64, and one
    # row more = a third block; a buffer that is no whole number of blocks
    # (the last block starts early), all of it held; blocks of two run blocks
    "a_third_of_seven_blocks": (_drawn(10, 200, 6, 16), 4, 4, 900, 128),
    "fit_on_a_block_boundary": (_held_by_the_first(32), 0, 4, 320, 64),
    "fit_one_row_past_a_block_boundary": (_one_more(_held_by_the_first(32), 40), 0, 4, 320, 64),
    "ragged_last_block_all_held": (_drawn(6, 130, 6, 6), 0, 6, 777, 200),
    "two_run_blocks_a_trip": (_drawn(12, 300, 6, 12), 0, 6, 1700, 512),
}


def _positions(topk_idx, first, held, R):
    """What ``expert_mlp_ragged`` hands the two functions, in numpy: the held
    token-choices sorted by expert (stable), cut at R positions."""
    S, k = topk_idx.shape
    local = topk_idx.reshape(-1) - first
    local = np.where((local >= 0) & (local < held), local, held)
    by_expert = np.argsort(local, kind="stable")
    fit = min(int((local < held).sum()), R)
    order = np.full(R, S * k, np.int32)
    order[:fit] = by_expert[:fit]
    inverse = np.full(S * k, R, np.int32)
    inverse[order[:fit]] = np.arange(fit)
    return order, inverse.reshape(S, k), fit


def _case(name, monkeypatch, dtype=jnp.float32):
    topk_idx, first, held, R = CASES[name][:4]
    if len(CASES[name]) > 4:
        monkeypatch.setattr(layer, "_ROW_BLOCK", CASES[name][4])
    S, k = topk_idx.shape
    order, inverse, fit = _positions(topk_idx, first, held, R)
    rng = np.random.default_rng(11)
    weights = rng.random((S, k)).astype(np.float32) + 0.1
    xs = rng.standard_normal((S, M)).astype(np.float32)
    g_tokens = rng.standard_normal((S, M)).astype(np.float32)
    # a position that holds nothing may hold anything (the grouped GEMM writes
    # only its groups' rows): nothing may come of it
    rows = rng.standard_normal((R, M)).astype(np.float32)
    rows[fit:] = np.nan
    as_dtype = lambda a: jnp.asarray(a).astype(dtype)
    runs, read = _held_runs(jnp.asarray(order), jnp.asarray(inverse), fit)
    return dict(S=S, k=k, R=R, fit=fit, order=order, inverse=inverse, weights=weights,
                xs=xs, rows=rows, g_tokens=g_tokens, cast=as_dtype,
                index=(jnp.asarray(order), jnp.asarray(inverse), runs, read))


def _near(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_dispatch_brings_each_position_its_tokens_row_and_sums_them_back(name, monkeypatch):
    c = _case(name, monkeypatch)
    order, inverse, runs, read = c["index"]
    got, vjp = jax.vjp(lambda x: _held_dispatch(x, order, c["k"], c["fit"], runs, read),
                       jnp.asarray(c["xs"]))
    want = np.zeros((c["R"], M), np.float32)
    for r in range(c["fit"]):
        want[r] = c["xs"][c["order"][r] // c["k"]]
    np.testing.assert_array_equal(np.asarray(got), want)
    d_want = np.zeros((c["S"], M), np.float32)
    for s in range(c["S"]):
        for j in range(c["k"]):
            if c["inverse"][s, j] < c["R"]:
                d_want[s] += c["rows"][c["inverse"][s, j]]
    _near(vjp(jnp.asarray(c["rows"]))[0], d_want)


def _combine_reference(c):
    S, k, R = c["S"], c["k"], c["R"]
    out = np.zeros((S, M), np.float32)
    d_rows = np.zeros((R, M), np.float32)
    d_weights = np.zeros((S, k), np.float32)
    for s in range(S):
        for j in range(k):
            r = c["inverse"][s, j]
            if r < R:
                out[s] += c["weights"][s, j] * c["rows"][r]
                d_rows[r] = c["weights"][s, j] * c["g_tokens"][s]
                d_weights[s, j] = np.dot(c["g_tokens"][s], c["rows"][r])
    return out, d_rows, d_weights


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_combine_weighs_and_sums_a_tokens_held_rows(name, monkeypatch):
    c = _case(name, monkeypatch)
    order, inverse, runs, read = c["index"]
    got, vjp = jax.vjp(lambda o, w: _held_combine(o, w, order, inverse, c["fit"], runs, read),
                       jnp.asarray(c["rows"]), jnp.asarray(c["weights"]))
    d_rows, d_weights = vjp(jnp.asarray(c["g_tokens"]))
    want, d_rows_want, d_weights_want = _combine_reference(c)
    _near(got, want)
    # a position that holds nothing, and a choice with no position, get zeros
    _near(d_rows, d_rows_want)
    _near(d_weights, d_weights_want)
    if name == "overflowing_buffer":
        held = (CASES[name][0] < CASES[name][2]).sum()
        assert c["fit"] == c["R"] < held
        assert (np.asarray(d_weights) != 0).sum() == c["R"]


@pytest.mark.parametrize("name", ["k10", "every_choice_held_several_blocks",
                                  "a_third_of_seven_blocks", "two_run_blocks_a_trip"])
def test_in_bfloat16_a_tokens_sum_is_rounded_once(name, monkeypatch):
    c = _case(name, monkeypatch, jnp.bfloat16)
    order, inverse, runs, read = c["index"]
    rows = np.where(np.isnan(c["rows"]), 0, c["rows"])
    rounded = dict(c, rows=np.asarray(c["cast"](rows), np.float32),
                   weights=np.asarray(c["cast"](c["weights"]), np.float32))
    want = _combine_reference(rounded)[0]
    got = _held_combine(c["cast"](c["rows"]), jnp.asarray(c["weights"]), order, inverse,
                        c["fit"], runs, read)
    assert got.dtype == jnp.bfloat16
    # the float32 sum of the rounded operands, rounded once: half a bf16 step
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=2 ** -8, atol=1e-6)


def _most_trips(body):
    """The most trips a row pass's loop makes, read off its body: a trip writes
    one block of the carry (``dynamic_update_slice``), so the carry's rows over
    the block's."""
    return max((-(-eqn.invars[0].aval.shape[0] // eqn.invars[1].aval.shape[0])
                for eqn in body.eqns if eqn.primitive.name == "dynamic_update_slice"), default=1)


def _inner(eqn):
    """(parameter name, jaxpr) of every program an equation holds."""
    for key, value in eqn.params.items():
        for sub in (value if isinstance(value, (list, tuple)) else (value,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield key, sub


def _equations(jaxpr):
    """Every equation of a traced program, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for _, sub in _inner(eqn):
            yield from _equations(sub)


def _loops(jaxpr, kind="while"):
    return [eqn for eqn in _equations(jaxpr) if eqn.primitive.name == kind]


def _row_lookups(jaxpr, width):
    """Rows of width ``width`` that the gathers of a traced program look up at
    most: a loop's body counts once a trip of the most it can make."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            operand, out = eqn.invars[0].aval, eqn.outvars[0].aval
            if operand.ndim == 2 and operand.shape[1] == width and out.shape[-1] == width:
                total += int(np.prod(out.shape[:-1]))
        for key, sub in _inner(eqn):
            trips = _most_trips(sub) if (eqn.primitive.name, key) == ("while", "body_jaxpr") else 1
            total += trips * _row_lookups(sub, width)
    return total


def _share_program(k, S=512, R=256, width=128, held=4, n_experts=16, buffer=True):
    """(the traced value and gradient of one layer of experts, its inputs):
    a rank's share with a buffer of R rows, or every expert held."""
    params = init_expert_mlp(jax.random.PRNGKey(0), held if buffer else n_experts, width, 32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (S, width))
    topk_idx = jnp.asarray(_drawn(k, S, k, n_experts), jnp.int32)
    weights = jnp.full((S, k), 1.0 / k)

    def loss(params, xs, weights):
        return expert_mlp_ragged(params, xs, topk_idx, weights,
                                 buffer_rows=R if buffer else None)[0].sum()

    program = jax.value_and_grad(loss, argnums=(0, 1, 2))
    return jax.make_jaxpr(program)(params, xs, weights), program, (params, xs, weights)


def test_no_pass_of_the_share_looks_up_k_rows_a_token(monkeypatch):
    # four blocks a pass at most: the walker multiplies the loops' bodies out
    monkeypatch.setattr(layer, "_ROW_BLOCK", 64)
    S, R, width = 512, 256, 128

    def lookups(k):
        traced = _share_program(k, S, R, width)[0]
        assert _loops(traced.jaxpr)
        return _row_lookups(traced.jaxpr, width)

    few, many = lookups(4), lookups(12)
    # the way in R; the way back R (and the runs' halo) + S; and as much again backward
    assert 3 * R + 2 * S <= few <= 6 * (R + S)
    assert many <= 6 * (R + S) < 3 * 12 * S
    assert many - few < S


def test_every_row_pass_is_a_loop_whose_trips_follow_the_rows_held(monkeypatch):
    """The mechanism read off the program and off a run of it: four loops of
    traced length (the way in, the way back, and the backward of each), and the
    rows they look up when run are those of the blocks below ``fit``, not R."""
    block, S, R, width, k = 64, 512, 2048, 128, 4
    monkeypatch.setattr(layer, "_ROW_BLOCK", block)
    looked_up = []
    plain = layer._rows_or_zeros

    def counted(x, index):
        jax.debug.callback(lambda: looked_up.append(index.shape[0]))
        return plain(x, index)

    monkeypatch.setattr(layer, "_rows_or_zeros", counted)
    traced, program, inputs = _share_program(k, S, R, width)
    loops = _loops(traced.jaxpr)
    assert len(loops) == 4
    for loop in loops:
        # ``fori_loop`` to a traced bound: the carry is (i, bound, ...) and the
        # condition compares the two; a static bound would have made a scan
        cond = loop.params["cond_jaxpr"].jaxpr
        assert [e.primitive.name for e in cond.eqns] == ["lt"]
        assert not any(hasattr(v, "val") for v in cond.eqns[0].invars)     # no literal
    assert sorted(_most_trips(loop.params["body_jaxpr"].jaxpr) for loop in loops) == \
        sorted([R // block, R // block, R // 256 + 1, R // 256 + 1])
    value, grads = jax.jit(program)(*inputs)
    jax.block_until_ready((value, grads))
    jax.effects_barrier()
    topk_idx = _drawn(k, S, k, 16)
    fit = int((topk_idx < 4).sum())
    assert 0 < fit < R // 2
    visited = -(-fit // block) * block
    assert int(held_rows_visited(fit, R)) == visited
    halo = layer._run_halo(k)
    run_rows = -(-fit // 256) * (256 + halo)        # one run block a trip at this block
    assert sorted(looked_up) == sorted([block] * (2 * visited // block)
                                       + [256 + halo] * (2 * run_rows // (256 + halo)))
    assert sum(looked_up) == 2 * visited + 2 * run_rows < 2 * R


@pytest.mark.parametrize("name", sorted(n for n in CASES if len(CASES[n]) > 4)
                         + ["nothing_held", "overflowing_buffer"])
def test_the_visited_rows_are_the_blocks_that_start_below_fit(name, monkeypatch):
    c = _case(name, monkeypatch)
    block = min(layer._ROW_BLOCK, c["R"])
    assert int(held_rows_visited(c["fit"], c["R"])) == min(-(-c["fit"] // block) * block, c["R"])
    assert int(held_rows_visited(jnp.int32(c["fit"]), c["R"])) >= c["fit"]
    if name == "nothing_held":
        assert c["fit"] == 0 == int(held_rows_visited(0, c["R"]))
    if name in ("overflowing_buffer", "ragged_last_block_all_held"):
        assert c["fit"] == c["R"] == int(held_rows_visited(c["fit"], c["R"]))


def test_the_route_that_holds_every_expert_has_no_loop_and_its_four_lookups():
    """``buffer_rows=None`` (``_permuted_rows``): the program PR 44's parent
    traced, S * k rows in, S * k back and both again backward, no loop."""
    S, width, k = 512, 128, 4
    traced = _share_program(k, S, width=width, buffer=False)[0]
    assert _loops(traced.jaxpr) == []
    assert _row_lookups(traced.jaxpr, width) == 4 * S * k


# a leading convolution + dense layer, then attention + routed and three
# convolution + routed layers, 4 of 8 experts held (tests/test_lfm2.py's shape)
TINY_STACK = {
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
    "intermediate_size": 96, "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                                             "conv"],
    "max_position_embeddings": 1024, "moe_intermediate_size": 32, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 5, "layers_held": [0, 2, 3, 4, 5],
    "num_key_value_heads": 2, "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 128, "tie_word_embeddings": True,
    "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
    "bias_update_speed": 0.001}


def _tiny_stats(cfg, ids):
    from shuffle_exchange_tpu.models.transformer import Transformer

    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    program = jax.value_and_grad(model.loss_and_stats, has_aux=True)
    traced = jax.make_jaxpr(program)(params, {"input_ids": ids})
    (loss, stats), grads = jax.jit(program)(params, {"input_ids": ids})
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    return traced, {key: np.asarray(value) for key, value in stats.items()}


def test_a_dense_model_and_one_that_holds_every_expert_trace_no_loop_and_no_counter():
    from shuffle_exchange_tpu.models.transformer import tiny, tiny_moe

    ids = np.random.default_rng(0).integers(0, 256, (2, 33)).astype(np.int32)
    traced, stats = _tiny_stats(tiny(), ids)
    assert _loops(traced.jaxpr) == [] and stats == {}
    traced, stats = _tiny_stats(tiny_moe(experts=8, moe_impl="ragged"), ids)
    assert _loops(traced.jaxpr) == []
    assert "moe_held_rows" in stats and "moe_visited_rows" not in stats


def test_the_model_hands_out_the_rows_its_passes_visited(monkeypatch):
    """A tiny held-share stack of several kinds under ``lax.scan`` and per-half
    remat ("full"), forward and backward: a routed layer's ``moe_visited_rows``
    is the blocks that start below its held rows, whole."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    block = 16
    monkeypatch.setattr(layer, "_ROW_BLOCK", block)
    cfg = dataclasses.replace(config_from_hf(TINY_STACK), remat=True, remat_policy="full")
    ids = np.random.default_rng(3).integers(0, 128, (2, 49)).astype(np.int32)
    traced, stats = _tiny_stats(cfg, ids)
    assert len(_loops(traced.jaxpr, "scan")) >= 2 and len(_loops(traced.jaxpr)) >= 4
    held = stats["moe_held_rows"]
    assert held.shape == (4,) and (stats["moe_overflow_rows"] == 0).all()
    buffer = layer.held_buffer_rows(2 * 48, 2, 4, 8, cfg.moe_held_rows_factor)
    np.testing.assert_array_equal(stats["moe_visited_rows"],
                                  np.minimum(-(-held // block) * block, buffer))
    assert (stats["moe_visited_rows"] < buffer).any()


def test_a_layer_that_routes_nothing_reports_no_visited_row(monkeypatch):
    from shuffle_exchange_tpu.models.transformer import (Transformer, _no_routing_stats,
                                                         tiny_moe)

    assert int(_no_routing_stats(8, share=True)["visited_rows"]) == 0
    assert "visited_rows" not in _no_routing_stats(8)
    monkeypatch.setattr(layer, "_ROW_BLOCK", 16)
    model = Transformer(tiny_moe(experts=8, n_experts_held=4, moe_impl="ragged",
                                 moe_layer_pattern=(True, False)))
    params = model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 256, (2, 32)).astype(np.int32)
    x, rope = model.embed(params, ids)
    # ``layer_ids`` (a pipeline stage's) keeps the dense layer's row
    stats = model.stack_apply(params["layers"], x, rope, with_stats=True,
                              layer_ids=jnp.arange(2))[2]
    held, visited = np.asarray(stats["held_rows"]), np.asarray(stats["visited_rows"])
    assert held[0] > 0 == held[1]
    assert visited[0] == -(-held[0] // 16) * 16 and visited[1] == 0
