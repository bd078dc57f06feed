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
                                            _held_runs, _route_index, expert_mlp_ragged,
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
    return order, inverse.reshape(S, k), fit, by_expert.astype(np.int32)


def _case(name, monkeypatch, dtype=jnp.float32):
    topk_idx, first, held, R = CASES[name][:4]
    if len(CASES[name]) > 4:
        monkeypatch.setattr(layer, "_ROW_BLOCK", CASES[name][4])
    S, k = topk_idx.shape
    order, inverse, fit, by_expert = _positions(topk_idx, first, held, R)
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
                index=(jnp.asarray(order), jnp.asarray(by_expert), runs, read))


def _near(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_dispatch_brings_each_position_its_tokens_row_and_sums_them_back(name, monkeypatch):
    c = _case(name, monkeypatch)
    order, _, runs, read = c["index"]
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
    order, by_expert, runs, read = c["index"]
    got, vjp = jax.vjp(lambda o, w: _held_combine(o, w, order, by_expert, c["fit"], runs, read),
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
    order, by_expert, runs, read = c["index"]
    rows = np.where(np.isnan(c["rows"]), 0, c["rows"])
    rounded = dict(c, rows=np.asarray(c["cast"](rows), np.float32),
                   weights=np.asarray(c["cast"](c["weights"]), np.float32))
    want = _combine_reference(rounded)[0]
    got = _held_combine(c["cast"](c["rows"]), jnp.asarray(c["weights"]), order, by_expert,
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


def _moved(idx, expert, to):
    """``idx`` with every choice of ``expert`` on expert ``to``."""
    return np.where(idx == expert, to, idx)


# name -> (topk_idx [S, k], first held expert, held experts, buffer rows R or
# None: every expert of the router's is held)
ROUTINGS = {
    "balanced_k4": (_drawn(20, 96, 4, 32), 8, 8, 288),
    "balanced_k10": (_drawn(21, 64, 10, 64), 0, 8, 192),
    "k1": (_drawn(22, 80, 1, 8), 2, 4, 64),
    "every_choice_on_one_expert": (np.full((80, 4), 5), 4, 4, 256),
    "an_expert_that_gets_nothing": (_moved(_drawn(23, 64, 4, 16), 2, 9), 0, 6, 160),
    "every_choice_absent": (_drawn(24, 32, 4, 16) % 8 + 8, 0, 8, 64),
    "held_rows_past_the_buffer": (_drawn(25, 64, 4, 8), 0, 6, 100),
    "one_expert_past_the_buffer_k10": (np.full((40, 10), 1), 0, 4, 128),
    "a_buffer_of_every_choice": (_drawn(26, 32, 4, 8), 0, 8, 128),
    "all_held_64_experts_k8": (_drawn(27, 96, 8, 64), 0, 64, None),
    "all_held_k1": (_drawn(28, 70, 1, 4), 0, 4, None),
    "all_held_an_expert_that_gets_nothing": (_drawn(29, 50, 4, 8) % 7, 0, 8, None),
}


def _index_by_a_loop(topk_idx, first, held, R):
    """The index arrays of one routing, by a loop over the token-choices:
    each held choice joins its expert's list in the order the choices come,
    the lists are laid end to end over the positions, and what lies past the
    buffer is dropped."""
    S, k = topk_idx.shape
    lists, absent = [[] for _ in range(held)], []
    for choice, expert in enumerate(topk_idx.reshape(-1)):
        (lists[expert - first] if first <= expert < first + held else absent).append(choice)
    n_held = sum(len(of) for of in lists)
    P = S * k if R is None else R               # positions
    order = np.full(P, S * k, np.int64)
    inverse = np.full(S * k, P, np.int64)
    group_sizes = np.zeros(held, np.int64)
    position = 0
    for expert, of in enumerate(lists):
        for choice in of:
            if position < P:
                order[position], inverse[choice] = choice, position
                group_sizes[expert] += 1
                position += 1
    want = dict(by_expert=np.array([choice for of in lists + [absent] for choice in of]),
                order=order, inverse=inverse, group_sizes=group_sizes, fit=position, held=n_held)
    if R is None:
        return want
    # the positions in token order, cut into run blocks with their halo
    H = layer._run_halo(k)
    B = max(H, min(layer._RUN_BLOCK, -(-R // 8) * 8))
    nb = R // B + 1
    by_token = [inverse[choice] for choice in range(S * k) if inverse[choice] < R]
    by_token += [R] * ((nb + 1) * B - len(by_token))
    runs = np.array([[by_token[b * B + j] for j in range(B + H)] for b in range(nb)])
    read, start = np.zeros(S, np.int64), 0
    for s in range(S):
        count = sum(inverse[s * k + j] < R for j in range(k))
        read[s] = start if count else nb * B - 1
        start += count
    return dict(want, inverse=inverse.reshape(S, k), runs=runs, read=read)


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_the_index_arrays_are_those_of_a_loop_over_the_token_choices(name):
    topk_idx, first, held, R = ROUTINGS[name]
    got = jax.jit(lambda t: _route_index(t, held, first, R))(jnp.asarray(topk_idx, jnp.int32))
    want = _index_by_a_loop(topk_idx, first, held, R)
    for key, value in want.items():
        np.testing.assert_array_equal(np.asarray(getattr(got, key)), value, err_msg=key)
    if R is None:
        assert got.runs is None and got.read is None
    S, k = topk_idx.shape
    on_held = int(((topk_idx >= first) & (topk_idx < first + held)).sum())
    assert want["held"] == on_held and want["fit"] == min(on_held, S * k if R is None else R)
    if name == "every_choice_absent":
        assert want["fit"] == 0
    if "past_the_buffer" in name:
        assert want["held"] > R == want["fit"]
    if "gets_nothing" in name:
        assert (want["group_sizes"] == 0).any()


def _scatters(jaxpr):
    """(primitive, elements of the updates) of every scatter of a traced program."""
    return [(eqn.primitive.name, int(np.prod(eqn.invars[2].aval.shape)))
            for eqn in _equations(jaxpr) if eqn.primitive.name.startswith("scatter")]


@pytest.mark.parametrize("buffer", [True, False], ids=["a_ranks_share", "every_expert_held"])
def test_no_scatter_over_the_token_choices_and_three_sorts_a_forward(buffer):
    """The mechanism read off the program: value and gradient of one layer hold
    no ``scatter`` / ``scatter-add`` whose updates are the S * k token-choices
    or the R positions (a TPU runs those one index at a time) and no lookup of
    S * k scalars; the forward's sorts are three (by expert, its inverse, and
    the share's positions by token), the backward's one (the weights' gradient
    from the positions to the choices: the one sort that carries floats)."""
    S, R, k = 512, 256, 4
    traced = _share_program(k, S, R, buffer=buffer)[0]
    assert [s for s in _scatters(traced.jaxpr) if s[1] >= min(R, S * k)] == []
    assert [eqn for eqn in _equations(traced.jaxpr) if eqn.primitive.name == "gather"
            and eqn.outvars[0].aval.size == S * k] == []
    sorts = _loops(traced.jaxpr, "sort")
    backward = [eqn for eqn in sorts
                if any(jnp.issubdtype(v.aval.dtype, jnp.floating) for v in eqn.invars)]
    forward = [eqn for eqn in sorts if eqn not in backward]
    assert sorted(eqn.invars[0].aval.shape[0] for eqn in forward) == \
        ([R] if buffer else []) + [S * k, S * k]
    assert [eqn.invars[0].aval.shape[0] for eqn in backward] == ([S * k] if buffer else [])


@pytest.mark.parametrize("buffer", [True, False], ids=["a_ranks_share", "every_expert_held"])
def test_biased_experts_add_each_rows_own_experts_bias(buffer):
    """The bias epilogue reads a row's expert off the group sizes: against
    ``expert_mlp`` run on one token at a time with its expert's leaves."""
    S, k, width, n_experts = 48, 2, 16, 8
    held, R = (4, 64) if buffer else (n_experts, None)
    params = init_expert_mlp(jax.random.PRNGKey(0), held, width, 24, bias=True)
    params = {key: (jax.random.normal(jax.random.PRNGKey(i), leaf.shape) if key.startswith("b_")
                    else leaf) for i, (key, leaf) in enumerate(sorted(params.items()))}
    xs = jax.random.normal(jax.random.PRNGKey(9), (S, width))
    topk_idx = _drawn(30, S, k, n_experts)
    weights = np.random.default_rng(31).random((S, k)).astype(np.float32)
    got = expert_mlp_ragged(params, xs, jnp.asarray(topk_idx, jnp.int32), jnp.asarray(weights),
                            buffer_rows=R)[0]
    want = np.zeros((S, width), np.float32)
    for s in range(S):
        for j in range(k):
            e = topk_idx[s, j]
            if e < held:
                one = {key: leaf[e:e + 1] for key, leaf in params.items()}
                want[s] += weights[s, j] * np.asarray(layer.expert_mlp(one, xs[s][None, None]))[0, 0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


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
