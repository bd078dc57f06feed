"""One rank's share of the experts moves the rows it holds (``moe/layer.py``
``_held_dispatch``, ``_held_combine``): values and gradients against a plain
float32 reference written here as loops over tokens and choices (nothing of the
reference comes from ``moe/layer.py``), and the mechanism itself read off the
traced program: no pass looks up k rows a token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.moe.layer import (_held_combine, _held_dispatch,
                                            _held_runs, expert_mlp_ragged,
                                            init_expert_mlp)

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


# name -> (topk_idx [S, k], held experts [first, first + held), buffer rows R)
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


def _case(name, dtype=jnp.float32):
    topk_idx, first, held, R = CASES[name]
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
def test_the_dispatch_brings_each_position_its_tokens_row_and_sums_them_back(name):
    c = _case(name)
    order, inverse, runs, read = c["index"]
    got, vjp = jax.vjp(lambda x: _held_dispatch(x, order, c["k"], runs, read),
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
def test_the_combine_weighs_and_sums_a_tokens_held_rows(name):
    c = _case(name)
    order, inverse, runs, read = c["index"]
    got, vjp = jax.vjp(lambda o, w: _held_combine(o, w, order, inverse, runs, read),
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


@pytest.mark.parametrize("name", ["k10", "every_choice_held_several_blocks"])
def test_in_bfloat16_a_tokens_sum_is_rounded_once(name):
    c = _case(name, jnp.bfloat16)
    order, inverse, runs, read = c["index"]
    rows = np.where(np.isnan(c["rows"]), 0, c["rows"])
    rounded = dict(c, rows=np.asarray(c["cast"](rows), np.float32),
                   weights=np.asarray(c["cast"](c["weights"]), np.float32))
    want = _combine_reference(rounded)[0]
    got = _held_combine(c["cast"](c["rows"]), jnp.asarray(c["weights"]), order, inverse, runs, read)
    assert got.dtype == jnp.bfloat16
    # the float32 sum of the rounded operands, rounded once: half a bf16 step
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=2 ** -8, atol=1e-6)


def _row_lookups(jaxpr, width):
    """Rows of width ``width`` that the gathers of a traced program look up."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            operand, out = eqn.invars[0].aval, eqn.outvars[0].aval
            if operand.ndim == 2 and operand.shape[1] == width and out.shape[-1] == width:
                total += int(np.prod(out.shape[:-1]))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _row_lookups(sub, width)
    return total


def test_no_pass_of_the_share_looks_up_k_rows_a_token():
    S, R, width, held, n_experts = 512, 256, 128, 4, 16
    params = init_expert_mlp(jax.random.PRNGKey(0), held, width, 32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (S, width))

    def lookups(k):
        topk_idx = jnp.asarray(_drawn(k, S, k, n_experts), jnp.int32)
        weights = jnp.full((S, k), 1.0 / k)

        def loss(params, xs, weights):
            return expert_mlp_ragged(params, xs, topk_idx, weights, buffer_rows=R)[0].sum()

        traced = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(params, xs, weights)
        return _row_lookups(traced.jaxpr, width)

    few, many = lookups(4), lookups(12)
    # the way in R; the way back R (and the runs' halo) + S; and as much again backward
    assert 3 * R + 2 * S <= few <= 6 * (R + S)
    assert many <= 6 * (R + S) < 3 * 12 * S
    assert many - few < S
