"""``ops/gated_delta.py``: the chunked (matrix-product) gated delta rule that
trains against the token-by-token recurrence, outputs and every gradient, at
small sizes in float32; the same for the Pallas kernels a TPU runs, here
through the interpreter (``SXT_FUSED_INTERPRET=1``) at the smallest shapes
they take; the causal depthwise convolution and the l2 norm against their
definitions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.ops import gated_delta as gd

CASES = {
    # name: (B, T, H, dk, dv, chunk, decay, beta)
    "one_chunk": (1, 64, 1, 16, 16, 64, "mild", "mid"),
    "two_chunks": (1, 128, 2, 16, 8, 64, "mild", "mid"),
    "ragged_tail": (2, 150, 3, 16, 16, 64, "mild", "mid"),
    "shorter_than_a_chunk": (2, 37, 2, 8, 16, 64, "mild", "mid"),
    "strong_decay": (1, 130, 2, 16, 16, 64, "strong", "mid"),
    "zero_decay": (2, 96, 2, 16, 16, 32, "zero", "mid"),
    "beta_zero": (1, 100, 2, 16, 16, 64, "mild", "zero"),
    "beta_one": (1, 100, 2, 16, 16, 64, "mild", "one"),
    "small_chunk": (2, 50, 4, 8, 8, 16, "mild", "mid"),
}


def inputs(name):
    B, T, H, dk, dv, chunk, decay, beta_kind = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(sorted(CASES).index(name)), 5)
    q = gd.l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = {"mild": -0.2 * jax.random.uniform(ks[3], (B, T, H)),
         "strong": -20.0 * jax.random.uniform(ks[3], (B, T, H)),
         "zero": jnp.zeros((B, T, H))}[decay]
    beta = {"mid": jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))),
            "zero": jnp.zeros((B, T, H)), "one": jnp.ones((B, T, H))}[beta_kind]
    return (q, k, v, g, beta), chunk


@pytest.mark.parametrize("name", sorted(CASES))
def test_chunked_equals_recurrent_outputs(name):
    args, chunk = inputs(name)
    want = gd.gated_delta_recurrent(*args)
    with jax.default_matmul_precision("highest"):
        got = gd.gated_delta_chunked(*args, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_chunked_equals_recurrent_gradients(name):
    args, chunk = inputs(name)
    probe = jax.random.normal(jax.random.PRNGKey(99),
                              args[2].shape[:3] + (args[2].shape[-1],))

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a) * probe)

    want = jax.grad(scalar(gd.gated_delta_recurrent), argnums=range(5))(*args)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(scalar(lambda *a: gd.gated_delta_chunked(*a, chunk=chunk)),
                       argnums=range(5))(*args)
    for a, b, leaf in zip(got, want, "q k v g beta".split()):
        assert bool(jnp.all(jnp.isfinite(a))), leaf
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=leaf)


def test_the_state_forgets_under_strong_decay():
    """exp(g) ~ 0: every output is beta_t (q_t . k_t) v_t, its own token's."""
    (q, k, v, _, beta), chunk = inputs("two_chunks")
    g = jnp.full(beta.shape, -60.0)
    got = gd.gated_delta_chunked(q, k, v, g, beta, chunk=chunk)
    own = (beta * jnp.sum(q * k, axis=-1))[..., None] * v
    np.testing.assert_allclose(got, own, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("seed", [0, 1])
def test_a_bf16_trainers_rule_carries_its_state_in_float32(seed):
    """bf16 q, k and v at a memory of tens to hundreds of tokens: the chunked
    rule (S in float32, rounded only as an operand) stays within 1% of the
    float32 recurrence in the output and every gradient; the recurrence with
    S rounded to bf16 after every token is over half as far again on each
    (three to five times at the benchmark's size, PERF.md section 6, PR 33)."""
    from shuffle_exchange_tpu.models import reference_qwen3next as ref

    B, T, H, d = 1, 512, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    low = lambda x: x.astype(jnp.bfloat16)
    q = low(gd.l2norm(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5)
    k = low(gd.l2norm(jax.random.normal(ks[1], (B, T, H, d))))
    v = low(jax.nn.silu(jax.random.normal(ks[2], (B, T, H, d))))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
    memory = jnp.exp(jax.random.uniform(ks[4], (H,), minval=np.log(16.), maxval=np.log(256.)))
    g = -jax.nn.softplus(jax.random.normal(ks[5], (B, T, H)) + 1.0) / (1.3133 * memory)
    cotangent = jax.random.normal(ks[6], (B, T, H, d))

    def answers(rule):
        o, back = jax.vjp(lambda *a: rule(*a).astype(jnp.float32), q, k, v, g, beta)
        return [x.astype(jnp.float32) for x in (o,) + back(cotangent)]

    up = lambda x: x.astype(jnp.float32)
    recurrence = lambda bits: lambda q, k, v, g, beta: ref.delta_rule(
        up(q), up(k), up(v), g, beta, state_bits=bits)
    with jax.default_matmul_precision("highest"):
        exact, rounded = answers(recurrence(None)), answers(recurrence((8, 7)))
    ours = answers(gd.gated_delta_chunked)
    gap = lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
    for mine, theirs, want, part in zip(ours, rounded, exact, "o q k v g beta".split()):
        assert gap(mine, want) < 0.01 and 1.5 * gap(mine, want) < gap(theirs, want), part


# -- the Pallas kernels, through the interpreter ------------------------------

KERNEL_CASES = {
    # name: (B, T, H, decay, beta); dk = dv = 128, chunk 64
    "one_chunk": (1, 64, 1, "mild", "mid"),
    "several_chunks": (1, 192, 2, "mild", "mid"),
    "ragged_tail": (2, 150, 2, "mild", "mid"),
    "shorter_than_a_chunk": (2, 37, 2, "mild", "mid"),
    "strong_decay": (2, 150, 2, "strong", "mid"),
    "zero_decay": (2, 150, 2, "zero", "mid"),
    "beta_zero": (2, 150, 2, "mild", "zero"),
    "beta_one": (2, 150, 2, "mild", "one"),
}
PARTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


def kernel_inputs(name, dtype):
    """((q, k, v, g, beta), cotangent): q, k, v rounded to ``dtype`` as a
    mixer hands them over, the rest float32."""
    B, T, H, decay, beta_kind = KERNEL_CASES[name]
    d = 128
    ks = jax.random.split(jax.random.PRNGKey(100 + sorted(KERNEL_CASES).index(name)), 6)
    q = gd.l2norm(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.nn.silu(jax.random.normal(ks[2], (B, T, H, d)))
    g = {"mild": -0.2 * jax.random.uniform(ks[3], (B, T, H)),
         "strong": -20.0 * jax.random.uniform(ks[3], (B, T, H)),
         "zero": jnp.zeros((B, T, H))}[decay]
    beta = {"mid": jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))),
            "zero": jnp.zeros((B, T, H)), "one": jnp.ones((B, T, H))}[beta_kind]
    return ((q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta),
            jax.random.normal(ks[5], (B, T, H, d)))


def answers(rule):
    """(o, dq, dk, dv, dg, dbeta) of ``rule`` under a cotangent, float32, as
    one jitted program (one compilation a shape)."""
    def both(args, cotangent):
        o, back = jax.vjp(lambda *a: rule(*a).astype(jnp.float32), *args)
        return tuple(x.astype(jnp.float32) for x in (o,) + back(cotangent))
    return jax.jit(both)


# traced under ``interpreted`` only: the route is chosen while tracing
kernel_answers = answers(lambda *a: gd.gated_delta_chunked(*a))
xla_answers = answers(lambda *a: gd.gated_delta_chunked(*a))
recurrent_answers = answers(lambda q, k, v, g, beta: gd.gated_delta_recurrent(
    q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), g, beta))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")


def calls(fn, *args):
    """The names of the ``pallas_call``s in ``fn``'s jaxpr, with the number
    of results each has. Traced anew every time (jax caches a trace by the
    function it was given, and the route is chosen while tracing)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], len(eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    return found


@pytest.mark.parametrize("why, q, v, chunk, forced, want", [
    ("eligible", (1, 64, 2, 128), (1, 64, 2, 128), 64, True, "interpret"),
    ("wider_heads", (1, 64, 2, 256), (1, 64, 2, 128), 64, True, "interpret"),
    ("off_a_tpu", (1, 64, 2, 128), (1, 64, 2, 128), 64, False, "xla"),
    ("narrow_keys", (1, 64, 2, 16), (1, 64, 2, 128), 64, True, "xla"),
    ("narrow_values", (1, 64, 2, 128), (1, 64, 2, 64), 64, True, "xla"),
    ("another_chunk", (1, 64, 2, 128), (1, 64, 2, 128), 32, True, "xla"),
])
def test_the_kernel_is_chosen_by_backend_and_shape(monkeypatch, why, q, v, chunk,
                                                   forced, want):
    if forced:
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    q, v = jnp.zeros(q, jnp.bfloat16), jnp.zeros(v, jnp.bfloat16)
    assert gd.kernel_route(q, q, v, chunk) == want


@pytest.mark.parametrize("dtype, want", [
    (jnp.bfloat16, "interpret"), (jnp.float32, "interpret"), (jnp.float16, "xla")])
def test_the_kernel_takes_bf16_and_float32(interpreted, dtype, want):
    x = jnp.zeros((1, 64, 2, 128), dtype)
    assert gd.kernel_route(x, x, x) == want
    assert gd.kernel_route(x, x.astype(jnp.float32), x) == (
        want if dtype == jnp.float32 else "xla")


def test_gated_delta_chunked_reaches_the_kernels(interpreted):
    """Through the entry itself: undifferentiated, the forward kernel with o
    alone; differentiated, the one that also keeps S0, and the backward."""
    args, cotangent = kernel_inputs("ragged_tail", jnp.bfloat16)
    assert calls(gd.gated_delta_chunked, *args) == [("gdn_rule_fwd", 1)]
    both = lambda *a: jax.vjp(gd.gated_delta_chunked, *a)[1](cotangent)
    assert calls(both, *args) == [("gdn_rule_fwd_keep", 2), ("gdn_rule_bwd", 5)]
    # the pass of a jax.checkpoint that keeps nothing writes o alone too
    remat = lambda *a: jax.vjp(jax.checkpoint(gd.gated_delta_chunked), *a)[1](cotangent)
    assert calls(remat, *args) == [("gdn_rule_fwd", 1), ("gdn_rule_fwd_keep", 2),
                                   ("gdn_rule_bwd", 5)]


def test_off_a_tpu_the_xla_form_runs():
    args, _ = kernel_inputs("ragged_tail", jnp.bfloat16)
    assert calls(gd.gated_delta_chunked, *args) == []


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernels_equal_recurrent_in_float32(interpreted, name):
    """o and all five gradients; float32 inputs take every product at
    float32 accuracy, so the kernels agree with the recurrence as the XLA
    form does at HIGHEST."""
    inputs = kernel_inputs(name, jnp.float32)
    got, want = kernel_answers(*inputs), recurrent_answers(*inputs)
    assert got[0].shape == want[0].shape and got[0].dtype == jnp.float32
    for a, b, part in zip(got, want, PARTS):
        assert bool(jnp.all(jnp.isfinite(a))), part
        tol = dict(rtol=2e-4, atol=2e-5) if part == "o" else dict(rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(a, b, err_msg=part, **tol)


@pytest.mark.parametrize("name", sorted(set(KERNEL_CASES) - {"beta_zero"}))
def test_kernels_read_no_further_from_the_recurrence_than_the_xla_form(
        interpreted, monkeypatch, name):
    """bf16 q, k and v: each of o, dq, dk, dv, dg, dbeta as a share of the
    recurrence's norm (the benchmark's ``state_gaps``); the kernels' worst
    part at most 1.15 times the XLA form's on the same numbers."""
    inputs = kernel_inputs(name, jnp.bfloat16)
    want = recurrent_answers(*inputs)
    got = kernel_answers(*inputs)
    monkeypatch.delenv("SXT_FUSED_INTERPRET")
    xla = xla_answers(*inputs)
    gap = lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
    ours = {part: gap(a, b) for a, b, part in zip(got, want, PARTS)}
    theirs = {part: gap(a, b) for a, b, part in zip(xla, want, PARTS)}
    assert max(ours.values()) <= 1.15 * max(theirs.values()), (ours, theirs)
    assert max(ours.values()) < 0.01, ours


def test_kernels_write_nothing_where_beta_is_zero(interpreted):
    """beta 0: no token writes, the state stays 0 and so does o; every
    gradient but beta's is 0 and all are finite (bf16 inputs)."""
    got = kernel_answers(*kernel_inputs("beta_zero", jnp.bfloat16))
    for a, part in zip(got, PARTS):
        assert bool(jnp.all(jnp.isfinite(a))), part
        if part != "dbeta":
            assert float(jnp.max(jnp.abs(a))) == 0.0, part


@pytest.mark.parametrize("width", [2, 4])
def test_causal_conv_is_the_definition(width):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (width, 5))
    got = gd.causal_conv1d(x, w)
    want = np.zeros(x.shape, np.float32)
    for t in range(9):
        for j in range(width):
            s = t - (width - 1) + j
            if s >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, s])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # nothing of a later position reaches an earlier one
    bumped = gd.causal_conv1d(x.at[:, 6].add(1.0), w)
    np.testing.assert_array_equal(bumped[:, :6], got[:, :6])


def test_l2norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7))
    np.testing.assert_allclose(
        gd.l2norm(x), x / np.sqrt((np.asarray(x) ** 2).sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)


# ----------------------------------------------------------------------
# The mixer's prologue (``gdn_prologue``): kernels against the composition
# ----------------------------------------------------------------------

PROLOGUE_PARTS = ("q", "k", "v", "z", "dqkvz", "dconv_w")


def prologue_inputs(K, rep, dtype, T=150, B=2, Hk=2, dk=128, dv=128):
    """(qkvz, conv_w) as a mixer has them and cotangents of q, k, v, z: 150
    tokens in blocks of 64 rows is a first block (zeros before position 0),
    one with a block on both sides, and a ragged last one."""
    W = 2 * dk + 2 * rep * dv
    ks = jax.random.split(jax.random.PRNGKey(10 * K + rep), 6)
    qkvz = jax.random.normal(ks[0], (B, T, Hk * W)).astype(dtype)
    conv_w = 0.5 * jax.random.normal(ks[1], (K, 2 * Hk * dk + Hk * rep * dv))
    cotangents = tuple(jax.random.normal(k, (B, T, Hk * rep, d)).astype(dtype)
                       for k, d in zip(ks[2:], (dk, dk, dv, dv)))
    return (qkvz, conv_w), cotangents


def prologue_answers(args, cotangents, exact=False, heads=(2, 128, 128)):
    """(q, k, v, z, dqkvz, dconv_w) of ``gdn_prologue`` (route chosen while
    tracing) in float32; ``exact``: the composition on the same numbers held
    in float32 throughout. ``heads``: (key heads, dk, dv)."""
    def both(args, cotangents):
        qkvz, conv_w = args
        if exact:
            qkvz = qkvz.astype(jnp.float32)
            cotangents = tuple(c.astype(jnp.float32) for c in cotangents)
        out, back = jax.vjp(lambda x, w: gd.gdn_prologue(x, w, *heads, rows=64),
                            qkvz, conv_w)
        return tuple(a.astype(jnp.float32) for a in out + back(cotangents))
    return jax.jit(both)(args, cotangents)


# (key heads, dk, dv, value heads a key head, taps, tokens): heads of whole
# lane tiles (a key head a grid step), then Olmo-Hybrid's 96 / 192 (two key
# heads a grid step, their segments between lane tiles), 2 key heads and the
# model's 30, 80 tokens (a ragged second block of 64 rows) and whole blocks;
# last 192 / 192: ONE key head a grid step with its segments between lane tiles
PROLOGUE_CASES = [(2, 128, 128, rep, K, 150) for K in (2, 4) for rep in (1, 2)] + [
    (Hk, 96, 192, 1, 4, T) for Hk in (2, 30) for T in (80, 128)] + [
    (2, 192, 192, 1, 4, 80)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("Hk, dk, dv, rep, K, T", PROLOGUE_CASES,
                         ids=lambda v: str(v))
def test_prologue_kernels_equal_the_composition(monkeypatch, Hk, dk, dv, rep, K, T,
                                                dtype):
    """``silu(causal_conv1d)`` -> split -> ``l2norm`` -> repeat against the
    one pass, outputs and the gradients of ``qkvz`` and ``conv_w``. float32:
    the same numbers to rounding. bf16: the pass rounds once, at the write,
    where the composition rounds the convolution's result and again after
    the norm: no part is further from the float32 composition than the
    composition in bf16 is."""
    heads = (Hk, dk, dv)
    args, cotangents = prologue_inputs(K, rep, dtype, T=T, Hk=Hk, dk=dk, dv=dv)
    assert gd.prologue_route(*args, dk, dv) == "xla"
    want = prologue_answers(args, cotangents, heads=heads)
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    assert gd.prologue_route(*args, dk, dv) == "interpret"
    got = prologue_answers(args, cotangents, heads=heads)
    monkeypatch.delenv("SXT_FUSED_INTERPRET")
    gap = lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
    for a, b, part in zip(got, want, PROLOGUE_PARTS):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a))), part
    if dtype == jnp.float32:
        for a, b, part in zip(got, want, PROLOGUE_PARTS):
            assert gap(a, b) < 2e-6, (part, gap(a, b))
    else:
        exact = prologue_answers(args, cotangents, exact=True, heads=heads)
        for a, b, c, part in zip(got, want, exact, PROLOGUE_PARTS):
            assert gap(a, c) <= 1.02 * gap(b, c) and gap(a, c) < 4e-3, (
                part, gap(a, c), gap(b, c))
    # z's channels of a key head's group go through as they are, both ways
    W = 2 * dk + 2 * rep * dv
    z_of = lambda x: x.reshape(2, T, Hk, W)[..., 2 * dk + rep * dv:].reshape(got[3].shape)
    np.testing.assert_array_equal(got[3], z_of(args[0].astype(jnp.float32)))
    np.testing.assert_array_equal(z_of(got[4]), cotangents[3].astype(jnp.float32))


def test_prologue_reaches_nothing_later_and_names_its_kernels(interpreted):
    """A bump at position 70 (the second block's 7th row) moves no output
    before it; differentiated, the two launches carry their own names."""
    (qkvz, conv_w), cotangents = prologue_inputs(4, 2, jnp.bfloat16)
    run = lambda x: gd.gdn_prologue(x, conv_w, 2, 128, 128, rows=64)
    plain, bumped = run(qkvz), run(qkvz.at[:, 70].add(1.0))
    for a, b in zip(plain, bumped):
        np.testing.assert_array_equal(a[:, :70], b[:, :70])
        assert bool(jnp.any(a[:, 70:74] != b[:, 70:74]))
    assert calls(run, qkvz) == [("gdn_prologue_fwd", 4)]
    both = lambda x, w: jax.vjp(lambda x, w: gd.gdn_prologue(
        x, w, 2, 128, 128, rows=64), x, w)[1](cotangents)
    assert calls(both, qkvz, conv_w) == [("gdn_prologue_fwd", 4), ("gdn_prologue_bwd", 2)]


@pytest.mark.parametrize("why, dk, dv, K, dtype, forced, want, Hk", [
    ("eligible", 128, 128, 4, jnp.bfloat16, True, "interpret", 2),
    ("float32", 128, 128, 4, jnp.float32, True, "interpret", 2),
    ("wider_heads", 256, 128, 2, jnp.bfloat16, True, "interpret", 2),
    ("a_sublane_tile_of_taps", 128, 128, 8, jnp.bfloat16, True, "interpret", 2),
    ("off_a_tpu", 128, 128, 4, jnp.bfloat16, False, "xla", 2),
    ("narrow_keys", 16, 128, 4, jnp.bfloat16, True, "xla", 2),
    ("narrow_values", 128, 64, 4, jnp.bfloat16, True, "xla", 2),
    ("more_taps_than_a_sublane_tile", 128, 128, 9, jnp.bfloat16, True, "xla", 2),
    ("float16", 128, 128, 4, jnp.float16, True, "xla", 2),
    # Olmo-Hybrid's heads: W = 576 is 4.5 lane tiles, two key heads are 9
    ("pairs_of_30_key_heads", 96, 192, 4, jnp.bfloat16, True, "interpret", 30),
    ("pairs_of_30_off_a_tpu", 96, 192, 4, jnp.bfloat16, False, "xla", 30),
    # 15 key heads do not come in pairs: no group is whole lane tiles
    ("15_key_heads_have_no_even_group", 96, 192, 4, jnp.bfloat16, True, "xla", 15),
    # the tests' 16-wide heads: 8 key heads of W = 64 would be whole tiles,
    # each segment a sixth of one
    ("narrow_heads_in_whole_tiles", 16, 16, 4, jnp.float32, True, "xla", 8),
    # 8 key heads of 96 / 200 (W = 592) are 37 lane tiles: wider than a step takes
    ("a_group_wider_than_a_step_takes", 96, 200, 4, jnp.bfloat16, True, "xla", 8),
    # and so is ONE key head of 256 / 384 (W = 1280 = 10 tiles): the bound is
    # the block's, whatever the group
    ("a_key_head_wider_than_a_step_takes", 256, 384, 4, jnp.bfloat16, True, "xla", 2),
    # one key head a step with its segments between lane tiles (W = 768)
    ("a_key_head_of_unaligned_segments", 192, 192, 4, jnp.bfloat16, True, "interpret", 2),
])
def test_the_prologue_is_chosen_by_backend_and_shape(monkeypatch, why, dk, dv, K,
                                                     dtype, forced, want, Hk):
    if forced:
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    qkvz = jnp.zeros((1, 64, Hk * (2 * dk + 2 * dv)), dtype)
    conv_w = jnp.zeros((K, Hk * (2 * dk + dv)), jnp.float32)
    assert gd.prologue_route(qkvz, conv_w, dk, dv) == want
    if want == "xla":
        # an ineligible shape runs the composition: no kernel in the program
        assert calls(lambda x, w: gd.gdn_prologue(x, w, Hk, dk, dv), qkvz, conv_w) == []


# The two launches of ``gdn_prologue`` as the commit before PR 68 lowered them
# for the TPU (4f76939; sha256 of the lowered text with every Mosaic body
# printed without its source locations, ``testing/program_text.canonical``):
# PR 68 taught the kernel bodies where the KDA mixer's segments lie, and the
# DeltaNet cells' programs did not move. (Hk, rep, dk, dv), dtype, tokens.
PROLOGUE_TEXTS = {
    "qwen3next": ((16, 2, 128, 128), jnp.bfloat16, 8192,
                  "554977398a05904252c49711c7d63c279d7784c73e178a41499a1efd557a10a9"),
    "olmohybrid": ((30, 1, 96, 192), jnp.bfloat16, 8192,
                   "ad28617db5b04908715680d58c3c1ccded0c09b192223c82660d0e1563606cb8"),
    "one_unaligned_key_head": ((4, 1, 192, 192), jnp.bfloat16, 8192,
                               "02493d04def02708c52def93484d614eaf77f759c13028957bb9ad305bc69af1"),
    "float32_ragged": ((2, 2, 128, 128), jnp.float32, 600,
                       "8499b1e0d2f905fe46c639580198cd0f48bc99232f837f2279e0344d35cdcf64"),
}


@pytest.mark.parametrize("case", list(PROLOGUE_TEXTS))
def test_the_prologues_launches_lower_to_the_text_they_had(case):
    """At the shapes ``qwen3next-train`` and ``olmohybrid-zero3-x4`` run the
    prologue (a key head, a pair of them a grid step), at the third shape the
    route admits and on a ragged float32 one. The text is this container's
    JAX's: after an upgrade that changes the printer, take the hashes again
    from a commit that is known good and say so."""
    from shuffle_exchange_tpu.testing import program_text

    (Hk, rep, dk, dv), dtype, T, want = PROLOGUE_TEXTS[case]

    def both(qkvz, conv_w, *cotangents):
        out, back = jax.vjp(lambda x, w: gd._gdn_prologue_pallas(x, w, Hk, dk, dv),
                            qkvz, conv_w)
        return out + back(cotangents)

    shaped = jax.ShapeDtypeStruct
    wide = lambda d: shaped((2, T, Hk * rep, d), dtype)
    text = jax.jit(both).trace(
        shaped((2, T, Hk * (2 * dk + 2 * rep * dv)), dtype),
        shaped((4, Hk * (2 * dk + rep * dv)), jnp.float32),
        wide(dk), wide(dk), wide(dv), wide(dv)).lower(lowering_platforms=("tpu",)).as_text()
    got = program_text.hashes(text)
    assert got["mosaic_bodies"] == 2
    assert got["lowered_no_locations_sha"] == want
