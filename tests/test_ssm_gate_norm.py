"""The state-space mixer's epilogue (``ops/ssm_gate_norm.py``): the two Pallas
kernels, interpreted on the CPU, against the XLA composition (the skip, the
gate, the grouped RMSNorm by the groups' indicator) and that against the
reference's ``gated_norm`` after the skip, at tiny sizes: 2 sequences, blocks
of 64 rows; 8 groups of 512 channels under 64 heads of 64 (the benchmark
cell's widths) and 2 groups of 128 under 8 heads of 32. 150 tokens a sequence
is 300 rows: four whole blocks and a ragged one; 128 is four whole blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.models import reference_nemotron3 as ref
from shuffle_exchange_tpu.ops import ssm_gate_norm as gn
from tests.test_ssm_conv import calls

EPS = 1e-5
SHAPES = {"8x512": (4096, 8, 64), "2x128": (256, 2, 8)}       # inner, groups, heads
PARTS = ("out", "do", "dx", "dz", "dD", "dgain")


def inputs(shape, dtype, T=150):
    """(o, x, z, D, gain) as a mixer has them and the output's cotangent."""
    inner, _, heads = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(inner + T), 6)
    o, x, z, dout = (jax.random.normal(k, (2, T, inner)).astype(dtype) for k in ks[:4])
    D = jax.random.normal(ks[4], (heads,))
    gain = 1.0 + 0.3 * jax.random.normal(ks[5], (inner,))
    return (o, x, z, D, gain), dout


def run(shape):
    return lambda *a: gn.ssm_gate_norm(*a, SHAPES[shape][1], EPS, rows=64)


def composition(shape):
    return lambda *a: gn._ssm_gate_norm_xla(*a, SHAPES[shape][1], EPS)


def answers(fn, args, dout, exact=False):
    """(out, do, dx, dz, dD, dgain) of ``fn`` in float32 (``ssm_gate_norm``'s
    route is chosen while tracing); ``exact``: on the same numbers held in
    float32 throughout."""
    def both(args, dout):
        if exact:
            args = tuple(a.astype(jnp.float32) for a in args)
            dout = dout.astype(jnp.float32)
        out, back = jax.vjp(lambda *a: fn(*a), *args)
        return tuple(a.astype(jnp.float32) for a in (out,) + back(dout))
    return jax.jit(both)(args, dout)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")


gap = lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("T", [128, 150], ids=["whole_blocks", "ragged"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernels_equal_the_composition(interpreted, shape, T, dtype):
    """The output and the gradients of o, x, z, the skip and the gain.
    float32: the same numbers to rounding. bf16: the forward has the
    composition's roundings (float32 from the reads to one rounding at the
    write); the backward rounds d o and d z once, and x's gradient is ``D``
    times the ROUNDED d o (as it was when the scan added the skip), where the
    composition's transpose rounds ``D d u`` once: x's part is a bf16 rounding
    from the float32 composition, every other part no further from it than
    the composition in bf16 is."""
    args, dout = inputs(shape, dtype, T)
    assert gn.ssm_gate_norm_route(args[0], SHAPES[shape][1]) == "interpret"
    got = answers(run(shape), args, dout)
    want = answers(composition(shape), args, dout)
    for a, b, part in zip(got, want, PARTS):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a))), part
    if dtype == jnp.float32:
        for a, b, part in zip(got, want, PARTS):
            assert gap(a, b) < 2e-6, (part, gap(a, b))
        return
    exact = answers(composition(shape), args, dout, exact=True)
    for a, b, c, part in zip(got, want, exact, PARTS):
        room = 4e-3 if part == "dx" else 1.02 * gap(b, c) + 2e-6
        assert gap(a, c) <= room and gap(a, c) < 4e-3, (part, gap(a, c), gap(b, c))
    # the forward's roundings are the composition's: an element in a few
    # thousand lands on the other side of a bf16 tie
    assert float(jnp.mean(got[0] != want[0])) < 1e-3 and gap(got[0], want[0]) < 3e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_composition_is_the_references_gated_norm_after_the_skip(shape, dtype):
    """``o + D x`` in float32, then ``models/reference_nemotron3.gated_norm``
    (the gate FIRST, the [.., G, inner / G] view): the output and the five
    gradients, on the route a CPU takes without the variable."""
    args, dout = inputs(shape, dtype)
    inner, groups, heads = SHAPES[shape]
    assert gn.ssm_gate_norm_route(args[0], groups) == "xla"

    def reference(o, x, z, D, gain):
        u = o.astype(jnp.float32) + jnp.repeat(D, inner // heads) * x.astype(jnp.float32)
        return ref.gated_norm(u, z, gain, groups, EPS).astype(o.dtype)

    got = answers(lambda *a: gn.ssm_gate_norm(*a, groups, EPS), args, dout)
    want = answers(reference, args, dout)
    for a, b, part in zip(got, want, PARTS):
        assert gap(a, b) < (2e-6 if dtype == jnp.float32 else 3e-4), (part, gap(a, b))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_nothing_reaches_another_group_or_another_row(interpreted, shape):
    """A bump inside one group of one row (of o, of x, of z, of the
    cotangent) moves that row's output and gradients inside that group and
    nothing else: no other group of the row, no other row of the block, no
    other block."""
    inner, groups, _ = SHAPES[shape]
    n = inner // groups
    args, dout = inputs(shape, jnp.bfloat16)
    row, group = (1, 70), groups - 1
    at = (*row, group * n + 5)
    plain = answers(run(shape), args, dout)[:4]

    def moved(a, b):
        where = np.asarray(a != b).reshape(2, -1, groups, n).any(axis=-1)
        return {tuple(int(i) for i in w) for w in zip(*np.nonzero(where))}

    for which in range(4):
        bumped = list(args) + [dout]
        bumped[which if which < 3 else 5] = bumped[which if which < 3 else 5].at[at].add(1.0)
        other = answers(run(shape), tuple(bumped[:5]), bumped[5])[:4]
        for a, b, part in zip(plain, other, PARTS):
            if which == 3 and part == "out":
                assert moved(a, b) == set()                  # the cotangent moves no output
            else:
                assert moved(a, b) == {(*row, group)}, (which, part)


@pytest.mark.parametrize("T", [128, 150], ids=["whole_blocks", "ragged"])
def test_z_is_read_where_it_lies_and_the_gradient_is_zs(interpreted, T):
    """``z_in``: z as the first columns of a wider array (the projection's
    output). The same numbers bit for bit as reading z; the whole gradient
    goes to z, none to ``z_in``; and the kernels' operand is ``z_in`` itself
    (ragged rows read z: the wide array is not padded)."""
    (o, x, z, D, gain), dout = inputs("2x128", jnp.bfloat16, T)
    wide = jnp.concatenate([z, 7.0 + jnp.zeros((2, T, 384), z.dtype)], axis=-1)
    fn = lambda o, x, z, wide, D, gain: gn.ssm_gate_norm(
        o, x, z, D, gain, 2, EPS, rows=64, z_in=wide)
    out, back = jax.vjp(fn, o, x, z, wide, D, gain)
    want, back_plain = jax.vjp(run("2x128"), o, x, z, D, gain)
    np.testing.assert_array_equal(out.astype(jnp.float32), want.astype(jnp.float32))
    got, plain = back(dout), back_plain(dout)
    for a, b in zip(got[:3] + got[4:], plain):
        np.testing.assert_array_equal(a.astype(jnp.float32), b.astype(jnp.float32))
    assert not bool(jnp.any(got[3]))
    # what the launch reads: the wide array where the rows are whole blocks
    jaxpr = jax.make_jaxpr(fn)(o, x, z, wide, D, gain)
    read = [v.aval.shape for eqn in jaxpr.eqns if eqn.primitive.name == "custom_vjp_call"
            for v in eqn.invars]
    assert ((2, T, 640) in read) == (T % 64 == 0), read


def test_the_launches_carry_their_names(interpreted):
    """One launch forward; differentiated, the forward's and the backward's
    (d o, d z and the weights' partial sums)."""
    args, dout = inputs("2x128", jnp.bfloat16)
    assert calls(run("2x128"), *args) == [("ssm_gate_norm_fwd", 1)]
    both = lambda *a: jax.vjp(run("2x128"), *a)[1](dout)
    assert calls(both, *args) == [("ssm_gate_norm_fwd", 1), ("ssm_gate_norm_bwd", 3)]


@pytest.mark.parametrize("why, inner, groups, dtype, forced, want", [
    ("eligible", 256, 2, jnp.bfloat16, True, "interpret"),
    ("float32", 256, 2, jnp.float32, True, "interpret"),
    ("the_cells_groups", 4096, 8, jnp.bfloat16, True, "interpret"),
    ("a_group_of_eight_lane_tiles", 2048, 2, jnp.bfloat16, True, "interpret"),
    ("off_a_tpu", 256, 2, jnp.bfloat16, False, "xla"),
    ("a_group_inside_a_lane_tile", 256, 4, jnp.bfloat16, True, "xla"),
    ("a_group_of_one_and_a_half_lane_tiles", 384, 2, jnp.bfloat16, True, "xla"),
    ("a_group_wider_than_the_registers_hold", 2304, 2, jnp.bfloat16, True, "xla"),
    ("groups_that_do_not_divide_the_channels", 384, 5, jnp.bfloat16, True, "xla"),
    ("float16", 256, 2, jnp.float16, True, "xla"),
])
def test_the_form_is_chosen_by_backend_and_shape(monkeypatch, why, inner, groups, dtype,
                                                 forced, want):
    if forced:
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    o = jnp.zeros((1, 64, inner), dtype)
    assert gn.ssm_gate_norm_route(o, groups) == want
    if want == "xla" and inner % groups == 0:
        # an ineligible shape runs the composition: no kernel in the program
        fn = lambda o, D, gain: gn.ssm_gate_norm(o, o, o, D, gain, groups, EPS)
        D, gain = jnp.zeros((groups,)), jnp.ones((inner,))
        assert calls(fn, o, D, gain) == []
        assert fn(o, D, gain).shape == o.shape and fn(o, D, gain).dtype == dtype


@pytest.mark.parametrize("tiles, inner, want", [
    (4, 4096, 1024), (1, 256, 256), (1, 4096, 1024), (8, 2048, 1024), (3, 768, 768),
    (5, 1280, 640), (8, 3072, 1024)])
def test_a_grid_steps_lanes_hold_whole_groups(tiles, inner, want):
    """The most whole groups up to 1024 lanes (one group where it is wider)
    that divide the channels."""
    block = gn._lane_block(tiles, inner)
    assert block == want
    assert block % (tiles * 128) == 0 and inner % block == 0
