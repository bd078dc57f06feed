"""The state-space mixer's convolution (``ops/ssm_conv.py``): the two Pallas
kernels, interpreted on the CPU, against ``silu(causal_conv1d(xBC, w, b))``
written here, at a tiny size: 2 sequences, [z 256 | x 256 | B 128 | C 128 |
dt 128] columns, blocks of 64 rows. 150 tokens is a first block (zeros
before position 0), one with a block on both sides and a ragged last one;
128 is two whole blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.ops import gated_delta as gd
from shuffle_exchange_tpu.ops import ssm_conv as sc

START, WIDTHS, AFTER = 256, (256, 128, 128), 128
CHANNELS = sum(WIDTHS)
PARTS = ("z", "x", "B", "C", "dt", "dzxbcdt", "dconv_w", "dconv_b")


def inputs(K, dtype, T=150):
    """(zxbcdt, conv_w, conv_b) as a mixer has them and cotangents of z, x,
    B, C, dt."""
    ks = jax.random.split(jax.random.PRNGKey(10 * K + T), 8)
    zxbcdt = jax.random.normal(ks[0], (2, T, START + CHANNELS + AFTER)).astype(dtype)
    conv_w = 0.5 * jax.random.normal(ks[1], (K, CHANNELS))
    conv_b = 0.3 * jax.random.normal(ks[2], (CHANNELS,))
    cotangents = tuple(jax.random.normal(k, (2, T, n)).astype(dtype)
                       for k, n in zip(ks[3:], (START, *WIDTHS, AFTER)))
    return (zxbcdt, conv_w, conv_b), cotangents


def composition(zxbcdt, conv_w, conv_b):
    """What the kernels compute, as the mixer wrote it before them."""
    y = jax.nn.silu(gd.causal_conv1d(
        zxbcdt[..., START:START + CHANNELS].astype(jnp.float32), conv_w, conv_b)
    ).astype(zxbcdt.dtype)
    x, B, C = jnp.split(y, [WIDTHS[0], WIDTHS[0] + WIDTHS[1]], axis=-1)
    return zxbcdt[..., :START], x, B, C, zxbcdt[..., START + CHANNELS:]


def run(zxbcdt, conv_w, conv_b):
    return sc.ssm_conv(zxbcdt, conv_w, conv_b, START, WIDTHS, rows=64)


def answers(fn, args, cotangents, exact=False):
    """(z, x, B, C, dt, dzxbcdt, dconv_w, dconv_b) of ``fn`` in float32
    (``ssm_conv``'s route is chosen while tracing); ``exact``: on the same
    numbers held in float32 throughout."""
    def both(args, cotangents):
        zxbcdt, conv_w, conv_b = args
        if exact:
            zxbcdt = zxbcdt.astype(jnp.float32)
            cotangents = tuple(c.astype(jnp.float32) for c in cotangents)
        out, back = jax.vjp(lambda *a: fn(*a), zxbcdt, conv_w, conv_b)
        return tuple(a.astype(jnp.float32) for a in out + back(cotangents))
    return jax.jit(both)(args, cotangents)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")


def calls(fn, *args):
    """The names of the ``pallas_call``s in ``fn``'s jaxpr, with the number
    of results each has (traced anew every time: the route is chosen while
    tracing)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], len(eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("T", [128, 150], ids=["whole_blocks", "ragged"])
@pytest.mark.parametrize("K", [4, 2])
def test_kernels_equal_the_composition(interpreted, K, T, dtype):
    """Outputs and the gradients of ``zxbcdt``, the taps and the bias.
    float32: the same numbers to rounding. bf16: the forward has the
    composition's roundings (float32 from the read to one rounding at the
    write), the backward rounds dx once where the composition's transpose
    rounds what it passes between XLA's fusions: no part is further from
    the float32 composition than the composition in bf16 is (the taps' and
    the bias's gradients are float32 sums on both sides)."""
    args, cotangents = inputs(K, dtype, T)
    got = answers(run, args, cotangents)
    want = answers(composition, args, cotangents)
    gap = lambda a, b: float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
    for a, b, part in zip(got, want, PARTS):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a))), part
    if dtype == jnp.float32:
        for a, b, part in zip(got, want, PARTS):
            assert gap(a, b) < 2e-6, (part, gap(a, b))
    else:
        exact = answers(composition, args, cotangents, exact=True)
        for a, b, c, part in zip(got, want, exact, PARTS):
            assert gap(a, c) <= 1.02 * gap(b, c) + 2e-6 and gap(a, c) < 4e-3, (
                part, gap(a, c), gap(b, c))
        # the forward's roundings are the composition's: an element in a
        # few thousand lands on the other side of a bf16 tie
        for a, b, part in zip(got[1:4], want[1:4], PARTS[1:4]):
            assert float(jnp.mean(a != b)) < 1e-3 and gap(a, b) < 3e-4, (part, gap(a, b))
    # z and dt go through as they are, both ways
    zxbcdt, dz, ddt = args[0].astype(jnp.float32), cotangents[0], cotangents[4]
    np.testing.assert_array_equal(got[0], zxbcdt[..., :START])
    np.testing.assert_array_equal(got[4], zxbcdt[..., START + CHANNELS:])
    np.testing.assert_array_equal(got[5][..., :START], dz.astype(jnp.float32))
    np.testing.assert_array_equal(got[5][..., START + CHANNELS:], ddt.astype(jnp.float32))


@pytest.mark.parametrize("at", [63, 64, 70, 149],
                         ids=["a_blocks_last_row", "a_blocks_first_row", "inside_a_block",
                              "a_sequences_last_row"])
def test_nothing_reaches_a_later_position_or_the_next_sequence(interpreted, at):
    """A bump of the first sequence's position ``at`` moves the outputs of
    that sequence at ``at`` .. ``at`` + 3 and no other (none before it, none
    of the second sequence: its first block's history is zeros, not the
    first sequence's tail); a bump of the cotangent there moves the input's
    gradient at ``at`` - 3 .. ``at`` of that sequence and no other."""
    (zxbcdt, conv_w, conv_b), cotangents = inputs(4, jnp.bfloat16)
    conv = slice(START, START + CHANNELS)

    def moved(a, b):
        rows = np.asarray(jnp.any(a.astype(jnp.float32) != b.astype(jnp.float32), axis=-1))
        return {(s, t) for s, t in zip(*np.nonzero(rows))}

    bumped = zxbcdt.at[0, at, conv].add(1.0)
    for a, b in zip(run(zxbcdt, conv_w, conv_b)[1:4], run(bumped, conv_w, conv_b)[1:4]):
        assert moved(a, b) == {(0, t) for t in range(at, min(at + 4, 150))}

    back = jax.vjp(lambda x: run(x, conv_w, conv_b), zxbcdt)[1]
    pushed = tuple(c.at[0, at].add(1.0) if 1 <= i <= 3 else c
                   for i, c in enumerate(cotangents))
    (plain,), (other,) = back(cotangents), back(pushed)
    assert moved(plain[..., conv], other[..., conv]) == {
        (0, t) for t in range(max(at - 3, 0), at + 1)}


def test_the_first_blocks_history_is_zeros(interpreted):
    """Position 0 sees the last tap alone, position 1 the last two."""
    (zxbcdt, conv_w, conv_b), _ = inputs(4, jnp.float32)
    got = jnp.concatenate(run(zxbcdt, conv_w, conv_b)[1:4], axis=-1)
    xbc = zxbcdt[..., START:START + CHANNELS]
    np.testing.assert_allclose(
        got[:, 0], jax.nn.silu(conv_w[3] * xbc[:, 0] + conv_b), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        got[:, 1], jax.nn.silu(conv_w[3] * xbc[:, 1] + conv_w[2] * xbc[:, 0] + conv_b),
        rtol=1e-6, atol=1e-6)


def test_the_launches_carry_their_names(interpreted):
    """A launch a segment; differentiated, the forward's and the
    backward's (dx and the weights' partial sums)."""
    args, cotangents = inputs(4, jnp.bfloat16)
    assert calls(run, *args) == [("ssm_conv_fwd", 1)] * 3
    both = lambda *a: jax.vjp(run, *a)[1](cotangents)
    assert calls(both, *args) == [("ssm_conv_fwd", 1)] * 3 + [("ssm_conv_bwd", 2)] * 3


@pytest.mark.parametrize("why, start, widths, K, dtype, forced, want", [
    ("eligible", 256, (256, 128, 128), 4, jnp.bfloat16, True, "interpret"),
    ("float32", 256, (256, 128, 128), 4, jnp.float32, True, "interpret"),
    ("a_sublane_tile_of_taps", 256, (256, 128, 128), 8, jnp.bfloat16, True, "interpret"),
    ("the_cells_columns", 4096, (4096, 1024, 1024), 4, jnp.bfloat16, True, "interpret"),
    ("off_a_tpu", 256, (256, 128, 128), 4, jnp.bfloat16, False, "xla"),
    ("a_start_inside_a_lane_tile", 64, (256, 128, 128), 4, jnp.bfloat16, True, "xla"),
    ("a_segment_inside_a_lane_tile", 256, (256, 32, 32), 4, jnp.bfloat16, True, "xla"),
    ("more_taps_than_a_sublane_tile", 256, (256, 128, 128), 9, jnp.bfloat16, True, "xla"),
    ("float16", 256, (256, 128, 128), 4, jnp.float16, True, "xla"),
])
def test_the_form_is_chosen_by_backend_and_shape(monkeypatch, why, start, widths, K,
                                                 dtype, forced, want):
    if forced:
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    zxbcdt = jnp.zeros((1, 64, start + sum(widths) + 64), dtype)
    conv_w, conv_b = jnp.zeros((K, sum(widths))), jnp.zeros((sum(widths),))
    assert sc.ssm_conv_route(zxbcdt, conv_w, start, widths) == want
    if want == "xla":
        # an ineligible shape runs the composition: no kernel in the program
        fn = lambda x, w, b: sc.ssm_conv(x, w, b, start, widths)
        assert calls(fn, zxbcdt, conv_w, conv_b) == []
        assert [a.shape[-1] for a in fn(zxbcdt, conv_w, conv_b)] == [start, *widths, 64]


@pytest.mark.parametrize("at, at_w, width, want", [
    (4096, 0, 4096, 1024), (8192, 4096, 1024, 1024), (9216, 5120, 1024, 1024),
    (256, 0, 256, 256), (640, 384, 128, 128), (1536, 0, 1536, 768)])
def test_a_grid_steps_lanes_reach_the_segment_in_both_arrays(at, at_w, width, want):
    """The widest block of whole lane tiles, up to 1024, whose index lands
    on the segment's first column of the activations and of the weights."""
    block = sc._lane_block(at, at_w, width)
    assert block == want
    assert at % block == at_w % block == width % block == 0
