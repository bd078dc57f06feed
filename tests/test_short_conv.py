"""The pass between a convolution mixer's two projections
(``ops/short_conv.py``): the two Pallas kernels, interpreted on the CPU,
against the XLA body of the same equation, at a tiny size: 2 sequences, three
blocks of 256 channels, row blocks of 64. 192 tokens are a first block (zeros
before position 0), one with a block on both sides and a last one (zeros
after the sequence's end); 64 tokens are one block that is all three.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.ops import short_conv as sc
from tests.test_ssm_conv import calls

C = 256
PARTS = ("y", "dB", "dC", "dx", "dw")


def inputs(K, dtype, T=192, channels=C):
    """(bcx, w) as a mixer has them and a cotangent of the pass's output."""
    ks = jax.random.split(jax.random.PRNGKey(10 * K + T), 3)
    bcx = jax.random.normal(ks[0], (2, T, 3 * channels)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (K, channels))).astype(dtype)
    return (bcx, w), jax.random.normal(ks[2], (2, T, channels)).astype(dtype)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels through the interpreter, 64 rows a grid step."""
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    monkeypatch.setattr(sc, "ROWS", 64)


def answers(fn, args, dy):
    """(y, dB, dC, dx, dw) of ``fn`` in float32 (``sconv_mix``'s route is
    chosen while tracing: a new function each time)."""
    def both(args, dy):
        y, back = jax.vjp(lambda *a: fn(*a), *args)
        dbcx, dw = back(dy)
        return tuple(a.astype(jnp.float32) for a in (y, *jnp.split(dbcx, 3, axis=-1), dw))
    return [np.asarray(a) for a in jax.jit(both)(args, dy)]


def within_a_bf16_ulp(a, b):
    return bool(np.all(np.abs(a - b) <= np.abs(b) * 2.0 ** -7))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("T", [64, 192], ids=["one_block", "three_blocks"])
@pytest.mark.parametrize("K", [2, 3, 4])
def test_kernels_equal_the_xla_body(interpreted, K, T, dtype):
    """The output and the gradients of the three blocks of ``bcx`` and of
    the taps. float32: the forward bit for bit (the same products summed in
    the same order), the gradients to float32's summation order. bf16: both
    bodies hold float32 from the read to ONE rounding at each write, so
    every number is the XLA body's or its neighbour."""
    args, dy = inputs(K, dtype, T)
    assert sc.sconv_route(*args) == "interpret"
    got, want = answers(sc.sconv_mix, args, dy), answers(sc._sconv_mix_xla, args, dy)
    gap = lambda a, b: float(np.linalg.norm(a - b) / np.linalg.norm(b))
    for a, b, part in zip(got, want, PARTS):
        assert a.shape == b.shape and np.all(np.isfinite(a)), part
    np.testing.assert_array_equal(got[0], want[0])
    for a, b, part in zip(got[1:], want[1:], PARTS[1:]):
        if dtype == jnp.float32:
            assert gap(a, b) < 2e-6, (part, gap(a, b))
        else:
            assert within_a_bf16_ulp(a, b) and float(np.mean(a != b)) < 5e-3, (
                part, gap(a, b), float(np.mean(a != b)))


@pytest.mark.parametrize("seq, at", [(0, 63), (0, 64), (0, 70), (0, 191), (1, 0), (1, 1)],
                         ids=["a_blocks_last_row", "a_blocks_first_row", "inside_a_block",
                              "the_first_sequences_last_row", "the_second_sequences_first_row",
                              "the_second_sequences_second_row"])
def test_nothing_reaches_an_earlier_position_or_another_sequence(interpreted, seq, at):
    """A bump of x at one position moves the outputs of that sequence at
    that position and the two after it and no other (none before it; none of
    the next sequence, whose first block's history is zeros, not this
    sequence's tail); a bump of the cotangent there moves the input's
    gradient at the two positions before it and at it and no other (none of
    the sequence before, whose last block's rows ahead are zeros, not this
    sequence's head)."""
    (bcx, w), dy = inputs(3, jnp.bfloat16)

    def moved(a, b):
        rows = np.asarray(jnp.any(a.astype(jnp.float32) != b.astype(jnp.float32), axis=-1))
        return {(int(s), int(t)) for s, t in zip(*np.nonzero(rows))}

    bumped = bcx.at[seq, at, 2 * C:].add(1.0)
    assert moved(sc.sconv_mix(bcx, w), sc.sconv_mix(bumped, w)) == {
        (seq, t) for t in range(at, min(at + 3, 192))}
    back = jax.vjp(lambda x: sc.sconv_mix(x, w), bcx)[1]
    (plain,), (other,) = back(dy), back(dy.at[seq, at].add(1.0))
    assert moved(plain, other) == {(seq, t) for t in range(max(at - 2, 0), at + 1)}


@pytest.mark.parametrize("K", [2, 3, 4])
@pytest.mark.parametrize("row", [63, 191], ids=["a_blocks_last_row", "the_sequences_last_row"])
def test_a_cotangent_of_one_row(interpreted, row, K):
    """A cotangent that is zero but for one row: the gradients reach the
    K - 1 rows before it through the rows AHEAD of theirs (the next block's
    first, or nothing after the sequence's last) and are the XLA body's."""
    args, dy = inputs(K, jnp.float32)
    dy = jnp.zeros_like(dy).at[:, row].set(dy[:, row])
    got, want = answers(sc.sconv_mix, args, dy), answers(sc._sconv_mix_xla, args, dy)
    for a, b, part in zip(got[1:], want[1:], PARTS[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=part)
    for part in got[1:4]:
        rows = np.nonzero(np.abs(part).max(axis=(0, 2)))[0]
        assert rows.min() >= row - (K - 1) and rows.max() == row


def test_the_first_blocks_history_is_zeros(interpreted):
    """Position 0 sees the last tap alone, position 1 the last two."""
    (bcx, w), _ = inputs(3, jnp.float32)
    got = sc.sconv_mix(bcx, w)
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
    u = gate_in * x
    np.testing.assert_allclose(got[:, 0], gate_out[:, 0] * w[2] * u[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[:, 1], gate_out[:, 1] * (w[1] * u[:, 0] + w[2] * u[:, 1]),
                               rtol=1e-6, atol=1e-6)


def test_the_launches_carry_their_names(interpreted):
    """One launch forward; differentiated, the forward's and the backward's
    (d ``bcx`` and the taps' partial sums): ``bcx`` and the taps are all the
    backward keeps."""
    args, dy = inputs(3, jnp.bfloat16)
    assert calls(sc.sconv_mix, *args) == [("sconv_mix_fwd", 1)]
    both = lambda *a: jax.vjp(sc.sconv_mix, *a)[1](dy)
    assert calls(both, *args) == [("sconv_mix_fwd", 1), ("sconv_mix_bwd", 2)]


@pytest.mark.parametrize("why, shape, K, dtype, where, want", [
    ("on_the_cpu", (2, 192, 3 * C), 3, jnp.bfloat16, "cpu", "xla"),
    ("interpreted", (2, 192, 3 * C), 3, jnp.bfloat16, "forced", "interpret"),
    ("float32", (2, 192, 3 * C), 3, jnp.float32, "forced", "interpret"),
    ("a_sublane_tile_of_taps", (2, 192, 3 * C), 8, jnp.bfloat16, "forced", "interpret"),
    ("the_cells_shapes", (8, 4096, 3 * 2048), 3, jnp.bfloat16, "tpu", "pallas"),
    ("the_cells_shapes_in_float32", (8, 4096, 3 * 2048), 3, jnp.float32, "tpu", "pallas"),
    ("channels_inside_a_lane_tile", (2, 192, 3 * 192), 3, jnp.bfloat16, "tpu", "xla"),
    ("rows_that_do_not_divide", (2, 100, 3 * C), 3, jnp.bfloat16, "tpu", "xla"),
    ("rows_too_wide_for_a_block", (2, 192, 3 * 65536), 3, jnp.float32, "tpu", "xla"),
    ("more_taps_than_a_sublane_tile", (2, 192, 3 * C), 9, jnp.bfloat16, "tpu", "xla"),
    ("float16", (2, 192, 3 * C), 3, jnp.float16, "tpu", "xla"),
    ("rows_alone", (192, 3 * C), 3, jnp.bfloat16, "tpu", "xla"),
])
def test_the_form_is_chosen_by_backend_and_shape(monkeypatch, why, shape, K, dtype, where, want):
    if where == "forced":
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    if where == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bcx = jax.ShapeDtypeStruct(shape, dtype)
    w = jax.ShapeDtypeStruct((K, shape[-1] // 3), dtype)
    assert sc.sconv_route(bcx, w) == want


@pytest.mark.parametrize("why, T, channels, K", [
    ("channels_inside_a_lane_tile", 64, 192, 3), ("rows_that_do_not_divide", 100, C, 3),
    ("more_taps_than_a_sublane_tile", 64, C, 9)])
def test_a_refused_shape_runs_the_xla_body(interpreted, why, T, channels, K):
    """No kernel in the program, and the XLA body's bits."""
    (bcx, w), _ = inputs(K, jnp.bfloat16, T, channels)
    assert sc.sconv_route(bcx, w) == "xla"
    assert calls(sc.sconv_mix, bcx, w) == []
    np.testing.assert_array_equal(np.asarray(sc.sconv_mix(bcx, w), np.float32),
                                  np.asarray(sc._sconv_mix_xla(bcx, w), np.float32))


@pytest.mark.parametrize("T, channels, itemsize, want", [
    (4096, 2048, 2, 512), (4096, 2048, 4, 256), (1536, 2048, 2, 512), (192, 256, 2, 192),
    (64, 256, 4, 64), (8192, 8192, 2, 128), (100, 256, 2, 0), (32, 256, 2, 0),
    (4096, 65536, 4, 0)])
def test_a_grid_steps_rows_divide_the_sequence_and_fit(T, channels, itemsize, want):
    """The most rows, in whole trips of 64 and up to 512, that divide T and
    whose blocks of the backward (7 C channels a row) fit 20 MB; none: 0."""
    R = sc._row_block(T, channels, itemsize)
    assert R == want
    if R:
        assert T % R == 0 and R % 64 == 0 and 7 * channels * itemsize * R <= 20 * 2 ** 20


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_model_on_the_kernels_is_the_model_on_the_xla_body(remat, monkeypatch):
    """LFM2's stack at a hidden size of one lane tile and 64 tokens: the
    loss and every gradient leaf with the four convolution mixers on the
    interpreted kernels are those on the XLA body, per-half remat (which
    replays the forward kernel) on and off."""
    from chipbench.drivers import train_steps_sconv as driver
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.models.hf import config_from_hf
    from tests.test_lfm2 import BIAS_STD, HF, gaps

    cfg = config_from_hf(dict(HF, hidden_size=128))
    if remat:
        cfg = dataclasses.replace(cfg, remat=True, remat_policy="full")
    model = Transformer(cfg)
    params = driver.initial_params(model, 5, BIAS_STD)
    batch = {"input_ids": np.random.default_rng(3).integers(0, 128, (2, 65)).astype(np.int32)}

    def answer():
        # (a new function each time: the route is chosen while tracing)
        fn = jax.value_and_grad(lambda p, b: model.loss(p, b))
        launches = [name for name, _ in calls(fn, params, batch)]
        return jax.jit(fn)(params, batch), launches

    (want, want_grads), launches = answer()
    assert launches == []
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    (got, got_grads), launches = answer()
    forward, backward = (launches.count("sconv_mix_" + k) for k in ("fwd", "bwd"))
    assert backward > 0 and forward == (2 * backward if remat else backward)    # the replay
    assert abs(float(got) - float(want)) < 1e-6
    worst = gaps(driver.flat_tree(got_grads),
                 {k: v for k, v in driver.flat_tree(want_grads).items()
                  if float(jnp.abs(v).max()) > 0})
    assert max(worst.values()) < 1e-5, worst
