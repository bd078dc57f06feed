"""The Mamba-2 mixer's convolution (mixer "ssm" of ``models/transformer.py``):
what lies between the mixer's input projection and its scan.

The projection writes ``zxbcdt`` [B, T, inner + (inner + 2 G N) + H]; its
middle columns, ``xBC``, go through a causal depthwise convolution of K taps
a channel with a bias (zero history before position 0) and SiLU, and leave as
the scan's x [B, T, inner], B and C [B, T, G N] (``ops/ssd.py``)::

    y[t, c] = silu(b[c] + sum_j w[j, c] * xBC[t - (K - 1) + j, c])

:func:`ssm_conv` is that, with two bodies of the same arithmetic chosen by
what the code can observe (:func:`ssm_conv_route`: backend and shape, as
``ops/dispatch.py`` states it):

  Pallas kernels  on a TPU backend where the first segment's offset and every
                  segment's width are whole lane tiles (128), K <= 8 and the
                  activations bf16 or float32: ``ssm_conv_fwd`` /
                  ``ssm_conv_bwd`` behind one ``jax.custom_vjp``, a launch a
                  segment (the convolution is per channel, so the segments are
                  independent). The CPU suite drives the same kernels through
                  the interpreter (``SXT_FUSED_INTERPRET=1``). Selected, they
                  run or raise.
  XLA ops         everywhere else: ``silu(causal_conv1d(xBC in float32))`` and
                  three slices. The off-TPU path and the kernels' oracle.

What the kernels read and write. The forward reads a segment's columns of
``zxbcdt`` WHERE THEY LIE (a ``BlockSpec`` over lanes: no slice or copy of the
activations before the launch), a block of rows with the 16 rows before it
(one bf16 tile; the taps look back K - 1 <= 7 of them; zeros before position
0, and a grid step never reads another sequence's rows), accumulates the taps
and the bias and applies SiLU in float32, and rounds ONCE, at the write: the
roundings of the XLA body. The backward reads the same columns and the
segment's cotangent, computes the pre-activation again (the input and the
weights are the only residuals), takes ``d pre = dy silu'(pre)`` and ``dx[t] =
sum_j w[j] d pre[t + K - 1 - j]`` in float32 and writes dx once. That needs
the K - 1 rows of ``d pre`` AFTER a block: the grid's last axis walks the row
blocks from the last to the first and a VMEM scratch carries the first rows'
of the block after (``gated_delta._prologue_bwd_kernel``'s sweep). The taps'
and the bias's gradients are float32 partial sums (one a batch row and
sublane), summed by XLA. The columns on either side of xBC (z and dt) pass
through :func:`ssm_conv` as they are, so that d ``zxbcdt`` is ONE
concatenation of their cotangents and the three dx: taken outside, their
gradients are pads added to a zero-filled buffer of all of ``zxbcdt`` a
layer, which XLA's scheduler, free to place a buffer nothing feeds, held
four of from the forward on (+415 MB of compiled peak, PR 47).

Required a layer and step at the trainer's [2, 8192, 6144] bf16 under per-half
remat: forward one read and one write of the columns, twice (forward and
replay), backward two reads and one write: 7 x 201 MB = 1.41 GB, 1.7 ms at
819 GB/s; the arithmetic (about a dozen operations a row and channel) is
nothing beside it.
"""

from __future__ import annotations

import functools
import math

from .gated_delta import _HALO, _SUB, _compiler_params, _taps, causal_conv1d

# Rows (tokens) and at most lanes (channels) a grid step; a trip inside one
# works on ``_SUB`` rows of one lane tile (8 float32 vregs a value). At 1024
# lanes 512 / 1024 / 2048 rows read 0.83 / 0.78 / 0.80 ms forward and 1.29 /
# 1.25 / 1.26 backward a layer, 256 rows 1.23 / 1.56 (my chip run, PR 47;
# narrower lane blocks not measured): the vector unit binds, not the steps.
ROWS = 1024
_LANES = 128
_LANE_BLOCK = 1024


def _offsets(start, widths):
    return [start + sum(widths[:i]) for i in range(len(widths))]


def ssm_conv_route(zxbcdt, conv_w, start: int, widths) -> str:
    """Which form :func:`ssm_conv` runs, from what it can observe, as
    ``gated_delta.prologue_route`` does: "pallas" on a TPU backend at an
    eligible shape (every segment starts and ends on a lane tile, so a block
    of lanes reaches it where it lies; a convolution no wider than one 8-row
    sublane tile; bf16 or float32 activations), "interpret" at such a shape
    under ``SXT_FUSED_INTERPRET=1``, else "xla"."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    eligible = (all(n % _LANES == 0 for n in (start, *widths))
                and conv_w.shape[0] <= 8
                and zxbcdt.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def ssm_conv(zxbcdt, conv_w, conv_b, start: int, widths, rows: int = ROWS):
    """``silu(conv(xBC) + b)`` of a state-space layer, from the projection's
    output to what the scan takes: ``zxbcdt`` [B, T, W] as the projection
    wrote it, ``conv_w`` [K, C] and ``conv_b`` [C] over the C = sum(``widths``)
    channels that start at column ``start`` -> (the columns before ``start``
    as they are (z), one array [B, T, n] a segment of ``widths`` (x, B, C),
    the columns after the last segment as they are (dt)), in ``zxbcdt``'s
    dtype. float32 from the read to the one rounding at the write, in both
    bodies (module docstring)."""
    assert conv_w.shape[1] == conv_b.shape[0] == sum(widths), (
        conv_w.shape, conv_b.shape, widths)
    assert start + sum(widths) <= zxbcdt.shape[-1], (zxbcdt.shape, start, widths)
    route = ssm_conv_route(zxbcdt, conv_w, start, widths)
    if route == "xla":
        return _ssm_conv_xla(zxbcdt, conv_w, conv_b, start, widths)
    return _ssm_conv_pallas(zxbcdt, conv_w, conv_b, start, widths, rows,
                            interpret=route == "interpret")


def _ssm_conv_xla(zxbcdt, conv_w, conv_b, start, widths):
    """``ssm_conv`` as XLA ops."""
    import jax
    import jax.numpy as jnp

    end = start + sum(widths)
    y = jax.nn.silu(causal_conv1d(zxbcdt[..., start:end].astype(jnp.float32),
                                  conv_w, conv_b)).astype(zxbcdt.dtype)
    cuts = [a - start for a in _offsets(start, widths)[1:]]
    return (zxbcdt[..., :start], *jnp.split(y, cuts, axis=-1), zxbcdt[..., end:])


def _ssm_conv_pallas(zxbcdt, conv_w, conv_b, start, widths, rows=ROWS,
                     interpret: bool = False):
    """``ssm_conv`` through the kernels. The weights go in as one [16, C]
    float32 array: the K taps padded to a sublane tile, then the bias in a
    tile of its own. T is padded to whole blocks of rows with zeros (nothing
    where ``rows`` divides it); the padding and the packing are XLA's, and so
    are their gradients."""
    import jax.numpy as jnp

    T, K = zxbcdt.shape[1], conv_w.shape[0]
    assert rows % _SUB == 0, rows
    wb = jnp.concatenate([
        jnp.pad(conv_w.astype(jnp.float32), ((0, 8 - K), (0, 0))),
        jnp.pad(conv_b.astype(jnp.float32)[None], ((0, 7), (0, 0)))])
    R = min(rows, -(-T // _SUB) * _SUB)
    x = jnp.pad(zxbcdt, ((0, 0), (0, -T % R), (0, 0)))
    core = _conv_core(K, start, tuple(widths), R, interpret)
    return tuple(y[:, :T] for y in core(x, wb))


@functools.lru_cache(maxsize=None)
def _conv_core(K, start, widths, R, interpret):
    """The convolution on whole blocks of R rows as one ``jax.custom_vjp``:
    (x [B, T, W], wb [16, C] float32) -> (x's columns before ``start``, y
    [B, T, n] a segment, x's columns after the segments). The inputs are the
    only residuals; each launch under its own jit, built once (see
    ``gated_delta._delta_core``)."""
    import jax
    import jax.numpy as jnp

    end = start + sum(widths)
    segments = [(a, a - start, n) for a, n in zip(_offsets(start, widths), widths)]
    launch = lambda fn: jax.jit(functools.partial(fn, K=K, R=R, interpret=interpret),
                                static_argnames=("at", "at_w", "width"))
    forward, backward = launch(_forward), launch(_backward)

    def run(x, wb):
        ys = [forward(x, wb, at=a, at_w=aw, width=n) for a, aw, n in segments]
        return (x[..., :start], *ys, x[..., end:])

    core = jax.custom_vjp(run)

    def fwd(x, wb):
        return run(x, wb), (x, wb)

    def bwd(kept, cotangents):
        x, wb = kept
        before, *dys, after = cotangents
        parts = [backward(x, wb, dy, at=a, at_w=aw, width=n)
                 for dy, (a, aw, n) in zip(dys, segments)]
        return (jnp.concatenate([before] + [p[0] for p in parts] + [after], axis=-1),
                jnp.concatenate([p[1] for p in parts], axis=-1))

    core.defvjp(fwd, bwd)
    return core


def _lane_block(at, at_w, width):
    """Lanes a grid step: the widest whole number of lane tiles, up to
    ``_LANE_BLOCK``, that a block index reaches the segment by in both
    arrays (it divides the segment's offset in each and its width)."""
    whole = math.gcd(math.gcd(at, at_w), width)
    return max(n for n in range(_LANES, _LANE_BLOCK + 1, _LANES) if whole % n == 0)


def _blocks(R, at, at_w, width, block_at):
    """The block specs of a grid step (row b, lane block c, step n) that
    works on rows ``block_at(n) * R`` onward of a segment ``width`` wide that
    starts at column ``at`` of x and ``at_w`` of the weights: ``rows`` of x
    [B, T, W], ``halo`` the ``_HALO`` rows before them (the first block
    reads its own and masks them), ``weights`` of wb [16, C], ``own`` for an
    array [B, T, width] of the segment alone; and the lanes of a block."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    Cb = _lane_block(at, at_w, width)
    rows = pl.BlockSpec((1, R, Cb), lambda b, c, n: (b, block_at(n), at // Cb + c))
    halo = pl.BlockSpec((1, _HALO, Cb), lambda b, c, n: (
        b, jnp.maximum(block_at(n) * (R // _HALO) - 1, 0), at // Cb + c))
    weights = pl.BlockSpec((16, Cb), lambda b, c, n: (0, at_w // Cb + c))
    own = pl.BlockSpec((1, R, Cb), lambda b, c, n: (b, block_at(n), c))
    return rows, halo, weights, own, Cb


def _forward(x, wb, K, R, interpret, at, at_w, width):
    """The forward kernel's launch on one segment -> y [B, T, width]."""
    import jax
    from jax.experimental import pallas as pl

    B, Tp, _ = x.shape
    rows, halo, weights, own, Cb = _blocks(R, at, at_w, width, lambda n: n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K),
        grid=(B, width // Cb, Tp // R),
        in_specs=[rows, halo, weights], out_specs=own,
        out_shape=jax.ShapeDtypeStruct((B, Tp, width), x.dtype),
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssm_conv_fwd",
    )(x, x, wb)


def _backward(x, wb, dy, K, R, interpret, at, at_w, width):
    """The backward kernel's launch on one segment -> (dx [B, T, width],
    dwb [16, width]). The sweep runs over the row blocks from the last to
    the first; the taps' and the bias's gradients come out as [B, 8 (K + 1),
    width] partial sums (one a batch row and sublane) and are summed here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, Tp, _ = x.shape
    N = Tp // R
    rows, halo, weights, own, Cb = _blocks(R, at, at_w, width, lambda n: N - 1 - n)
    dx, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K),
        grid=(B, width // Cb, N),
        in_specs=[rows, halo, weights, own],
        out_specs=[own, pl.BlockSpec((1, 8 * (K + 1), Cb), lambda b, c, n: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, width), x.dtype),
                   jax.ShapeDtypeStruct((B, 8 * (K + 1), width), f32)],
        scratch_shapes=[pltpu.VMEM((8, Cb), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssm_conv_bwd",
    )(x, x, wb, dy)
    dwb = jnp.sum(dwb.reshape(B, K + 1, 8, width), axis=(0, 2))
    return dx, jnp.concatenate([
        jnp.pad(dwb[:K], ((0, 8 - K), (0, 0))), jnp.pad(dwb[K:], ((0, 7), (0, 0)))])


def _tile_rows(x_ref, halo_ref, at_start, lanes):
    """trip -> (float32 [8 + _SUB, 128]: the 8 rows before the trip's rows,
    then its rows, of the lane tile ``lanes``; the slice of the trip's rows).
    Before a block's first trip come the halo's last rows, zeros where the
    block is the sequence's first (``gated_delta._chunk_rows`` for a lane
    tile whose start is only known in the kernel)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    before = jnp.where(at_start, 0.0, halo_ref[0, _HALO - 8:, lanes].astype(f32))

    def rows(c):
        c0 = pl.multiple_of(c * _SUB, _SUB)
        back = pl.multiple_of(jnp.maximum(c0 - _HALO, 0), _HALO)
        prev = x_ref[0, pl.ds(back, _HALO), lanes].astype(f32)[_HALO - 8:]
        at = pl.ds(c0, _SUB)
        return jnp.concatenate([jnp.where(c == 0, before, prev),
                                x_ref[0, at, lanes].astype(f32)], axis=0), at
    return rows


def _each_lane_tile(ref, body):
    """``body(lanes)`` for every lane tile of a block, as a loop in the
    kernel (unrolled, a block of 8 tiles is 8 copies of the body for the
    host to trace and lower, several times a run)."""
    import jax
    from jax.experimental import pallas as pl

    def tile(i, carry):
        body(pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES))
        return carry

    jax.lax.fori_loop(0, ref.shape[-1] // _LANES, tile, 0)


def _fwd_kernel(x_ref, halo_ref, wb_ref, y_ref, *, K):
    """R rows of one lane block of one batch row: per lane tile the taps
    over the K rows that end at a row, the bias and SiLU, float32 until the
    write."""
    import jax
    from jax.experimental import pallas as pl

    at_start = pl.program_id(2) == 0

    def tile(lanes):
        w = [wb_ref[j:j + 1, lanes] for j in range(K)]
        bias = wb_ref[8:9, lanes]
        rows = _tile_rows(x_ref, halo_ref, at_start, lanes)

        def trip(c, carry):
            ext, at = rows(c)
            pre = sum(wj * xj for wj, xj in zip(w, _taps(ext, K))) + bias
            y_ref[0, at, lanes] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, x_ref.shape[1] // _SUB, trip, 0)

    _each_lane_tile(x_ref, tile)


def _bwd_kernel(x_ref, halo_ref, wb_ref, dy_ref, dx_ref, dwb_ref, ahead, *, K):
    """The same block's gradients; the grid's last axis walks the row blocks
    from the last to the first, and so do the trips inside a block. The
    pre-activation is computed again in float32. The convolution's transpose
    needs the pre-activation's cotangent of the K - 1 rows AFTER a row:
    ``ahead`` [8, lanes] carries the first rows' of the block after this one
    (zeros at the end), and each trip hands its own first 8 rows' to the
    trip before it. The taps' and the bias's gradients sum over all rows: 8
    partial sums (one a sublane) each, carried through the trips in
    registers and accumulated in the output block over the walk."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    trips = x_ref.shape[1] // _SUB
    at_start = pl.program_id(2) == pl.num_programs(2) - 1
    by_sublane = lambda p: sum(p[s:s + 8] for s in range(0, _SUB, 8))

    @pl.when(pl.program_id(2) == 0)
    def _():
        ahead[...] = jnp.zeros_like(ahead)
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    def tile(lanes):
        w = [wb_ref[j:j + 1, lanes] for j in range(K)]
        bias = wb_ref[8:9, lanes]
        rows = _tile_rows(x_ref, halo_ref, at_start, lanes)

        def trip(t, carry):
            after, sums = carry
            ext, at = rows(trips - 1 - t)
            taps = _taps(ext, K)
            pre = sum(wj * xj for wj, xj in zip(w, taps)) + bias
            sig = jax.nn.sigmoid(pre)
            dpre = dy_ref[0, at, lanes].astype(jnp.float32) * (
                sig * (1.0 + pre * (1.0 - sig)))
            sums = tuple(s + by_sublane(p) for s, p in zip(
                sums, [dpre * xj for xj in taps] + [dpre]))
            # dx[t] = sum_j w[j] dpre[t + K - 1 - j]
            ext = jnp.concatenate([dpre, after], axis=0)
            dx = sum(wj * (dpre if u == 0 else pltpu.roll(ext, _SUB + 8 - u, 0)[:_SUB])
                     for wj, u in zip(w, range(K - 1, -1, -1)))
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
            return dpre[:8], sums

        zero = jnp.zeros((8, _LANES), jnp.float32)
        after, sums = jax.lax.fori_loop(
            0, trips, trip, (ahead[:, lanes], (zero,) * (K + 1)))
        ahead[:, lanes] = after
        for j, s in enumerate(sums):
            dwb_ref[0, 8 * j:8 * j + 8, lanes] += s

    _each_lane_tile(x_ref, tile)
