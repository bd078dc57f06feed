"""The Mamba-2 mixer's epilogue (mixer "ssm" of ``models/transformer.py``):
what lies between the mixer's scan and its output projection.

With the scan's output o (``ops/ssd.py``, without its skip), the
convolution's x (``ops/ssm_conv.py``) and the projection's z, all [B, T,
inner] in the compute dtype, the skip ``D`` [H] (a number a head of inner / H
channels) and the norm's gain [inner], both float32::

    u = o + D[h] x        y = u * silu(z)
    out = y * rsqrt(mean over each group's inner / G channels of y^2 + eps) * gain

The gate FIRST, then an RMSNorm over each of G groups of channels under one
gain. float32 from the reads to the one rounding at the write, in both
bodies: the skip's sum is not rounded on its way to the gate.

:func:`ssm_gate_norm` is that, with two bodies of the same arithmetic chosen
by what the code can observe (:func:`ssm_gate_norm_route`: backend and shape,
as ``ops/dispatch.py`` states it):

  Pallas kernels  on a TPU backend where a group is 1 to 8 whole lane tiles
                  (128) and the activations bf16 or float32:
                  ``ssm_gate_norm_fwd`` / ``ssm_gate_norm_bwd`` behind one
                  ``jax.custom_vjp``. The CPU suite drives the same kernels
                  through the interpreter (``SXT_FUSED_INTERPRET=1``).
                  Selected, they run or raise.
  XLA ops         everywhere else: the lines above with a group's mean square
                  and its way back to the channels as products with a 0/1
                  [inner, G] indicator, so that nothing is viewed as [.., G,
                  inner / G] (on a TPU that view of wide activations is a
                  relayout). The off-TPU path and the kernels' oracle.

What the kernels read and write. A grid step is a block of rows (tokens of
one sequence; the rows are independent) by a block of lanes that holds whole
groups. The forward reads o, x, z, forms y a lane tile at a time in
float32, adds the group's tiles' squares and takes ONE lane reduce a row
for the statistic, and writes out once. The backward reads the same three and
the output's cotangent, computes y and the statistic again (the inputs are
the only residuals: nothing float32 is kept) and with ``w = d out * gain``
writes::

    d y = r (w - y r^2 mean(y w))     d o = d u = d y silu(z)
    d z = d y u silu'(z)              r = rsqrt(mean(y^2) + eps)

The gain's and the skip's gradients leave as float32 partial sums a row
block and sublane, ``d out y r`` and ``d u x`` a CHANNEL, which XLA adds (the
skip's over a head's channels too: D goes in as a number a channel, so a head
may be any width). x's cotangent, ``D d u``, is left to XLA: x also feeds the
scan, so an add of two cotangents reads d u anyway, and a third output of the
kernel would be 134 MB a layer more to write and to read. z's values may be
read where they lie in the projection's output (``z_in``).

Required a layer and step at the trainer's [2, 8192, 4096] bf16 under
per-half remat: forward three reads and one write, twice (forward and
replay), backward four reads and two writes: 14 x 134 MB = 1.88 GB, 2.3 ms at
819 GB/s.
"""

from __future__ import annotations

import functools

# Rows (tokens) and at most lanes (channels) a grid step; a trip inside one
# works on ``_SUB`` rows of one group (8 float32 vregs a value and lane tile).
# The vector unit binds, not the steps: at [2, 8192, 4096] bf16 row blocks of
# 512 / 1024 / 2048 at 1024 lanes read 0.83 / 0.80 / 0.80 ms forward and 1.28 /
# 1.26 / 1.25 backward a layer, lane blocks of 512 / 2048 / 4096 the same to
# 0.02; trips of 16 / 32 / 64 rows 1.23 / 0.80 / 0.79 and 1.40 / 1.26 / 1.20 (my
# chip runs, PR 48).
ROWS = 1024
_SUB = 64
_LANES = 128
_LANE_BLOCK = 1024
_MAX_TILES = 8


def ssm_gate_norm_route(o, groups: int) -> str:
    """Which form :func:`ssm_gate_norm` runs, from what it can observe, as
    ``ssm_conv.ssm_conv_route`` does: "pallas" on a TPU backend at an eligible
    shape (a group 1 to ``_MAX_TILES`` whole lane tiles, which a kernel holds
    in registers at once; bf16 or float32 activations), "interpret" at such a
    shape under ``SXT_FUSED_INTERPRET=1``, else "xla"."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    inner = o.shape[-1]
    eligible = (inner % groups == 0 and (inner // groups) % _LANES == 0
                and inner // groups <= _MAX_TILES * _LANES
                and o.dtype in (jnp.bfloat16, jnp.float32))
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def ssm_gate_norm(o, x, z, D, gain, groups: int, eps: float, rows: int = ROWS,
                  z_in=None):
    """``rmsnorm_grouped((o + D x) * silu(z)) * gain`` of a state-space layer:
    o, x, z [B, T, inner] in one dtype, ``D`` [H] (H divides inner) and
    ``gain`` [inner] -> [B, T, inner] in that dtype (module docstring).
    ``z_in``: the array z was cut from, [B, T, >= inner] with z its FIRST
    inner columns (the mixer's: the projection's output). The kernels then
    read z's values there, where they lie, and XLA need not write the slice
    out for them (a pass over 134 MB a layer, three times a step, at the
    trainer's shape); the gradient is z's all the same. The XLA body reads
    z."""
    inner = o.shape[-1]
    assert o.ndim == 3 and o.shape == x.shape == z.shape and o.dtype == x.dtype == z.dtype, (
        o.shape, x.shape, z.shape, o.dtype, x.dtype, z.dtype)
    assert gain.shape == (inner,) and D.ndim == 1 and inner % D.shape[0] == 0, (
        gain.shape, D.shape, inner)
    assert z_in is None or (z_in.shape[:2] == z.shape[:2] and z_in.shape[2] >= inner
                            and z_in.dtype == z.dtype), (z_in.shape, z_in.dtype, z.shape)
    route = ssm_gate_norm_route(o, groups)
    if route == "xla":
        return _ssm_gate_norm_xla(o, x, z, D, gain, groups, eps)
    return _ssm_gate_norm_pallas(o, x, z, D, gain, groups, eps, rows,
                                 interpret=route == "interpret", z_in=z_in)


def _by_channel(D, inner):
    """The skip a channel: [H] -> [inner] float32."""
    import jax.numpy as jnp

    return jnp.repeat(D.astype(jnp.float32), inner // D.shape[0])


def _ssm_gate_norm_xla(o, x, z, D, gain, groups, eps):
    """``ssm_gate_norm`` as XLA ops. The groups' sums and their way back to
    the channels are products with the groups' indicator at float32 accuracy
    (``Precision.HIGHEST``: a TPU's default would round y^2 to bf16)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    inner = o.shape[-1]
    n = inner // groups
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    member = (jnp.arange(inner)[:, None] // n == jnp.arange(groups)[None, :]).astype(f32)
    y = (o.astype(f32) + _by_channel(D, inner) * x.astype(f32)) * jax.nn.silu(z.astype(f32))
    r = jax.lax.rsqrt(dot(y * y, member) / n + eps)                       # [..., G]
    return (y * dot(r, member.T) * gain.astype(f32)).astype(o.dtype)


def _ssm_gate_norm_pallas(o, x, z, D, gain, groups, eps, rows=ROWS,
                          interpret: bool = False, z_in=None):
    """``ssm_gate_norm`` through the kernels. T is padded to whole blocks of
    rows with zeros (nothing where ``rows`` divides it: a row of zeros gives
    zeros and adds nothing to a sum; a ragged T reads z itself, not ``z_in``,
    rather than pad all of that). The weights go in as one [8, inner] float32
    array: the gain, then the skip a channel; the packing and the padding are
    XLA's, and so are their gradients."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T, inner = o.shape[1:]
    assert rows % _SUB == 0, rows
    wb = jnp.pad(jnp.stack([gain.astype(f32), _by_channel(D, inner)]), ((0, 6), (0, 0)))
    R = min(rows, -(-T // _SUB) * _SUB)
    o, x, z = (jnp.pad(a, ((0, 0), (0, -T % R), (0, 0))) for a in (o, x, z))
    read = z if z_in is None or T % R else jax.lax.stop_gradient(z_in)
    core = _gate_norm_core(inner // groups // _LANES, float(eps), R, interpret)
    return core(o, x, z, read, wb)[:, :T]


@functools.lru_cache(maxsize=None)
def _gate_norm_core(tiles, eps, R, interpret):
    """The epilogue on whole blocks of R rows as one ``jax.custom_vjp``: (o,
    x, z [B, T, inner], ``read`` [B, T, >= inner], wb [8, inner] float32) ->
    out [B, T, inner] (the arrays in the shape their neighbours have them:
    through a reshape XLA does not fuse ``D d u`` into the add of x's two
    cotangents). z's VALUES are read from the first inner columns of ``read``
    (z itself, or the array it was cut from), z is there to take the
    gradient: nothing reads it, so XLA drops a slice that makes it. The
    inputs are the only residuals; each launch under its own jit, built once
    (see ``ssd._ssd_core``)."""
    import jax
    import jax.numpy as jnp

    launch = lambda fn: jax.jit(functools.partial(
        fn, tiles=tiles, eps=eps, R=R, interpret=interpret))
    forward, backward = launch(_forward), launch(_backward)

    @jax.custom_vjp
    def core(o, x, z, read, wb):
        return forward(o, x, read, wb)

    def fwd(o, x, z, read, wb):
        return forward(o, x, read, wb), (o, x, read, wb)

    def bwd(kept, dout):
        o, x, read, wb = kept
        do, dz, dwb = backward(o, x, read, wb, dout)
        # x's: the skip's share of d u, formed where XLA adds it to the scan's
        dx = (wb[1] * do.astype(jnp.float32)).astype(x.dtype)
        return do, dx, dz, None, dwb

    core.defvjp(fwd, bwd, optimize_remat=True)
    return core


def _lane_block(tiles, inner):
    """Lanes a grid step: the most whole groups, up to ``_LANE_BLOCK`` lanes
    (one group where it is wider), that divide the channels."""
    n = tiles * _LANES
    groups = inner // n
    return n * max(k for k in range(1, groups + 1)
                   if groups % k == 0 and (k == 1 or k * n <= _LANE_BLOCK))


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel",) * 3,
                                vmem_limit_bytes=64 * 1024 * 1024)


def _blocks(shape, tiles, R):
    """The block specs of a grid step (row b, row block n, lane block c):
    ``rows`` of an array [B, T, inner], ``weights`` of wb [8, inner], ``sums``
    of the partial sums [B, T / R, 16, inner]; and the grid."""
    from jax.experimental import pallas as pl

    B, T, inner = shape
    Cb = _lane_block(tiles, inner)
    return (pl.BlockSpec((1, R, Cb), lambda b, n, c: (b, n, c)),
            pl.BlockSpec((8, Cb), lambda b, n, c: (0, c)),
            pl.BlockSpec((1, 1, 16, Cb), lambda b, n, c: (b, n, 0, c)),
            (B, T // R, inner // Cb))


def _forward(o, x, z, wb, tiles, eps, R, interpret):
    """The forward kernel's launch -> out [B, T, inner]."""
    import jax
    from jax.experimental import pallas as pl

    rows, weights, _, grid = _blocks(o.shape, tiles, R)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, eps=eps), grid=grid,
        in_specs=[rows, rows, rows, weights],
        out_specs=rows, out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssm_gate_norm_fwd",
    )(o, x, z, wb)


def _backward(o, x, z, wb, dout, tiles, eps, R, interpret):
    """The backward kernel's launch -> (d o, d z [B, T, inner], dwb [8,
    inner]: the gain's gradient, then the skip's a channel). They come out of
    the kernel as [B, T / R, 16, inner] partial sums (one a row block and
    sublane) and are summed here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, inner = o.shape
    rows, weights, sums, grid = _blocks(o.shape, tiles, R)
    like = jax.ShapeDtypeStruct(o.shape, o.dtype)
    do, dz, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles, eps=eps), grid=grid,
        in_specs=[rows, rows, rows, weights, rows],
        out_specs=[rows, rows, sums],
        out_shape=[like, like, jax.ShapeDtypeStruct((B, T // R, 16, inner), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssm_gate_norm_bwd",
    )(o, x, z, wb, dout)
    dwb = jnp.sum(dwb.reshape(B * (T // R), 2, 8, inner), axis=(0, 2))
    return do, dz, jnp.pad(dwb, ((0, 6), (0, 0)))


def _each_group(ref, tiles, body):
    """``body(the group's lane tiles)`` for every group of a block, as a loop
    in the kernel (``ssm_conv._each_lane_tile``: unrolled, the groups of a
    block are so many copies of the body for the host to trace and lower)."""
    import jax
    from jax.experimental import pallas as pl

    n = tiles * _LANES

    def group(i, carry):
        body([pl.ds(pl.multiple_of(i * n + j * _LANES, _LANES), _LANES)
              for j in range(tiles)])
        return carry

    jax.lax.fori_loop(0, ref.shape[-1] // n, group, 0)


def _gated(o_ref, x_ref, z_ref, at, lanes, skip):
    """(u, z, sigmoid(z)) of ``_SUB`` rows of one lane tile, float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    z = z_ref[0, at, lanes].astype(f32)
    u = o_ref[0, at, lanes].astype(f32) + skip * x_ref[0, at, lanes].astype(f32)
    return u, z, jax.nn.sigmoid(z)


def _fwd_kernel(o_ref, x_ref, z_ref, wb_ref, out_ref, *, tiles, eps):
    """R rows of one lane block: per group and trip of ``_SUB`` rows the
    gated values of its lane tiles, the tiles' squares added and reduced over
    the lanes once, float32 until the write."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    scale = 1.0 / (tiles * _LANES)

    def group(lanes):
        gain = [wb_ref[0:1, at] for at in lanes]
        skip = [wb_ref[1:2, at] for at in lanes]

        def trip(t, carry):
            at = pl.ds(pl.multiple_of(t * _SUB, _SUB), _SUB)
            ys = []
            for lane, d in zip(lanes, skip):
                u, z, s = _gated(o_ref, x_ref, z_ref, at, lane, d)
                ys.append(u * (z * s))
            r = jax.lax.rsqrt(scale * jnp.sum(sum(y * y for y in ys), axis=-1,
                                              keepdims=True) + eps)          # [_SUB, 1]
            for lane, y, g in zip(lanes, ys, gain):
                out_ref[0, at, lane] = (y * r * g).astype(out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, o_ref.shape[1] // _SUB, trip, 0)

    _each_group(o_ref, tiles, group)


def _bwd_kernel(o_ref, x_ref, z_ref, wb_ref, dout_ref, do_ref, dz_ref, dwb_ref, *,
                tiles, eps):
    """The same block's gradients (module docstring). The gated values and
    the statistic are computed again in float32. The gain's and the skip's
    gradients sum over all rows: 8 partial sums (one a sublane) a lane tile
    each, carried through the trips in registers and written once a block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    scale = 1.0 / (tiles * _LANES)
    lanesum = lambda parts: jnp.sum(sum(parts), axis=-1, keepdims=True)      # [_SUB, 1]
    by_sublane = lambda p: sum(p[s:s + 8] for s in range(0, _SUB, 8))

    def group(lanes):
        gain = [wb_ref[0:1, at] for at in lanes]
        skip = [wb_ref[1:2, at] for at in lanes]

        def trip(t, sums):
            at = pl.ds(pl.multiple_of(t * _SUB, _SUB), _SUB)
            # what the second pass over the tiles needs of the first, and no
            # more (a value here is _SUB / 8 vregs a tile, of 64 in all): y,
            # the cotangent, silu(z) and u silu'(z)
            kept = []
            for lane, skip_, g in zip(lanes, skip, gain):
                u, z, s = _gated(o_ref, x_ref, z_ref, at, lane, skip_)
                silu = z * s
                kept.append((u * silu, dout_ref[0, at, lane].astype(f32), silu,
                             u * (s * (1.0 + z * (1.0 - s)))))
            r = jax.lax.rsqrt(scale * lanesum([y * y for y, _, _, _ in kept]) + eps)
            pull = r * r * scale * lanesum([y * (d * g) for (y, d, _, _), g in zip(kept, gain)])
            out = []
            for lane, (y, d, silu, slope), g, (sg, sd) in zip(lanes, kept, gain, sums):
                dy = r * (d * g - y * pull)
                du = dy * silu
                do_ref[0, at, lane] = du.astype(do_ref.dtype)
                dz_ref[0, at, lane] = (dy * slope).astype(dz_ref.dtype)
                out.append((sg + by_sublane(d * y * r),
                            sd + by_sublane(du * x_ref[0, at, lane].astype(f32))))
            return tuple(out)

        zero = jnp.zeros((8, _LANES), f32)
        sums = jax.lax.fori_loop(0, o_ref.shape[1] // _SUB, trip, ((zero, zero),) * tiles)
        for lane, (sg, sd) in zip(lanes, sums):
            dwb_ref[0, 0, 0:8, lane] = sg
            dwb_ref[0, 0, 8:16, lane] = sd

    _each_group(o_ref, tiles, group)
