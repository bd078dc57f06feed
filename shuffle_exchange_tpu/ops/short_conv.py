"""The gated short convolution (LFM2's ``conv`` layers; mixer "sconv" of
``models/transformer.py``): what lies between the mixer's two projections.

The layer is ``[B, C, x] = split3(y W_in)``, ``u = B * x``, a causal depthwise
convolution of ``K`` taps over u (no bias, no activation), ``C *`` its result,
then ``W_out``: no heads, no softmax, no state but the last ``K - 1`` rows of
u. :func:`sconv_mix` is the pass between the projections, one equation with
two bodies of the same arithmetic chosen by what the code can observe
(:func:`sconv_route`: backend and shape, as ``ops/dispatch.py`` states it):

  Pallas kernels  on a TPU backend where C is whole lane tiles (128), the
                  rows of a sequence divide into blocks (T a multiple of 64
                  and a block of ``bcx``, ``dy`` and d ``bcx`` fits the
                  kernels' VMEM), K <= 8 and the activations are bf16 or
                  float32: ``sconv_mix_fwd`` / ``sconv_mix_bwd`` behind one
                  ``jax.custom_vjp`` whose residuals are ``bcx`` and the taps.
                  The CPU suite drives the same kernels through the
                  interpreter (``SXT_FUSED_INTERPRET=1``). Selected, they run
                  or raise.
  XLA ops         everywhere else: the gate before, the taps
                  (``gated_delta.causal_conv1d``: the oracle for them), the
                  gate after. The off-TPU path and the kernels' oracle.

Both bodies take products and the taps' sum in float32 and round ONCE, at
each write, forward and backward.

What the kernels read and write. A grid step is one block of R rows of one
sequence, all 3C channels; a loop inside walks the C / 128 lane tiles and,
per tile, trips of ``_TRIP`` rows whose values stay in registers. The forward
reads the three lane tiles of ``bcx`` at channel offsets 0, C and 2C WHERE THE
PROJECTION WROTE THEM (no slice or copy before the launch) and the 16 rows
before the block (one bf16 tile; the taps look back K - 1 <= 7 of them; zeros
before position 0, and a step never reads another sequence's rows), forms
``u = B * x``, the taps' sum and ``C *`` and writes [R, C] once: one read of
[rows, 3C] and one write of [rows, C], 537 MB = 0.66 ms a layer at LFM2's
[8, 4096, 6144] bf16 and 819 GB/s. The backward reads ``bcx`` and ``dy`` once,
forms u and the taps' sum again (nothing float32 is kept or written), takes
``dC = dy * conv``, ``dc = dy * C``, ``du[t] = sum_j w[j] dc[t + K - 1 - j]``
(the taps' transpose reaches K - 1 rows FORWARD: a step also reads the 16 rows
of ``dy`` and C after its block, zeros after a sequence's last), ``dB = du *
x``, ``dx = du * B`` and writes d[B | C | x] as one [R, 3C] block at the same
three offsets, and the taps' gradient ``dw[j] = sum_t dc[t] u[t - (K - 1) +
j]`` as float32 partial sums (one a sequence and sublane, accumulated over a
sequence's blocks, summed by XLA): read [rows, 3C] + [rows, C], write
[rows, 3C]: 940 MB = 1.15 ms a layer. XLA's body of the same pass wrote u as
float32, held three such float32 arrays a layer in the backward and hid the
taps' gradient in the output projection's backward matmul (``PERF.md``
section 6, PR 54).
"""

from __future__ import annotations

import functools

from .gated_delta import _HALO, _taps

# Rows (tokens) a grid step at most, and rows a trip of the loop inside one
# works on, a lane tile at a time. ``_BLOCK_BYTES``: what one grid step's
# blocks of the backward (bcx, dy, d bcx: 7C channels a row) may hold; the
# pipeline keeps two of each.
ROWS = 512
_TRIP = 64
_LANES = 128
_BLOCK_BYTES = 20 * 1024 * 1024


def _row_block(T: int, C: int, itemsize: int) -> int:
    """Rows a grid step: the most, in whole trips, up to ``ROWS``, that
    divide a sequence's T rows and whose blocks fit; 0 where there are
    none."""
    most = min(ROWS, T, _BLOCK_BYTES // (7 * C * itemsize))
    return max((r for r in range(_TRIP, most + 1, _TRIP) if T % r == 0), default=0)


def sconv_route(bcx, w) -> str:
    """Which form :func:`sconv_mix` runs for these operands, from what it can
    observe, as ``gated_delta.prologue_route`` does: "pallas" on a TPU
    backend at an eligible shape (``bcx`` [B, T, 3C] with C whole lane tiles,
    T in whole row blocks that fit, no more taps than one 8-row sublane tile,
    bf16 or float32 activations), "interpret" at such a shape under
    ``SXT_FUSED_INTERPRET=1``, else "xla"."""
    import jax.numpy as jnp

    from .dispatch import interpret_forced, pallas_enabled

    K, C = w.shape
    eligible = (bcx.ndim == 3 and bcx.shape[-1] == 3 * C and C % _LANES == 0
                and K <= 8 and bcx.dtype in (jnp.bfloat16, jnp.float32)
                and _row_block(bcx.shape[1], C, bcx.dtype.itemsize) > 0)
    if not eligible:
        return "xla"
    if interpret_forced():
        return "interpret"
    return "pallas" if pallas_enabled() else "xla"


def sconv_mix(bcx, w):
    """``bcx`` [B, T, 3C] as the input projection wrote it (three blocks of
    C channels: the gate before B, the gate after C, the signal x, in this
    order) and the taps ``w`` [K, C] -> [B, T, C] in ``bcx``'s dtype:
    ``C[t] * sum_j w[j] * (B * x)[t - (K - 1) + j]``, (B * x) zero before
    position 0 of each sequence. Products and the taps' sum are float32; the
    result is rounded once (and so is each gradient), in both bodies (module
    docstring)."""
    C = w.shape[-1]
    if bcx.shape[-1] != 3 * C:
        raise ValueError(f"sconv_mix: {bcx.shape[-1]} channels for taps over {C}: "
                         "the projection writes three blocks of the taps' width")
    route = sconv_route(bcx, w)
    if route == "xla":
        return _sconv_mix_xla(bcx, w)
    return _sconv_mix_pallas(bcx, w, interpret=route == "interpret")


def _sconv_mix_xla(bcx, w):
    """``sconv_mix`` as XLA ops."""
    import jax.numpy as jnp

    from .gated_delta import causal_conv1d

    C = w.shape[-1]
    f32 = jnp.float32
    gate_in, gate_out, x = (bcx[..., i * C:(i + 1) * C].astype(f32) for i in range(3))
    # float32 in, float32 out: ``causal_conv1d`` returns its input's dtype
    return (gate_out * causal_conv1d(gate_in * x, w)).astype(bcx.dtype)


def _sconv_mix_pallas(bcx, w, interpret: bool = False):
    """``sconv_mix`` through the kernels. The taps go in as one [8, C]
    float32 array, padded to a sublane tile; the padding and the cast are
    XLA's, and so are their gradients."""
    import jax.numpy as jnp

    K, C = w.shape
    w8 = jnp.pad(w.astype(jnp.float32), ((0, 8 - K), (0, 0)))
    R = _row_block(bcx.shape[1], C, bcx.dtype.itemsize)
    return _mix_core(K, R, interpret)(bcx, w8)


@functools.lru_cache(maxsize=None)
def _mix_core(K, R, interpret):
    """The pass on whole blocks of R rows as one ``jax.custom_vjp``: (bcx
    [B, T, 3C], w8 [8, C] float32) -> [B, T, C]. The inputs are the only
    residuals; each launch under its own jit, built once (see
    ``gated_delta._delta_core``)."""
    import jax

    forward, backward = (jax.jit(functools.partial(fn, K=K, R=R, interpret=interpret))
                         for fn in (_forward, _backward))
    core = jax.custom_vjp(forward)
    core.defvjp(lambda bcx, w8: (forward(bcx, w8), (bcx, w8)),
                lambda kept, dy: backward(*kept, dy))
    return core


def _compiler_params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=64 * 1024 * 1024)


def _blocks(T, C, R):
    """The block specs of a grid step (sequence b, row block n) over ``bcx``
    [B, T, 3C]: its R ``rows`` of all channels, ``behind`` the ``_HALO`` rows
    before them (the first block reads its own and masks them), ``weights``
    of w8 [8, C], ``own`` R rows of an array [B, T, C], and ``ahead(lane
    block)`` the ``_HALO`` rows after a block's of C channels (the last block
    reads its own and masks them)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    per, last = R // _HALO, T // _HALO - 1
    rows = pl.BlockSpec((1, R, 3 * C), lambda b, n: (b, n, 0))
    behind = pl.BlockSpec((1, _HALO, 3 * C), lambda b, n: (b, jnp.maximum(n * per - 1, 0), 0))
    weights = pl.BlockSpec((8, C), lambda b, n: (0, 0))
    own = pl.BlockSpec((1, R, C), lambda b, n: (b, n, 0))
    ahead = lambda at: pl.BlockSpec(
        (1, _HALO, C), lambda b, n: (b, jnp.minimum((n + 1) * per, last), at))
    return rows, behind, weights, own, ahead


def _forward(bcx, w8, K, R, interpret):
    """The forward kernel's launch -> y [B, T, C]."""
    import jax
    from jax.experimental import pallas as pl

    B, T, C = bcx.shape[0], bcx.shape[1], w8.shape[1]
    rows, behind, weights, own, _ = _blocks(T, C, R)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K),
        grid=(B, T // R), in_specs=[rows, behind, weights], out_specs=own,
        out_shape=jax.ShapeDtypeStruct((B, T, C), bcx.dtype),
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret, name="sconv_mix_fwd",
    )(bcx, bcx, w8)


def _backward(bcx, w8, dy, K, R, interpret):
    """The backward kernel's launch -> (d bcx [B, T, 3C], d w8 [8, C]). The
    taps' gradient comes out as [B, 8 K, C] partial sums (one a sequence and
    sublane) and is summed here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, C = bcx.shape[0], bcx.shape[1], w8.shape[1]
    rows, behind, weights, own, ahead = _blocks(T, C, R)
    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K),
        grid=(B, T // R),
        # the rows after a block: of dy, and of the gate after (``bcx``'s
        # second block of C channels)
        in_specs=[rows, behind, ahead(1), weights, own, ahead(0)],
        out_specs=[rows, pl.BlockSpec((1, 8 * K, C), lambda b, n: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((B, 8 * K, C), jnp.float32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret, name="sconv_mix_bwd",
    )(bcx, bcx, bcx, w8, dy, dy)
    dw = jnp.sum(dw.reshape(B, K, 8, C), axis=(0, 2))
    return dbcx, jnp.pad(dw, ((0, 8 - K), (0, 0)))


def _each_lane_tile(C, body):
    """``body(B's, C's, x's lanes of bcx; the tile's lanes of an array of C
    channels)`` for every lane tile, as a loop in the kernel (unrolled, 16
    tiles are 16 copies of the body for the host to trace and lower, several
    times a run: ``ssm_conv._each_lane_tile``)."""
    import jax
    from jax.experimental import pallas as pl

    lanes = lambda start: pl.ds(pl.multiple_of(start, _LANES), _LANES)

    def tile(i, carry):
        body(*(lanes(j * C + i * _LANES) for j in range(3)), lanes(i * _LANES))
        return carry

    jax.lax.fori_loop(0, C // _LANES, tile, 0)


def _gated(ref, rows, gate, x):
    """float32 ``u = B * x`` of ``rows`` of a block of ``bcx``."""
    import jax.numpy as jnp

    f32 = jnp.float32
    return ref[0, rows, gate].astype(f32) * ref[0, rows, x].astype(f32)


def _gated_before(behind_ref, at_start, gate, x):
    """float32 [8, 128]: u of the 8 rows before a block, zeros where the
    block is the sequence's first."""
    import jax.numpy as jnp

    return jnp.where(at_start, 0.0, _gated(behind_ref, slice(_HALO - 8, _HALO), gate, x))


def _fwd_kernel(bcx_ref, behind_ref, w_ref, y_ref, *, K):
    """R rows of one sequence: per lane tile and trip, u, the taps over the
    K rows that end at a row (a trip hands the next its last 8 rows of u;
    the block's first takes the halo's, zeros at a sequence's start) and the
    gate after, float32 until the write."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    at_start = pl.program_id(1) == 0

    def tile(gate_in, gate_out, x, own):
        w = [w_ref[j:j + 1, own] for j in range(K)]

        def trip(c, tail):
            at = pl.ds(pl.multiple_of(c * _TRIP, _TRIP), _TRIP)
            u = _gated(bcx_ref, at, gate_in, x)
            conv = sum(wj * uj for wj, uj in zip(w, _taps(jnp.concatenate([tail, u], axis=0), K)))
            y_ref[0, at, own] = (bcx_ref[0, at, gate_out].astype(f32) * conv).astype(y_ref.dtype)
            return u[_TRIP - 8:]

        jax.lax.fori_loop(0, y_ref.shape[1] // _TRIP, trip,
                          _gated_before(behind_ref, at_start, gate_in, x))

    _each_lane_tile(y_ref.shape[-1], tile)


def _bwd_kernel(bcx_ref, behind_ref, gate_ahead_ref, w_ref, dy_ref, dy_ahead_ref,
                dbcx_ref, dw_ref, *, K):
    """The same block's gradients. u and the taps' sum are computed again in
    float32. The taps' transpose needs ``dc = dy * C`` of the K - 1 rows
    AFTER a row: a trip reads the next trip's first rows, the block's last
    the halo ahead (zeros at a sequence's end). The taps' gradient sums over
    all rows: 8 partial sums (one a sublane) a tap, carried through the trips
    in registers and accumulated in the output block over a sequence's
    blocks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    R = dy_ref.shape[1]
    trips = R // _TRIP
    at_start = pl.program_id(1) == 0
    at_end = pl.program_id(1) == pl.num_programs(1) - 1
    by_sublane = lambda p: sum(p[s:s + 8] for s in range(0, _TRIP, 8))

    @pl.when(at_start)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def tile(gate_in, gate_out, x, own):
        w = [w_ref[j:j + 1, own] for j in range(K)]
        beyond = jnp.where(at_end, 0.0, dy_ahead_ref[0, :8, own].astype(f32)
                           * gate_ahead_ref[0, :8, own].astype(f32))

        def trip(c, carry):
            tail, sums = carry
            at = pl.ds(pl.multiple_of(c * _TRIP, _TRIP), _TRIP)
            gate, sig = bcx_ref[0, at, gate_in].astype(f32), bcx_ref[0, at, x].astype(f32)
            dy = dy_ref[0, at, own].astype(f32)
            u = gate * sig
            taps = _taps(jnp.concatenate([tail, u], axis=0), K)
            conv = sum(wj * uj for wj, uj in zip(w, taps))
            dbcx_ref[0, at, gate_out] = (dy * conv).astype(dbcx_ref.dtype)
            dc = dy * bcx_ref[0, at, gate_out].astype(f32)
            sums = tuple(s + by_sublane(dc * uj) for s, uj in zip(sums, taps))
            # du[t] = sum_j w[j] dc[t + K - 1 - j]
            nxt = pl.ds(pl.multiple_of(jnp.minimum((c + 1) * _TRIP, R - _HALO), _HALO), _HALO)
            after = (dy_ref[0, nxt, own].astype(f32)
                     * bcx_ref[0, nxt, gate_out].astype(f32))[:8]
            ext = jnp.concatenate([dc, jnp.where(c == trips - 1, beyond, after)], axis=0)
            du = sum(wj * (dc if s == 0 else pltpu.roll(ext, _TRIP + 8 - s, 0)[:_TRIP])
                     for wj, s in zip(w, range(K - 1, -1, -1)))
            dbcx_ref[0, at, gate_in] = (du * sig).astype(dbcx_ref.dtype)
            dbcx_ref[0, at, x] = (du * gate).astype(dbcx_ref.dtype)
            return u[_TRIP - 8:], sums

        zero = jnp.zeros((8, _LANES), f32)
        _, sums = jax.lax.fori_loop(
            0, trips, trip, (_gated_before(behind_ref, at_start, gate_in, x), (zero,) * K))
        for j, s in enumerate(sums):
            dw_ref[0, 8 * j:8 * j + 8, own] += s

    _each_lane_tile(dy_ref.shape[-1], tile)
