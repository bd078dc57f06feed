"""The experts' activation pass (``moe/layer.expert_mlp_ragged``): what lies
between the grouped GEMMs of a routed layer.

With the up projection's ``up`` [R, F] and, for a gated unit, the gate
projection's ``gate`` [R, F] (the rows of the expert-sorted buffer, of which
the first ``fit`` hold a token-choice)::

    h = act(gate) * up      ("swiglu": SiLU, "reglu": ReLU)
    h = act(up)             (ungated: "relu", "relu2", "silu")

:func:`expert_act` is that, with two bodies of the same arithmetic chosen by
what the code can observe (:func:`expert_act_route`: the grouped GEMM's own
route, the rows' dtype and count):

  Pallas kernels  where ``grouped_matmul`` takes megablox for the projection
                  that made ``up`` (a TPU backend, whole lane tiles), the rows
                  are 2-byte floats, the activation is one of ``ACTIVATIONS``,
                  there is at least one whole block of ``ROWS`` rows and no
                  kernel mesh over several devices: ``sxt_expert_act_fwd`` /
                  ``sxt_expert_act_bwd`` behind one ``jax.custom_vjp`` whose
                  residuals are ``gate``, ``up`` and ``fit``. The CPU suite
                  drives the same kernels through the interpreter
                  (``SXT_FUSED_INTERPRET=1``). Selected, they run or raise.
  XLA ops         everywhere else (the CPU, float32, the GELUs, decode-sized
                  batches, a kernel mesh of several devices): the lines above
                  in the arrays' dtype, differentiated by autodiff, over all R
                  rows. The text the layer had, and the kernels' oracle.

What the kernels read and write. A grid step is one block of ``ROWS`` rows,
all F channels (F whole, so any width is a legal block: 1856 is 14.5 lane
tiles). ``fit`` arrives by scalar prefetch. A block whose first row is at or
past ``fit`` does nothing, and its index map points at the last block that
does, so it moves no bytes either: the pass costs the blocks that start below
``fit``, not R (``moe/layer._held_blocks``' rule, one block of ``ROWS`` at a
time). Inside a visited block a loop walks trips of ``_TRIP`` rows by lane
tiles: the values are read, taken to float32, put through the SAME text
(``_text``) and rounded ONCE at the write: never less than the XLA body's
precision (its fusion rounds at most once an operation), never a second
rounding of ``h``, no bf16 arithmetic. The backward reads ``gate``, ``up``
and ``dh`` and writes ``dgate`` and ``dup``; its formulas are ``jax.vjp`` of
that text on the loaded tile, so no second copy of an activation's derivative
exists anywhere.

ROWS PAST THE VISITED BLOCKS ARE LEFT UNWRITTEN, in ``h``, ``dgate`` and
``dup`` alike (as megablox ``gmm`` leaves the rows past its groups; within
the last visited block the rows from ``fit`` on are computed from whatever
the projections left there). Nothing may read them; who reads these arrays
is listed in ``expert_mlp_ragged``'s docstring.

Required a layer and step under per-half remat, V the visited rows: forward
and replay 3 passes of [V, F] each (2 reads, 1 write; 2 ungated), backward 5
(3 reads, 2 writes; 3 ungated): 11 V F x 2 bytes; at ``lfm2-train``'s V ~
34,800 of R 98,304 and F 1792 1.37 GB a layer, 1.7 ms at 819 GB/s, where the
XLA body moved ~13 passes of [R, F].
"""

from __future__ import annotations

import functools

# Rows a grid step (what a skipped block skips, and the most a visited block
# wastes past ``fit``) and rows a trip of the loop inside one works on, a lane
# tile at a time (8 float32 vregs a value).
ROWS = 512
_TRIP = 64
_LANES = 128

#: the activations the kernels take: ``_text`` of each lowers in Mosaic and
#: costs a few vector operations an element (a GELU keeps today's text)
ACTIVATIONS = ("swiglu", "reglu", "relu", "relu2", "silu")


def _text(activation: str):
    """The pass as plain text on arrays of one dtype: ``(gate, up) -> h`` for a
    gated unit, ``(up,) -> h`` for an ungated one. The XLA body is this on
    the arrays as they are; the kernels run it in float32 on a loaded tile
    (and ``jax.vjp`` of it for the backward)."""
    from ..models.transformer import activation_fn, gate_fn

    gate_act = gate_fn(activation)
    if gate_act is not None:
        return lambda gate, up: gate_act(gate) * up
    return activation_fn(activation)


def expert_act_route(x, w, activation: str) -> str:
    """Which form :func:`expert_act` runs after the projection
    ``grouped_matmul(x, w, ...)``, from what it can observe: "pallas" where
    that call takes megablox (``ops/dispatch.resolve_grouped_gemm``: a TPU
    backend, ``_gmm_ok`` shapes) at 2-byte float rows, an activation of
    ``ACTIVATIONS``, at least ``ROWS`` rows and no kernel mesh over several
    devices; "interpret" at such a call under ``SXT_FUSED_INTERPRET=1`` (these
    kernels have the interpreter megablox lacks); else "xla"."""
    import jax.numpy as jnp

    from ..parallel.mesh import kernel_mesh_devices
    from .dispatch import resolve_grouped_gemm
    from .grouped_gemm import _gmm_ok

    eligible = (jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize == 2
                and activation in ACTIVATIONS and x.shape[0] >= ROWS
                and kernel_mesh_devices() == 1)
    if not eligible:
        return "xla"
    route = resolve_grouped_gemm("moe", shapes_ok=_gmm_ok(x, w), interpret_capable=True)
    return "xla" if route == "fallback" else route


def expert_act(gate, up, fit, activation: str, route: str = "xla"):
    """``act(gate) * up`` (``gate`` None: ``act(up)``) of the experts' buffer:
    ``gate``, ``up`` [R, F] in one dtype -> h [R, F] in that dtype. ``fit``
    (int32 scalar, traced or static): the rows [0, ``fit``) are the ones
    anybody reads. ``route``: :func:`expert_act_route`'s answer. On the
    kernels' routes the rows of the blocks that start at or past ``fit`` are
    left unwritten, forward and backward (module docstring)."""
    arrays = (up,) if gate is None else (gate, up)
    assert all(a.ndim == 2 and a.shape == up.shape and a.dtype == up.dtype for a in arrays), (
        [(a.shape, a.dtype) for a in arrays])
    if route == "xla":
        return _text(activation)(*arrays)
    return _expert_act_pallas(arrays, fit, activation, interpret=route == "interpret")


def _expert_act_pallas(arrays, fit, activation, rows=None, interpret: bool = False):
    """``expert_act`` through the kernels: ``arrays`` is (gate, up) or (up,);
    ``rows``: the rows a grid step, ``ROWS`` unless a test says otherwise."""
    import jax.numpy as jnp

    rows = rows or ROWS
    assert rows % _TRIP == 0 and arrays[0].shape[0] >= rows, (rows, arrays[0].shape)
    fit = jnp.asarray(fit, jnp.int32).reshape(1)
    return _act_core(activation, rows, interpret)(fit, tuple(arrays))


@functools.lru_cache(maxsize=None)
def _act_core(activation, rows, interpret):
    """The pass as one ``jax.custom_vjp``: (fit [1] int32, (gate, up) or (up,)
    [R, F]) -> h [R, F]. The inputs are the only residuals; each launch under
    its own jit, built once (see ``gated_delta._delta_core``)."""
    import jax

    forward, backward = (jax.jit(functools.partial(
        fn, activation=activation, rows=rows, interpret=interpret))
        for fn in (_forward, _backward))
    core = jax.custom_vjp(forward)
    core.defvjp(lambda fit, arrays: (forward(fit, arrays), (fit, arrays)),
                lambda kept, dh: (None, backward(*kept, dh)))
    return core


def _launch(kernel, name, fit, operands, n_out, rows, interpret):
    """One launch over the row blocks of ``operands`` ([R, F] each) -> ``n_out``
    arrays [R, F]: step i works on block i where that starts below ``fit``;
    past it the index maps stay on the last block that does (block 0 where
    ``fit`` is 0: it is fetched, and written back as it was found), so a
    skipped step moves nothing and its rows of the outputs are never written.
    "arbitrary": the steps run in order on one core, which is what keeps a
    revisited block in place."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, F = operands[0].shape
    block = pl.BlockSpec(
        (rows, F), lambda i, fit: (jnp.minimum(i, jnp.maximum(pl.cdiv(fit[0], rows) - 1, 0)), 0))
    like = jax.ShapeDtypeStruct((R, F), operands[0].dtype)
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(R, rows),),
            in_specs=[block] * len(operands), out_specs=[block] * n_out),
        out_shape=[like] * n_out,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(fit, *operands)


def _forward(fit, arrays, activation, rows, interpret):
    """The forward kernel's launch -> h [R, F]."""
    kernel = functools.partial(_fwd_kernel, text=_text(activation))
    return _launch(kernel, "sxt_expert_act_fwd", fit, arrays, 1, rows, interpret)[0]


def _backward(fit, arrays, dh, activation, rows, interpret):
    """The backward kernel's launch -> (dgate, dup) or (dup,) [R, F]."""
    kernel = functools.partial(_bwd_kernel, text=_text(activation), n_in=len(arrays))
    return tuple(_launch(kernel, "sxt_expert_act_bwd", fit, (*arrays, dh), len(arrays),
                         rows, interpret))


def _each_tile(fit_ref, ref, body):
    """``body(at)`` for every tile ``at`` = (``_TRIP`` rows, a lane tile) of
    this grid step's block, where the block starts below ``fit``; as loops in
    the kernel (``ssm_conv._each_lane_tile``: unrolled, the tiles of a block
    are so many copies of the body for the host to trace and lower). A width
    that is no whole number of lane tiles ends in one narrower tile."""
    import jax
    from jax.experimental import pallas as pl

    rows, F = ref.shape

    @pl.when(pl.program_id(0) * rows < fit_ref[0])
    def _():
        def trip(t, carry):
            r = pl.ds(pl.multiple_of(t * _TRIP, _TRIP), _TRIP)

            def lane(j, carry):
                body((r, pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)))
                return carry

            jax.lax.fori_loop(0, F // _LANES, lane, 0)
            if F % _LANES:
                body((r, pl.ds(F - F % _LANES, F % _LANES)))
            return carry

        jax.lax.fori_loop(0, rows // _TRIP, trip, 0)


def _fwd_kernel(fit_ref, *refs, text):
    """A visited block: ``text`` of the inputs' tiles in float32, rounded once."""
    import jax.numpy as jnp

    *in_refs, h_ref = refs

    def tile(at):
        h_ref[at] = text(*(ref[at].astype(jnp.float32) for ref in in_refs)).astype(h_ref.dtype)

    _each_tile(fit_ref, h_ref, tile)


def _bwd_kernel(fit_ref, *refs, text, n_in):
    """The same block's cotangents: ``jax.vjp`` of ``text`` at the inputs'
    tiles in float32, applied to ``dh``'s, each rounded once."""
    import jax
    import jax.numpy as jnp

    in_refs, dh_ref, out_refs = refs[:n_in], refs[n_in], refs[n_in + 1:]

    def tile(at):
        _, pull = jax.vjp(text, *(ref[at].astype(jnp.float32) for ref in in_refs))
        for ref, d in zip(out_refs, pull(dh_ref[at].astype(jnp.float32))):
            ref[at] = d.astype(ref.dtype)

    _each_tile(fit_ref, dh_ref, tile)
