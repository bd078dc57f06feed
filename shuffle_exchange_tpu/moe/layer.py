"""Expert-parallel MoE layer.

Capability parity with the reference MoE stack (SURVEY.md §2.6 EP row):
``MoE`` wrapper (``moe/layer.py:17``), einsum dispatch → all-to-all over the
expert group → local expert FFNs → return all-to-all → combine
(``moe/sharded_moe.py:587-678``), EP×DP group construction
(``utils/groups.py:240``), residual MoE (``layer.py:105-131``), expert
param identification for the optimizer (``moe/utils.py:72``).

TPU-native shape: expert weights are stacked on a leading E dim sharded
over the mesh "expert" axis; dispatched activations get a
``with_sharding_constraint`` putting the expert dim on the same axis, and
XLA lowers the resharding into exactly the all-to-all pair the reference
issues by hand — scheduled/overlapped by the compiler (SURVEY §2.13
moe_gemm → the per-expert matmul is a single batched einsum on the MXU).
"""

from __future__ import annotations

import contextlib
import math
from typing import NamedTuple, Optional

from .gating import topk_gating


def init_expert_mlp(rng, n_experts: int, d_model: int, d_ff: int, activation: str = "swiglu",
                    bias: bool = False):
    """Stacked expert FFN weights: leading dim E (shard over "expert").

    ``bias=True`` adds per-expert b_up/b_down (+ b_gate for a gated unit) leaves —
    the classic Megatron/DeepSpeed-MoE expert layout (reference
    module_inject/containers/megatron_gpt_moe.py imports biased experts)."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    params = {
        "w_up": jax.random.normal(k2, (n_experts, d_model, d_ff), jnp.float32) * scale_in,
        "w_down": jax.random.normal(k3, (n_experts, d_ff, d_model), jnp.float32) * scale_out,
    }
    from ..models.transformer import gate_fn

    gated = gate_fn(activation) is not None      # "swiglu", "reglu": a third matrix
    if gated:
        params["w_gate"] = jax.random.normal(k1, (n_experts, d_model, d_ff), jnp.float32) * scale_in
    if bias:
        params["b_up"] = jnp.zeros((n_experts, d_ff), jnp.float32)
        params["b_down"] = jnp.zeros((n_experts, d_model), jnp.float32)
        if gated:
            params["b_gate"] = jnp.zeros((n_experts, d_ff), jnp.float32)
    return params


def expert_partition_specs(params):
    from jax.sharding import PartitionSpec as P

    def spec(k):
        if k in ("w_gate", "w_up"):
            return P("expert", None, "tensor")
        if k in ("b_gate", "b_up"):
            return P("expert", "tensor")
        if k == "b_down":
            return P("expert", None)
        return P("expert", "tensor", None)

    return {k: spec(k) for k in params}


def _dense_w(w, dtype):
    """Expert weight -> dense compute form. int8/fp8 STORAGE leaves
    (``QuantizedMatrix``, inference quantized serving) dequantize HERE,
    explicitly: XLA fuses the convert into the consuming einsum operand,
    so expert weights cross HBM at quantized width and convert in
    registers — the streamed-weight decode contract. (``.astype`` on a
    QuantizedMatrix materializes identically; the explicit branch keeps
    the contract visible at the use site.)"""
    from ..ops.quant_matmul import QuantizedMatrix

    if isinstance(w, QuantizedMatrix):
        return w.dequantize().astype(dtype)
    return w.astype(dtype)


def expert_mlp(params, x, activation: str = "swiglu"):
    """x [E, C', M] -> [E, C', M]: per-expert FFN as one batched einsum.
    Optional per-expert biases (b_gate/b_up/b_down) add as [E, 1, F]
    broadcasts — the Megatron biased-expert layout. Expert weights may be
    int8/fp8 ``QuantizedMatrix`` leaves (see :func:`_dense_w`)."""
    import jax.numpy as jnp

    def b(key, t):
        return t + params[key].astype(t.dtype)[:, None, :] if key in params else t

    from ..models.transformer import activation_fn, gate_fn

    gate_act = gate_fn(activation)
    up = b("b_up", jnp.einsum("ecm,emf->ecf", x, _dense_w(params["w_up"], x.dtype)))
    if gate_act:
        gate = b("b_gate", jnp.einsum("ecm,emf->ecf", x, _dense_w(params["w_gate"], x.dtype)))
        h = gate_act(gate) * up
    else:
        h = activation_fn(activation)(up)
    return b("b_down", jnp.einsum("ecf,efm->ecm", h, _dense_w(params["w_down"], x.dtype)))


def _gather_expert_sharded(params, expert_axis: str = "expert"):
    """Under a live expert axis, pin the stacked expert leaves to replicated
    inside the trace so XLA inserts an explicit all-gather before the
    grouped matmuls: weights stay expert-sharded at rest, the ragged math
    runs on the gathered copy. Written when GSPMD on jax 0.4.x
    mis-partitioned ``lax.ragged_dot`` with its RHS sharded over the group
    (expert) dim (wrong numerics on the 8-device CPU mesh, max err ~2.4).
    On jax 0.9 (the one installation this code targets) nobody has
    re-checked whether the partitioner is right without the pin; the
    megablox ``gmm`` kernel cannot be partitioned by XLA at all, so on a TPU
    the gather is needed either way. It stays until a cell runs an
    expert-parallel program (ROADMAP R1)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import (constraint_mesh, get_topology,
                                 topology_is_initialized)

    if not topology_is_initialized():
        return params
    mesh = get_topology().mesh
    if mesh.shape.get(expert_axis, 1) == 1:
        return params
    rep = NamedSharding(constraint_mesh(mesh), P())
    # tree.map (not a dict comprehension) so QuantizedMatrix expert
    # leaves pin BOTH children (q + scales) — a constraint on the
    # wrapper node would be structure-mismatched, and skipping it
    # would re-open the ragged_dot mispartition this gather fixes
    return jax.tree.map(
        lambda v: jax.lax.with_sharding_constraint(v, rep), params)


def _permuted_rows(x, perm, inverse, k: int = 1):
    """x [R, M] -> [len(perm), M]: row ``perm[i] // k`` of ``x`` at position
    i, where ``perm`` is a permutation of R * k items and ``inverse`` its
    inverse. The backward is a GATHER by ``inverse`` and a sum over each
    row's k copies. XLA's own transpose of the forward gather is a
    scatter-add (with colliding rows when k > 1), which a TPU serialises:
    33 ms of a 363 ms OLMoE step (PERF.md section 6, PR 28)."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def rows(x, perm, inverse, k):
        return jnp.take(x, perm // k, axis=0)

    def fwd(x, perm, inverse, k):
        return rows(x, perm, inverse, k), inverse

    def bwd(k, inverse, g):
        back = jnp.take(g, inverse, axis=0)
        return back.reshape(-1, k, g.shape[-1]).sum(axis=1), None, None

    rows.defvjp(fwd, bwd)
    return rows(x, perm, inverse, k)


def _rows_or_zeros(x, index):
    """Rows ``index`` of x [N, M]; a row of zeros where the index is N or more
    (how the held share marks "nothing here")."""
    import jax.numpy as jnp

    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


# Rows of the token-ordered buffer one banded product sums at a time
# (_run_sums). On a v5e 128 to 512 read the same to 5% (PERF.md section 6, PR 38).
_RUN_BLOCK = 256
# Positions of the buffer one trip of a row pass moves (_held_blocks): a
# multiple of _RUN_BLOCK. A pass costs the blocks up to ``fit``, so a smaller
# block wastes fewer empty rows of the last one and pays more trips
# (PERF.md section 6, PR 44: the sizes tried on a v5e).
_ROW_BLOCK = 2048


def _run_halo(k: int) -> int:
    """Rows of the next block a block's product reads too: a token's run of at
    most k rows that starts in a block ends within k - 1 rows of the next."""
    return -(-(k - 1) // 8) * 8


def _blocks_below(filled, extent: int, block: int):
    """How a row pass walks [0, ``extent``) up to ``filled`` (traced): (items a
    block, blocks that start below ``filled``)."""
    import jax.numpy as jnp

    block = max(1, min(block, extent))
    return block, -(-jnp.asarray(filled, jnp.int32) // block)


def _held_blocks(filled, extent: int, block: int, body, init):
    """``body(start, size, carry)`` over the blocks of ``size`` items of [0,
    ``extent``) that start below ``filled`` (traced), first to last: the trip
    count is read from the input, so the blocks past what the buffer holds
    cost nothing and ``init`` (zeros) is what the carry keeps there. The last
    block of an extent that is no whole number of blocks starts early and
    overlaps its neighbour, which therefore gets the same values twice (every
    body here writes a pure function of the position). Only ever called
    inside the hand-written rules of a ``custom_vjp``: a loop of traced length
    has no transpose."""
    import jax
    import jax.numpy as jnp

    size, trips = _blocks_below(filled, extent, block)

    def trip(i, carry):
        return body(jnp.minimum(i * size, extent - size), size, carry)

    return jax.lax.fori_loop(0, trips, trip, init)


def held_rows_visited(fit, buffer_rows: int):
    """Positions of a share's buffer its row passes visit: the blocks of
    _ROW_BLOCK that start below ``fit``, whole; over ``buffer_rows`` the share
    of the buffer that costs time."""
    import jax.numpy as jnp

    size, trips = _blocks_below(fit, buffer_rows, _ROW_BLOCK)
    return jnp.minimum(trips * size, buffer_rows)


def _held_runs(order, inverse, fit):
    """The held share's positions in TOKEN order, for the two sums over a
    token's held choices (the combine, the dispatch's backward).

    ``order`` [R]: the token-choice at each position (S * k where it holds
    nothing); ``inverse`` [S, k]: the position of each token-choice (R: none);
    ``fit``: positions that hold something. Sorting the R positions by their
    token-choice makes a token's held choices ONE contiguous run of at most k
    rows, and the positions that hold nothing sort last: the first ``fit`` rows
    of the token order hold something too. Returns ``runs`` [blocks, B + H]
    int32, the positions in that order cut into blocks of B rows, each with
    the first H rows of the next (R where there is no position: a row of
    zeros), and ``read`` [S]: the row of the run sums (:func:`_run_sums`)
    where token s's run starts; for a token with no held choice a row whose
    run is empty. One block more than the positions fill, so that such a row
    exists."""
    import jax.numpy as jnp

    R = order.shape[0]
    k = inverse.shape[1]
    H = _run_halo(k)
    B = max(H, min(_RUN_BLOCK, -(-R // 8) * 8))
    nb = R // B + 1
    by_token = jnp.argsort(order, stable=True).astype(jnp.int32)
    by_token = jnp.where(jnp.arange(R) < fit, by_token, R)
    by_token = jnp.concatenate([by_token, jnp.full(((nb + 1) * B - R,), R, jnp.int32)])
    runs = jnp.concatenate([by_token[:nb * B].reshape(nb, B),
                            by_token[B:].reshape(nb, B)[:, :H]], axis=1)
    count = (inverse < R).sum(axis=1, dtype=jnp.int32)
    read = jnp.where(count > 0, jnp.cumsum(count) - count, nb * B - 1)
    return runs, read


def _run_sums(rows, runs, read, order, k: int, fit, weights=None):
    """out[s] = the sum of ``rows`` [R, M] over the positions of token s's held
    choices, each times its choice's weight where ``weights`` [S * k] is given:
    [S, M]. ``runs``, ``read``: :func:`_held_runs`; ``fit``: the positions
    that hold something, the first ``fit`` rows of the token order.

    Row lookups bring the rows into token order, a banded 0/1 (or weight)
    matrix a block sums every run from each of its rows on in ONE product
    (float32 accumulation, rounded once; bf16 operands are exact in it), and S
    lookups read each token's sum where its run starts. The lookups into token
    order and the products walk the run blocks below ``fit`` only, _ROW_BLOCK
    rows a trip (:func:`_held_blocks`): ``fit`` x (1 + H / B) rows with the
    halo, up to a whole trip, not R x (1 + H / B); the sums past them are
    zeros nobody reads but a token with no held choice. On a v5e at R 30,720
    ALL visited, S 16,384, width 2048, k 10: 2.1-2.4 ms against 7.6 for k
    lookups a token; a k-row window a token 35-42, log2(k) shifted adds 6-10,
    k shifted adds 7-13 (PERF.md section 6, PR 38)."""
    import jax
    import jax.numpy as jnp

    nb, BH = runs.shape
    B = BH - _run_halo(k)
    M = rows.shape[1]
    dtype = rows.dtype
    ahead = jnp.arange(B)[:, None] <= jnp.arange(BH)[None, :]

    def some_blocks(start, size, sums):
        run = jax.lax.dynamic_slice_in_dim(runs, start, size)
        # S * k where there is no position, as ``order`` has it: "token" S, weight 0
        choice = jnp.take(order, run, mode="fill", fill_value=read.shape[0] * k)
        token = choice // k
        # band[b, i, j]: row j of block b (and its halo) holds the token of row i, at or after it
        band = (token[:, :B, None] == token[:, None, :]) & ahead
        if weights is None:
            band = band.astype(dtype)
        else:
            w = jnp.take(weights, choice, mode="fill", fill_value=0)
            band = jnp.where(band, w[:, None, :], 0).astype(dtype)
        # what the grouped GEMM leaves at a position that holds nothing is not
        # zeros: those rows are read as zeros, not multiplied by zero
        ordered = _rows_or_zeros(rows, run.reshape(-1)).reshape(run.shape + (M,))
        part = jnp.einsum(
            "bij,bjm->bim", band, ordered, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None)
        return jax.lax.dynamic_update_slice_in_dim(sums, part.astype(dtype), start, axis=0)

    sums = _held_blocks(-(-jnp.asarray(fit, jnp.int32) // B), nb, _ROW_BLOCK // B,
                        some_blocks, jnp.zeros((nb, B, M), dtype))
    return jnp.take(sums.reshape(nb * B, M), read, axis=0, mode="clip")


def _held_dispatch(xs, order, k: int, fit, runs, read):
    """One rank's share, the way in: xs [S, M] -> [R, M], row ``order[r] // k``
    of xs at position r (zeros where ``order[r]`` is S * k: the position holds
    nothing). The forward looks up the rows of the position blocks below
    ``fit`` (:func:`_held_blocks`); past them the result is the zeros it
    started as. The backward sums the gradient rows of a token's held choices
    over the token-ordered positions (:func:`_run_sums`: the visited rows + S
    lookups), never k lookups a token."""
    import jax
    import jax.numpy as jnp

    R = order.shape[0]

    @jax.custom_vjp
    def dispatch(xs, order, fit, runs, read):
        def block(start, size, out):
            index = jax.lax.dynamic_slice_in_dim(order, start, size) // k
            return jax.lax.dynamic_update_slice_in_dim(
                out, _rows_or_zeros(xs, index), start, axis=0)

        return _held_blocks(fit, R, _ROW_BLOCK, block, jnp.zeros((R,) + xs.shape[1:], xs.dtype))

    def fwd(xs, order, fit, runs, read):
        return dispatch(xs, order, fit, runs, read), (order, fit, runs, read)

    def bwd(res, g):
        order, fit, runs, read = res
        return _run_sums(g, runs, read, order, k, fit), None, None, None, None

    dispatch.defvjp(fwd, bwd)
    return dispatch(xs, order, fit, runs, read)


def _held_combine(out_sorted, weights, order, by_expert, fit, runs, read):
    """One rank's share, the way back: out[s] = sum over the token's k choices
    of ``weights[s, j]`` x the row of out_sorted [R, M] at that choice's
    position (nothing for a choice with no position), summed over the
    token-ordered positions (:func:`_run_sums`: the visited rows + S row
    lookups). The backward looks up the rows of the cotangent ONCE a position
    of the blocks below ``fit`` (row ``order[r] // k``; :func:`_held_blocks`):
    scaled by the position's weight they are out_sorted's gradient (zeros past
    the visited blocks), and their float32 dot with the position's own row of
    out_sorted is that choice's weight gradient. It goes from the positions to
    [S, k] by a SORT: ``by_expert`` [S * k] (:class:`RouteIndex`) names the
    choice at every position of the unbounded order, a permutation, so sorting
    the positions' numbers by it lays each at its choice (zeros at a position
    that holds nothing, so at a choice that has none); a lookup of S * k
    scalars took 7 ns each on a v5e (PERF.md section 6, PR 63)."""
    import jax
    import jax.numpy as jnp

    S, k = weights.shape
    R, M = out_sorted.shape
    dtype = out_sorted.dtype

    @jax.custom_vjp
    def combine(out_sorted, weights, order, by_expert, fit, runs, read):
        return _run_sums(out_sorted, runs, read, order, k, fit, weights.reshape(-1))

    def fwd(out_sorted, weights, order, by_expert, fit, runs, read):
        return (combine(out_sorted, weights, order, by_expert, fit, runs, read),
                (out_sorted, weights, order, by_expert, fit))

    def bwd(res, g):
        out_sorted, weights, order, by_expert, fit = res

        def block(start, size, grads):
            d_sorted, d_w_sorted = grads
            choice = jax.lax.dynamic_slice_in_dim(order, start, size)
            # a position that holds nothing reads weight 0 and a row of zeros
            w = jnp.take(weights.reshape(-1), choice, mode="fill", fill_value=0)
            g_rows = _rows_or_zeros(g, choice // k)
            own = jax.lax.dynamic_slice_in_dim(out_sorted, start, size)
            d_w = jnp.sum(g_rows.astype(jnp.float32) * own.astype(jnp.float32), axis=-1)
            return (jax.lax.dynamic_update_slice_in_dim(
                        d_sorted, w[:, None].astype(dtype) * g_rows, start, axis=0),
                    jax.lax.dynamic_update_slice_in_dim(d_w_sorted, d_w, start, axis=0))

        d_sorted, d_w_sorted = _held_blocks(
            fit, R, _ROW_BLOCK, block,
            (jnp.zeros((R, M), dtype), jnp.zeros((R,), jnp.float32)))
        # the last visited block's rows past ``fit`` are nobody's: 0 x what stands there
        d_w_sorted = jnp.where(jnp.arange(R) < fit, d_w_sorted, 0)
        d_w_sorted = jnp.pad(d_w_sorted, (0, max(0, S * k - R)))[:S * k]
        d_weights = jax.lax.sort((by_expert, d_w_sorted), num_keys=1)[1].reshape(S, k)
        return d_sorted, d_weights.astype(weights.dtype), None, None, None, None, None

    combine.defvjp(fwd, bwd)
    return combine(out_sorted, weights, order, by_expert, fit, runs, read)


def held_buffer_rows(tokens: int, k: int, held: int, n_experts: int,
                     factor: float, tile: int = 512) -> int:
    """Static rows of the buffer one rank's share of a routed layer sorts its
    held token-choices into: ``factor`` x the balanced share (tokens x k x
    held / n_experts), up to whole row tiles of the grouped GEMM, at most
    every token-choice."""
    balanced = tokens * k * held / n_experts
    return min(tokens * k, tile * max(1, math.ceil(factor * balanced / tile)))


def _choices_per_expert(flat_e, n_experts: int):
    """How many of the token-choices ``flat_e`` [S * k] fall on each of the
    experts [0, ``n_experts``): a compare against each expert, summed over the
    choices; a choice on no such expert counts nowhere. On a v5e 0.003-0.008
    ms at 163,840 choices over 9 to 65 experts and 0.055 ms over 513, where
    ``jnp.bincount``'s scatter-add took 1.43 ms whatever the width and however
    the choices collide (PERF.md section 6, PR 63)."""
    import jax.numpy as jnp

    experts = jnp.arange(n_experts, dtype=flat_e.dtype)
    return (flat_e[None, :] == experts[:, None]).sum(axis=1, dtype=jnp.int32)


class RouteIndex(NamedTuple):
    """The integer arrays of one dropless routing (:func:`_route_index`)."""
    by_expert: "jax.Array"      # [S * k] the token-choices sorted by expert: a permutation
    order: "jax.Array"          # [positions] the token-choice at each position
    inverse: "jax.Array"        # the position of each token-choice
    group_sizes: "jax.Array"    # [E] positions of each held expert
    fit: "jax.Array"            # positions that hold something
    held: "jax.Array"           # token-choices on held experts
    runs: Optional["jax.Array"]     # :func:`_held_runs`, a rank's share only
    read: Optional["jax.Array"]


def _route_index(topk_idx, E: int, expert_first: int = 0,
                 buffer_rows: Optional[int] = None) -> RouteIndex:
    """Where each token-choice of ``topk_idx`` [S, k] goes, sorted by expert
    (stable: a token's choices, and an expert's tokens, keep their order).

    ``buffer_rows`` None: all E experts are held. ``order`` [S * k] is the
    token-choice at each position (``by_expert`` itself), ``inverse`` [S * k]
    the position of each token-choice, ``fit`` = ``held`` = S * k. Else one
    rank's share, the experts [``expert_first``, ``expert_first`` + E): the
    held token-choices take the positions [0, ``fit``) of R = ``buffer_rows``;
    ``held`` - ``fit`` did not fit and are dropped, last experts first;
    ``order`` [R] reads S * k at a position that holds nothing, ``inverse``
    [S, k] reads R for a choice that has no position (absent or dropped).

    Every array comes from sorts, compares, reductions and running sums:
    nothing here is a scatter over the S * k token-choices, which a TPU runs
    one index at a time. On a v5e at 163,840 choices the inverse by a scatter
    took 0.76 ms and the counts by a scatter-add 1.43 (4.6 and 8.7 ns a
    choice, whatever the bins and however the choices collide) of the 2.41 ms
    this function took a layer and pass; a sort of as many (key, index) pairs
    0.15 (PERF.md section 6, PR 63). ``by_expert`` is a permutation, so its
    inverse is its argsort; the counts are :func:`_choices_per_expert`."""
    import jax.numpy as jnp

    S, k = topk_idx.shape
    share = buffer_rows is not None
    flat_e = topk_idx.reshape(-1)                        # [S*k]
    if share:
        # absent experts sort behind the held ones, as group E
        local = flat_e - expert_first
        flat_e = jnp.where((local >= 0) & (local < E), local, E)
    by_expert = jnp.argsort(flat_e, stable=True)
    inverse = jnp.argsort(by_expert)
    group_sizes = _choices_per_expert(flat_e, E)
    if not share:
        return RouteIndex(by_expert, by_expert, inverse, group_sizes, S * k, S * k, None, None)
    R = buffer_rows
    ends = jnp.minimum(jnp.cumsum(group_sizes), R)
    held, fit = group_sizes.sum(), ends[-1]
    group_sizes = jnp.diff(ends, prepend=0)
    # positions past the held rows hold nothing; absent and dropped
    # choices have no position
    order = jnp.where(jnp.arange(R) < fit, by_expert[:R], S * k)
    inverse = jnp.where(inverse < fit, inverse, R).reshape(S, k)
    runs, read = _held_runs(order, inverse, fit)
    return RouteIndex(by_expert, order, inverse, group_sizes, fit, held, runs, read)


def expert_mlp_ragged(params, xs, topk_idx, topk_w, activation: str = "swiglu",
                      expert_first: int = 0, buffer_rows: Optional[int] = None):
    """Dropless grouped-GEMM experts (reference cutlass moe_gemm /
    megablocks, SURVEY §2.13): tokens sort by expert and one grouped matmul
    per projection (``ops/grouped_gemm.py``: Pallas megablox ``gmm`` on
    TPU, ``lax.ragged_dot`` elsewhere) — no capacity padding slots, no
    dropped tokens, ragged group sizes straight onto the MXU.

    xs [S, M]; topk_idx [S, k] int32; topk_w [S, k] f32 -> (out [S, M],
    rows computed, rows dropped): S * k and 0 unless ``buffer_rows`` is given.

    ``buffer_rows`` None: ``params`` holds every expert ``topk_idx`` names.
    Else ONE RANK'S SHARE: ``params`` holds the experts [``expert_first``,
    ``expert_first`` + held) of the router's, and only the token-choices that
    fall on those are gathered (into ``buffer_rows`` static rows, sorted by
    held expert), multiplied and combined; a token-choice on an absent expert
    adds nothing here (its part of the result is another rank's). Held rows
    past the buffer are DROPPED, last experts first, and counted. There is
    no exchange: a rank alone computes its own part of the layer's result.

    The share moves the rows it HOLDS, never k lookups a token and never the
    buffer's empty tail: the held rows sort into the prefix [0, ``fit``) of
    the R = ``buffer_rows`` positions (and of their token order), and every
    row pass walks the blocks of _ROW_BLOCK positions that start below ``fit``
    (:func:`_held_blocks`; V = :func:`held_rows_visited`, ``fit`` up to a whole
    block, R when the buffer overflows). Of rows of width M: the way in V (and
    V again where the layer is replayed); the way back V (1.06 V with the
    runs' halo) into token order + S; backward V for the combine (one lookup
    serves ``out_sorted``'s gradient and the weights') and V + S for the
    dispatch: 5 V + 2 S, and a write of R rows of zeros a pass for what the
    passes skip, where PR 38's form looked up 5 R + 2 S and its parent's
    per-choice form 3 R + 3 k S (:func:`_held_runs`, :func:`_run_sums`). Of
    rows of the experts' width F, between the grouped GEMMs
    (``ops/expert_act.py``; on its kernels' route, which is ``grouped_matmul``'s
    megablox route at 2-byte rows): the activation reads ``gate`` and ``up``
    and writes ``h``, 3 V forward and 3 V replayed, and its backward reads
    those two and ``dh`` and writes ``dgate`` and ``dup``, 5 V: 11 V (7 V
    ungated), V there the blocks of ``expert_act.ROWS`` that start below
    ``fit``, where the plain text autodiff differentiated moved ~13 R and kept
    the activation's intermediates beside ``gate``, ``up`` and ``h``. The
    buffer's factor buys room in memory, not time in the passes.

    THE BUFFER'S TAIL IS NOBODY'S. Past ``fit`` a position holds nothing, and
    what stands there is not zeros: megablox ``gmm`` writes the rows of its
    groups and leaves the rest as it found them, the activation's kernels
    write the blocks they visit. Every reader takes its groups, or its runs,
    only: ``up`` / ``gate`` [R, F] (``gmm``'s) are read by the activation;
    ``h`` by the down projection's ``gmm`` and, backward, by the ``tgmm`` of
    ``w_down``'s gradient (groups only, both); ``dh`` (``gmm``'s) by the
    activation's backward; ``dgate`` / ``dup`` by the projections' backward
    ``gmm`` (the rows' gradient) and ``tgmm`` (the weights'), groups only;
    ``out_sorted`` [R, M] by :func:`_held_combine` through ``runs`` (a
    position that holds nothing is read as zeros, never multiplied by zero) and
    by its backward beside a weight no token-choice looks up; the rows'
    gradient d ``xsort`` by :func:`_held_dispatch`'s backward through ``runs``.
    The bias epilogues alone pass over every row (and their gradients sum
    every row): a share whose experts have biases would sum the tail into
    them on a TPU, before the kernels as after; no model here has both.
    """
    import jax.numpy as jnp

    from ..ops.expert_act import expert_act, expert_act_route
    from ..ops.grouped_gemm import grouped_matmul
    from ..ops.quant_matmul import QuantizedMatrix
    from ..profiling import trace

    params = _gather_expert_sharded(params)
    S, M = xs.shape
    k = topk_idx.shape[1]
    E = params["w_up"].shape[0]
    dtype = xs.dtype
    share = buffer_rows is not None
    with trace.scope("moe_dispatch"):
        by_expert, order, inverse, group_sizes, fit, held, runs, read = _route_index(
            topk_idx, E, expert_first, buffer_rows)
        if share:
            xsort = _held_dispatch(xs, order, k, fit, runs, read)    # [R, M]
        else:
            xsort = _permuted_rows(xs, order, inverse, k)    # [S*k, M]

    def b(key, t):
        # grouped-GEMM bias epilogue: gather each row's expert bias (a
        # position that holds nothing reads some held expert's bias into a
        # row nobody reads)
        if key not in params:
            return t
        e_sorted = jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(t.shape[0]),
                                    side="right", method="compare_all")
        return t + jnp.take(params[key].astype(dtype), jnp.minimum(e_sorted, E - 1), axis=0)

    def w(key):
        # int8/fp8 QuantizedMatrix expert stacks pass through UNCAST:
        # grouped_matmul owns the dequant policy (fused into ragged_dot's
        # operand on the fallback path; materialized once for the
        # megablox kernel) — an .astype here would densify at the call
        # site and forfeit the streamed-weight HBM win
        wt = params[key]
        return wt if isinstance(wt, QuantizedMatrix) else wt.astype(dtype)

    with trace.scope("moe_experts"):
        from ..models.transformer import gate_fn

        w_up = w("w_up")
        up = b("b_up", grouped_matmul(xsort, w_up, group_sizes))
        gate = (b("b_gate", grouped_matmul(xsort, w("w_gate"), group_sizes))
                if gate_fn(activation) else None)
        # the rows anybody reads: the held ones, every row where all are held
        h = expert_act(gate, up, fit, activation,
                       expert_act_route(xsort, w_up, activation))
        out_sorted = b("b_down", grouped_matmul(h, w("w_down"), group_sizes))
    with trace.scope("moe_combine"):
        if share:
            return (_held_combine(out_sorted, topk_w, order, by_expert, fit, runs, read),
                    fit, held - fit)
        out_flat = _permuted_rows(out_sorted, inverse, order)   # unsort
        out = (out_flat.reshape(S, k, M) * topk_w[..., None].astype(dtype)).sum(axis=1)
        return out, S * k, 0


class MoEResult(NamedTuple):
    output: "jax.Array"
    aux_loss: "jax.Array"
    metadata: dict


def resolve_moe_impl(impl: str, ep_size: int, scanned: bool = False) -> str:
    """Resolve ``impl="auto"`` to a concrete dispatch path. An explicit impl
    passes through: a model whose source routes every token to all its k
    experts (OLMoE) gets ``moe_impl="ragged"`` from
    ``models/hf.config_from_hf``, since any capacity is a different function.

    - an expert axis > 1 -> "capacity" (the EP path; XLA inserts the
      all-to-all pair around the sharded dispatch);
    - under a scanned layer stack -> "capacity" even without an expert
      axis, for models that were written against GShard capacity semantics
      (their tests pin it). It is a choice of SEMANTICS now, not of speed:
      the July "scan cliff" (megablox gmm ~4x slower under a ``lax.scan``)
      was the kernel's default (128, 128, 128) tile, alone and scanned
      alike. On a v5e at 131,072 rows x 64 experts x 2048 x 1024 the nine
      grouped GEMMs of a layer take 480 ms alone and 481 ms under a scan at
      that tile (5% of the bf16 peak), 35.7 ms and 36.5 ms at
      (512, 1024, 1024) (70%) (chip runs of PR 28, PERF.md 6; since PR 66
      ``ops/grouped_gemm._gmm_tiling`` gives each product its own tile);
    - otherwise -> "ragged" (dropless grouped-GEMM).
    """
    if impl != "auto":
        return impl
    if ep_size > 1 or scanned:
        return "capacity"
    return "ragged"


def moe_layer(gate_w, expert_params, x, k: int = 2, capacity_factor: float = 1.0,
              activation: str = "swiglu", train: bool = True, rng=None,
              noise_std: float = 0.0, min_capacity: int = 4, expert_axis: str = "expert",
              mesh=None, impl: str = "auto", normalize_weights: bool = True,
              scanned: bool = False, aux: str = "first_choice",
              expert_first: int = 0, buffer_rows: Optional[int] = None,
              score: str = "softmax", select_bias=None,
              weight_scale: float = 1.0, router_x=None) -> MoEResult:
    """x [..., M] -> MoEResult. gate_w [M, E].

    impl:
      - "capacity": GShard capacity/drop semantics dispatched BY INDEX
        (scalar slot scatter + row gathers, zero matmul flops); the EP path
        (dispatched tensor sharding-constrained to the expert axis -> XLA
        inserts the all-to-all pair).
      - "capacity_einsum": the dense [S, E, C] one-hot einsum dispatch —
        identical semantics, kept as the parity oracle (the one-hot
        matmuls cost 2·S·E·C·M flops each, more than the expert compute
        once S exceeds the expert width).
      - "ragged": dropless grouped-GEMM (``expert_mlp_ragged``) — no
        capacity padding FLOPs, no drops; the single-device/data-parallel
        path (reference cutlass moe_gemm).
      - "auto": capacity when the mesh has an expert axis > 1 OR the layer
        runs under a scanned stack (``scanned=True``, see
        ``resolve_moe_impl``); ragged otherwise.

    ``buffer_rows`` (with ``expert_first``): one expert-parallel rank's share.
    ``gate_w`` routes over all E experts (top-k, weights and balancing loss
    as for the whole layer), ``expert_params`` holds fewer, and the output is
    the part of the layer's result that those give
    (:func:`expert_mlp_ragged`; "ragged" only: the capacity paths dispatch
    into slots of every expert). ``metadata`` then also carries ``held_rows``
    (token-choices computed here), ``overflow_rows`` (held rows dropped) and
    ``visited_rows`` (buffer positions the row passes walked:
    :func:`held_rows_visited`).

    ``aux``: which balancing loss ``aux_loss`` is (``gating.topk_select``).
    ``score`` ("softmax" | "sigmoid"), ``select_bias`` [E] (selects, is not
    weighed, gets no gradient) and ``weight_scale``: the router's other forms
    (``gating.topk_select``; DeepSeek-V3's is sigmoid, biased, scaled, with
    ``aux="sequence"`` or ``"none"``), on the dropless "ragged" impl only: the capacity paths
    renormalise after their drops, which these forms have not been held to.
    ``router_x`` [..., M] (x's leading shape): what the ROUTER reads where that
    is not what the experts read (SmallThinker: the block's input, taken before
    attention, while the experts read the post-attention norm ``x``): the
    logits, the mean score and the balancing loss come from it, the experts'
    rows from ``x``, and the router's gradient reaches ``router_x``. On the
    "ragged" impl, a rank's share too; the router's scopes then nest in
    ``pre_router``. None: the router reads ``x``.
    Named scopes inside the caller's ``moe``: ``moe_router`` (router matmul,
    softmax, top-k, aux), ``moe_dispatch`` (sort / slot assignment, gather,
    group sizes), ``moe_experts`` (the expert matmuls and activation),
    ``moe_combine`` (unsort, weighting, sum over k). ``metadata`` carries
    ``expert_counts`` [E] (token-choices each expert computed) and
    ``router_prob`` [E] (mean router probability, differentiable); with a
    ``select_bias`` also ``expert_weight`` [E], the sum of the weights of each
    expert's token-choices.
    """
    import jax
    import jax.numpy as jnp

    from ..profiling import trace

    if impl not in ("auto", "capacity", "capacity_einsum", "ragged"):
        # validate BEFORE the dispatch chain: an unrecognized string (e.g. a
        # typo like "einsum" or "index") would otherwise silently fall
        # through to the index-dispatch capacity path (ADVICE r5 #1)
        raise ValueError(
            f"moe impl must be one of 'auto', 'capacity', 'capacity_einsum', "
            f"'ragged'; got {impl!r}")

    if buffer_rows is not None and impl != "ragged":
        raise ValueError(
            "a rank's share of the experts (buffer_rows) runs the dropless "
            f"'ragged' impl only; got impl={impl!r}")
    plain_router = (score == "softmax" and select_bias is None
                    and weight_scale == 1.0 and aux != "sequence"
                    and router_x is None)
    if not plain_router and impl != "ragged":
        raise ValueError(
            "a sigmoid router, a selection bias, a weight scale, the "
            "sequence-wise balance loss or a router input of its own (router_x) "
            f"run the dropless 'ragged' impl only; got impl={impl!r}")
    orig_shape = x.shape
    M = orig_shape[-1]
    xs = x.reshape(-1, M)
    S = xs.shape[0]
    if router_x is not None and router_x.shape != orig_shape:
        raise ValueError(f"router_x {router_x.shape} is not x's shape {orig_shape}")
    rs = xs if router_x is None else router_x.reshape(-1, M)

    @contextlib.contextmanager
    def router_scope():
        """``moe_router``, inside ``pre_router`` where the router reads an
        input of its own."""
        outer = contextlib.nullcontext() if router_x is None else trace.scope("pre_router")
        with outer, trace.scope("moe_router"):
            yield
    with router_scope():
        logits = (rs.astype(jnp.float32)) @ gate_w.astype(jnp.float32)   # [S, E]
        # mean router score per expert (the gating's own softmax or sigmoid
        # again: XLA computes it once)
        prob = (jax.nn.sigmoid(logits) if score == "sigmoid"
                else jax.nn.softmax(logits, axis=-1)).mean(axis=0)

    if impl == "auto":
        # the explicit mesh argument wins; fall back to the global topology
        if mesh is not None:
            ep = dict(getattr(mesh, "shape", {})).get(expert_axis, 1)
        else:
            from ..parallel.mesh import get_topology, topology_is_initialized

            ep = get_topology().size(expert_axis) if topology_is_initialized() else 1
        impl = resolve_moe_impl("auto", ep, scanned)
        from ..utils.logging import warning_once

        if impl == "ragged":
            warning_once(
                "moe_impl=auto resolved to the dropless ragged grouped-GEMM "
                "path (no expert axis > 1, unscanned): capacity_factor/"
                "min_capacity/drop semantics do not apply — set "
                "moe_impl='capacity' to keep GShard capacity/drop behavior")
        elif ep <= 1 and scanned:
            warning_once(
                "moe_impl=auto resolved to the capacity (index-dispatch) "
                "path: this layer runs under a scanned stack. "
                "Capacity/drop semantics apply "
                "(capacity_factor/min_capacity; overflow tokens drop) — set "
                "moe_impl='ragged' for dropless routing (as fast under a scan "
                "as outside one since ops/grouped_gemm tiles the kernel: "
                "PERF.md section 6, PR 28)")
    if impl == "ragged":
        from .gating import topk_select

        with router_scope():
            idx, w, aux_loss, masks = topk_select(
                logits, k, normalize_weights=normalize_weights, train=train,
                rng=rng, noise_std=noise_std, aux=aux, score=score,
                select_bias=select_bias, weight_scale=weight_scale,
                sequences=math.prod(orig_shape[:-2]))
            counts = _choices_per_expert(idx.reshape(-1), gate_w.shape[1])
        meta = {"expert_counts": counts, "drop_fraction": jnp.zeros(()),
                "capacity": S, "router_prob": prob}
        if select_bias is not None:
            # what each expert's choices weigh in all: a bias that entered the
            # weights as well as the choice shows here and in no count
            with router_scope():
                meta["expert_weight"] = jax.lax.stop_gradient(sum(
                    (m * w[:, j:j + 1]).sum(axis=0) for j, m in enumerate(masks)))
        out, rows, dropped = expert_mlp_ragged(
            expert_params, xs, idx, w, activation,
            expert_first=expert_first, buffer_rows=buffer_rows)
        if buffer_rows is not None:
            meta.update(held_rows=rows, overflow_rows=dropped,
                        visited_rows=held_rows_visited(rows, buffer_rows),
                        drop_fraction=dropped / (S * k), capacity=buffer_rows)
        return MoEResult(out.reshape(orig_shape), aux_loss, meta)

    if impl == "capacity_einsum":
        # the GShard dense-mask contract, kept as the parity oracle: the
        # one-hot dispatch/combine einsums are real matmuls costing
        # 2·S·E·C·M flops EACH (the expert matrices cost 2·E·C·M·F each)
        gate = topk_gating(logits, k=k, capacity_factor=capacity_factor, train=train,
                           rng=rng, noise_std=noise_std, min_capacity=min_capacity,
                           normalize_weights=normalize_weights, aux=aux)

        dispatched = jnp.einsum("sec,sm->ecm", gate.dispatch_mask.astype(xs.dtype), xs)
        dispatched = _constrain_expert(dispatched, expert_axis, mesh)
        expert_out = expert_mlp(expert_params, dispatched, activation)
        expert_out = _constrain_expert(expert_out, expert_axis, mesh)
        combined = jnp.einsum("sec,ecm->sm", gate.combine_weights.astype(xs.dtype), expert_out)
        return MoEResult(combined.reshape(orig_shape), gate.aux_loss,
                         {**gate.metadata, "router_prob": prob})

    # "capacity": same assignment/drop semantics in index form — dispatch is
    # one scalar scatter (slot -> token id) plus a row gather, combine is a
    # row gather weighted by the compact gate weights. Zero matmul flops
    # (round 5; the reference's own v2 engine dispatches by index the same
    # way, inference/v2/ragged_ops/moe_scatter). EP evidence: parity +
    # training on the 8-device CPU mesh (test_moe_expert_parallel_*,
    # dryrun config 3) and 1.84x on one real chip; how XLA lowers the
    # cross-shard gather on a real EP pod (a2a vs all-gather of xs) is
    # unmeasured until multi-chip hardware is available — if it regresses
    # there, set moe_impl="capacity_einsum" to restore the proven wire.
    from .gating import topk_gating_compact

    with trace.scope("moe_router"):
        ca = topk_gating_compact(logits, k=k, capacity_factor=capacity_factor,
                                 train=train, rng=rng, noise_std=noise_std,
                                 min_capacity=min_capacity,
                                 normalize_weights=normalize_weights, aux=aux)
    E = gate_w.shape[1]
    C = ca.capacity
    with trace.scope("moe_dispatch"):
        slot = ca.eidx * C + ca.loc                              # [S, k]
        trash = E * C                                            # dropped -> trash slot
        tgt = jnp.where(ca.kept, slot, trash)
        token_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None], tgt.shape)
        # kept slots are unique by construction (cumsum buffer positions), so
        # the scatter never collides; empty slots keep sentinel S -> zero row
        inv = jnp.full((E * C + 1,), S, jnp.int32).at[tgt.reshape(-1)].set(
            token_ids.reshape(-1), mode="drop")[:E * C]
        xs_pad = jnp.concatenate([xs, jnp.zeros((1, M), xs.dtype)], axis=0)
        dispatched = xs_pad[inv].reshape(E, C, M)
        dispatched = _constrain_expert(dispatched, expert_axis, mesh)
    with trace.scope("moe_experts"):
        expert_out = expert_mlp(expert_params, dispatched, activation)
    with trace.scope("moe_combine"):
        expert_out = _constrain_expert(expert_out, expert_axis, mesh)
        eo = expert_out.reshape(E * C, M)
        gath = eo[jnp.clip(slot, 0, E * C - 1)]                  # [S, k, M]
        # ca.weights is already zero for dropped choices (the one drop-zeroing
        # site, topk_gating_compact), so the clipped gather row is harmless
        w = ca.weights.astype(xs.dtype)
        combined = (w[..., None] * gath).sum(axis=1)
    return MoEResult(combined.reshape(orig_shape), ca.aux_loss,
                     {**ca.metadata, "router_prob": prob})


def _constrain_expert(t, expert_axis, mesh):
    import jax
    from jax.sharding import PartitionSpec as P

    from jax.sharding import NamedSharding

    if mesh is None:
        from ..parallel.mesh import topology_is_initialized, get_topology

        if not topology_is_initialized():
            return t
        mesh = get_topology().mesh
    if mesh.shape.get(expert_axis, 1) == 1:
        return t
    from ..parallel.mesh import constraint_mesh

    return jax.lax.with_sharding_constraint(
        t, NamedSharding(constraint_mesh(mesh), P(expert_axis, None, None)))


def residual_moe(gate_w, expert_params, dense_params, coef_w, x, activation: str = "swiglu",
                 **moe_kwargs) -> MoEResult:
    """Residual MoE (reference moe/layer.py:105-131): blend a dense MLP path
    with the MoE path via a learned 2-way coefficient."""
    import jax
    import jax.numpy as jnp

    res = moe_layer(gate_w, expert_params, x, activation=activation, **moe_kwargs)
    dense = expert_mlp({k: v[None] for k, v in dense_params.items()},
                       x.reshape(1, -1, x.shape[-1]), activation).reshape(x.shape)
    coef = jax.nn.softmax((x.astype(jnp.float32) @ coef_w.astype(jnp.float32)), axis=-1)
    out = dense * coef[..., 0:1].astype(x.dtype) + res.output * coef[..., 1:2].astype(x.dtype)
    return MoEResult(out, res.aux_loss, res.metadata)
