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

import math
from typing import NamedTuple, Optional

from .gating import topk_gating


def init_expert_mlp(rng, n_experts: int, d_model: int, d_ff: int, activation: str = "swiglu",
                    bias: bool = False):
    """Stacked expert FFN weights: leading dim E (shard over "expert").

    ``bias=True`` adds per-expert b_up/b_down (+ b_gate for swiglu) leaves —
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
    if activation == "swiglu":
        params["w_gate"] = jax.random.normal(k1, (n_experts, d_model, d_ff), jnp.float32) * scale_in
    if bias:
        params["b_up"] = jnp.zeros((n_experts, d_ff), jnp.float32)
        params["b_down"] = jnp.zeros((n_experts, d_model), jnp.float32)
        if activation == "swiglu":
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
    import jax
    import jax.numpy as jnp

    def b(key, t):
        return t + params[key].astype(t.dtype)[:, None, :] if key in params else t

    up = b("b_up", jnp.einsum("ecm,emf->ecf", x, _dense_w(params["w_up"], x.dtype)))
    if activation == "swiglu":
        gate = b("b_gate", jnp.einsum("ecm,emf->ecf", x, _dense_w(params["w_gate"], x.dtype)))
        h = jax.nn.silu(gate) * up
    else:
        from ..models.transformer import activation_fn

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


def expert_mlp_ragged(params, xs, topk_idx, topk_w, activation: str = "swiglu"):
    """Dropless grouped-GEMM experts (reference cutlass moe_gemm /
    megablocks, SURVEY §2.13): tokens sort by expert and one grouped matmul
    per projection (``ops/grouped_gemm.py``: Pallas megablox ``gmm`` on
    TPU, ``lax.ragged_dot`` elsewhere) — no capacity padding slots, no
    dropped tokens, ragged group sizes straight onto the MXU.

    xs [S, M]; topk_idx [S, k] int32; topk_w [S, k] f32 -> [S, M].
    """
    import jax
    import jax.numpy as jnp

    from ..ops.grouped_gemm import grouped_matmul
    from ..ops.quant_matmul import QuantizedMatrix
    from ..profiling import trace

    params = _gather_expert_sharded(params)
    S, M = xs.shape
    k = topk_idx.shape[1]
    E = params["w_up"].shape[0]
    dtype = xs.dtype
    with trace.scope("moe_dispatch"):
        flat_e = topk_idx.reshape(-1)                        # [S*k]
        order = jnp.argsort(flat_e, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(S * k, dtype=order.dtype), unique_indices=True)
        xsort = _permuted_rows(xs, order, inverse, k)        # [S*k, M]
        group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        e_sorted = jnp.take(flat_e, order)                   # [S*k] expert per row

    def b(key, t):
        # grouped-GEMM bias epilogue: gather each row's expert bias
        if key not in params:
            return t
        return t + jnp.take(params[key].astype(dtype), e_sorted, axis=0)

    def w(key):
        # int8/fp8 QuantizedMatrix expert stacks pass through UNCAST:
        # grouped_matmul owns the dequant policy (fused into ragged_dot's
        # operand on the fallback path; materialized once for the
        # megablox kernel) — an .astype here would densify at the call
        # site and forfeit the streamed-weight HBM win
        wt = params[key]
        return wt if isinstance(wt, QuantizedMatrix) else wt.astype(dtype)

    with trace.scope("moe_experts"):
        up = b("b_up", grouped_matmul(xsort, w("w_up"), group_sizes))
        if activation == "swiglu":
            gate = b("b_gate", grouped_matmul(xsort, w("w_gate"), group_sizes))
            h = jax.nn.silu(gate) * up
        else:
            from ..models.transformer import activation_fn

            h = activation_fn(activation)(up)
        out_sorted = b("b_down", grouped_matmul(h, w("w_down"), group_sizes))
    with trace.scope("moe_combine"):
        out_flat = _permuted_rows(out_sorted, inverse, order)   # unsort
        return (out_flat.reshape(S, k, M) * topk_w[..., None].astype(dtype)).sum(axis=1)


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
      ``ops/grouped_gemm._GMM_TILE`` (70%) (chip runs of PR 28, PERF.md 6);
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
              scanned: bool = False, aux: str = "first_choice") -> MoEResult:
    """x [..., M] -> MoEResult. gate_w [M, E].

    impl:
      - "capacity": GShard capacity/drop semantics dispatched BY INDEX
        (scalar slot scatter + row gathers, zero matmul flops); the EP path
        (dispatched tensor sharding-constrained to the expert axis -> XLA
        inserts the all-to-all pair).
      - "capacity_einsum": the dense [S, E, C] one-hot einsum dispatch —
        identical semantics, kept as the parity oracle (the one-hot
        matmuls cost 2·S·E·C·M flops each, ~4x the expert compute at
        bench shapes — round-5 on-chip profile).
      - "ragged": dropless grouped-GEMM (``expert_mlp_ragged``) — no
        capacity padding FLOPs, no drops; the single-device/data-parallel
        path (reference cutlass moe_gemm).
      - "auto": capacity when the mesh has an expert axis > 1 OR the layer
        runs under a scanned stack (``scanned=True``, see
        ``resolve_moe_impl``); ragged otherwise.

    ``aux``: which balancing loss ``aux_loss`` is (``gating.topk_select``).
    Named scopes inside the caller's ``moe``: ``moe_router`` (router matmul,
    softmax, top-k, aux), ``moe_dispatch`` (sort / slot assignment, gather,
    group sizes), ``moe_experts`` (the expert matmuls and activation),
    ``moe_combine`` (unsort, weighting, sum over k). ``metadata`` carries
    ``expert_counts`` [E] (token-choices each expert computed) and
    ``router_prob`` [E] (mean router probability, differentiable).
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

    orig_shape = x.shape
    M = orig_shape[-1]
    xs = x.reshape(-1, M)
    S = xs.shape[0]
    with trace.scope("moe_router"):
        logits = (xs.astype(jnp.float32)) @ gate_w.astype(jnp.float32)   # [S, E]
        # mean router probability per expert (the gating's own softmax again:
        # XLA computes it once)
        prob = jax.nn.softmax(logits, axis=-1).mean(axis=0)

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

        with trace.scope("moe_router"):
            idx, w, aux_loss, _ = topk_select(
                logits, k, normalize_weights=normalize_weights, train=train,
                rng=rng, noise_std=noise_std, aux=aux)
            counts = jnp.bincount(idx.reshape(-1), length=gate_w.shape[1])
        out = expert_mlp_ragged(expert_params, xs, idx, w, activation)
        return MoEResult(out.reshape(orig_shape), aux_loss,
                         {"expert_counts": counts, "drop_fraction": jnp.zeros(()),
                          "capacity": S, "router_prob": prob})

    if impl == "capacity_einsum":
        # the GShard dense-mask contract, kept as the parity oracle: the
        # one-hot dispatch/combine einsums are real matmuls costing
        # 2·S·E·C·M flops EACH — ~4x the expert compute at bench shapes
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
