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
    """GSPMD on jax 0.4.x mis-partitions ``lax.ragged_dot`` when the RHS
    is sharded over the group (expert) dim — wrong numerics, not just a
    slow program (observed on the 8-device CPU mesh: max err ~2.4 vs the
    replicated reference). Under a live expert axis, pin the stacked
    expert leaves to replicated inside the trace so XLA inserts an
    explicit all-gather before the grouped matmuls: weights stay
    expert-sharded at rest, the ragged math runs on the gathered copy."""
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

    params = _gather_expert_sharded(params)
    S, M = xs.shape
    k = topk_idx.shape[1]
    E = params["w_up"].shape[0]
    flat_e = topk_idx.reshape(-1)                        # [S*k]
    order = jnp.argsort(flat_e, stable=True)
    token_of = order // k
    xsort = jnp.take(xs, token_of, axis=0)               # [S*k, M]
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

    from ..ops.grouped_gemm import grouped_matmul
    from ..ops.quant_matmul import QuantizedMatrix

    dtype = xs.dtype
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

    up = b("b_up", grouped_matmul(xsort, w("w_up"), group_sizes))
    if activation == "swiglu":
        gate = b("b_gate", grouped_matmul(xsort, w("w_gate"), group_sizes))
        h = jax.nn.silu(gate) * up
    else:
        from ..models.transformer import activation_fn

        h = activation_fn(activation)(up)
    out_sorted = b("b_down", grouped_matmul(h, w("w_down"), group_sizes))
    out_flat = jnp.zeros_like(out_sorted).at[order].set(out_sorted)   # unsort
    return (out_flat.reshape(S, k, M) * topk_w[..., None].astype(dtype)).sum(axis=1)


class MoEResult(NamedTuple):
    output: "jax.Array"
    aux_loss: "jax.Array"
    metadata: dict


def resolve_moe_impl(impl: str, ep_size: int, scanned: bool = False) -> str:
    """Resolve ``impl="auto"`` to a concrete dispatch path.

    - an expert axis > 1 -> "capacity" (the EP path; XLA inserts the
      all-to-all pair around the sharded dispatch);
    - under a scanned layer stack -> "capacity" even without an expert
      axis: the Pallas megablox gmm ran the bench step ~4x slower inside
      a ``lax.scan`` over stacked layer weights (5.3% vs 23.1% active-param
      MFU on-chip, scripts/bench_moe_impl.py) — the scan context starves
      the grouped kernel; standalone gmm is fine;
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
              scanned: bool = False) -> MoEResult:
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
        path (reference cutlass moe_gemm). Perf note (v5e, 2026-07, both
        measured on-chip): under a ``lax.scan`` over stacked layer weights
        the Pallas megablox gmm ran the bench step 2.4x SLOWER than the
        capacity einsums (5.3% vs 12.5% active-param MFU) — measure before
        picking ragged for a scanned stack; standalone gmm is fine.
      - "auto": capacity when the mesh has an expert axis > 1 OR the layer
        runs under a scanned stack (``scanned=True`` — the model's
        ``stack_apply`` passes it; megablox gmm measured ~4x slower there,
        see ``resolve_moe_impl``); ragged otherwise.
    """
    import jax
    import jax.numpy as jnp

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
    logits = (xs.astype(jnp.float32)) @ gate_w.astype(jnp.float32)   # [S, E]

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
                "path: this layer runs under a scanned stack, where the "
                "ragged megablox grouped-GEMM measured ~4x SLOWER on-chip "
                "(5.3% vs 23.1% active-param MFU, scripts/bench_moe_impl.py)."
                " Capacity/drop semantics apply (capacity_factor/"
                "min_capacity; overflow tokens drop) — set "
                "moe_impl='ragged' to force dropless routing despite the "
                "perf cliff")
    if impl == "ragged":
        from .gating import topk_select

        idx, w, aux, _ = topk_select(logits, k, normalize_weights=normalize_weights,
                                     train=train, rng=rng, noise_std=noise_std)
        out = expert_mlp_ragged(expert_params, xs, idx, w, activation)
        counts = jnp.bincount(idx.reshape(-1), length=gate_w.shape[1])
        return MoEResult(out.reshape(orig_shape), aux,
                         {"expert_counts": counts, "drop_fraction": jnp.zeros(()),
                          "capacity": S})

    if impl == "capacity_einsum":
        # the GShard dense-mask contract, kept as the parity oracle: the
        # one-hot dispatch/combine einsums are real matmuls costing
        # 2·S·E·C·M flops EACH — ~4x the expert compute at bench shapes
        gate = topk_gating(logits, k=k, capacity_factor=capacity_factor, train=train,
                           rng=rng, noise_std=noise_std, min_capacity=min_capacity,
                           normalize_weights=normalize_weights)

        dispatched = jnp.einsum("sec,sm->ecm", gate.dispatch_mask.astype(xs.dtype), xs)
        dispatched = _constrain_expert(dispatched, expert_axis, mesh)
        expert_out = expert_mlp(expert_params, dispatched, activation)
        expert_out = _constrain_expert(expert_out, expert_axis, mesh)
        combined = jnp.einsum("sec,ecm->sm", gate.combine_weights.astype(xs.dtype), expert_out)
        return MoEResult(combined.reshape(orig_shape), gate.aux_loss, gate.metadata)

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

    ca = topk_gating_compact(logits, k=k, capacity_factor=capacity_factor,
                             train=train, rng=rng, noise_std=noise_std,
                             min_capacity=min_capacity,
                             normalize_weights=normalize_weights)
    E = gate_w.shape[1]
    C = ca.capacity
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
    expert_out = expert_mlp(expert_params, dispatched, activation)
    expert_out = _constrain_expert(expert_out, expert_axis, mesh)
    eo = expert_out.reshape(E * C, M)
    gath = eo[jnp.clip(slot, 0, E * C - 1)]                  # [S, k, M]
    # ca.weights is already zero for dropped choices (the one drop-zeroing
    # site, topk_gating_compact), so the clipped gather row is harmless
    w = ca.weights.astype(xs.dtype)
    combined = (w[..., None] * gath).sum(axis=1)
    return MoEResult(combined.reshape(orig_shape), ca.aux_loss, ca.metadata)


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
