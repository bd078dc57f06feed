"""MoE gating: top-1 / top-2 / top-k with capacity and aux losses.

Capability parity with the reference's ``moe/sharded_moe.py`` (top1gating
:183, top2gating :290, topkgating :374 — itself the GShard formulation):
softmax gate over experts, iterative top-k selection, per-expert capacity
``ceil(k·S/E · capacity_factor)`` with overflow drop, load-balancing aux
loss ``E · Σ_e mean(gates_e)·mean(mask_e)``, optional gate-noise for
exploration, and the (combine_weights, dispatch_mask) einsum-dispatch
contract.

All shapes are static: [S, E] in, ([S, E, C], [S, E, C] bool, aux) out —
XLA-friendly (no dynamic token routing; drops are masked, not ragged).
"""

from __future__ import annotations

from typing import NamedTuple


class GateOutput(NamedTuple):
    combine_weights: "jax.Array"   # [S, E, C] f32
    dispatch_mask: "jax.Array"     # [S, E, C] bool
    aux_loss: "jax.Array"          # scalar
    metadata: dict                 # expert_counts, dropped fraction (traced)


class GateCompact(NamedTuple):
    """Index-form capacity assignment (same semantics as GateOutput's dense
    masks, O(S·k) instead of O(S·E·C)): the dense dispatch/combine einsums
    are one-hot MATMULS costing 2·S·E·C·M flops each, against the expert
    MLP's own 2·E·C·M·F a matrix, while gather/scatter dispatch moves the
    same rows with no matmul at all."""

    eidx: "jax.Array"       # [S, k] i32  expert id per choice
    loc: "jax.Array"        # [S, k] i32  slot within the expert's buffer
    kept: "jax.Array"       # [S, k] bool False = dropped (over capacity)
    weights: "jax.Array"    # [S, k] f32  post-drop (+renorm) combine weight
    capacity: int
    aux_loss: "jax.Array"
    metadata: dict


def compute_capacity(num_tokens: int, num_experts: int, k: int, capacity_factor: float,
                     min_capacity: int = 4) -> int:
    cap = int(-(-num_tokens * k * capacity_factor // num_experts))
    return max(cap, min_capacity)


def topk_select(logits, k: int, normalize_weights: bool = True,
                train: bool = False, rng=None, noise_std: float = 0.0,
                aux: str = "first_choice", score: str = "softmax",
                select_bias=None, weight_scale: float = 1.0, sequences: int = 1):
    """The ONE top-k routing rule (iterative argmax — ties broken by
    expert order), shared by the capacity path (topk_gating) and the
    dropless ragged path (moe/layer.expert_mlp_ragged), so the two can
    never diverge on selection/noise/aux semantics.

    logits [S, E] -> (idx [S,k] i32, weights [S,k] f32, aux_loss, masks)
    where masks is the per-choice one-hot list. ``aux``: "first_choice" is
    the reference l_aux on the first choice (moe/sharded_moe.py);
    "all_choices" is HF's ``load_balancing_loss_func`` over these tokens,
    ``E * sum_{j,e} mean_s(mask_j)[e] * mean_s(gates)[e]`` with all k choices
    counted (OLMoE, Mixtral's HF form); "none": no balancing loss (0);
    "sequence": DeepSeek-V3's complementary sequence-wise balance loss, the
    mean over the ``sequences`` the S tokens form (contiguous, equally long)
    of ``sum_e f_e P_e`` with ``f_e = E / (k T) x`` the sequence's
    token-choices of expert e and ``P_e`` the sequence's mean of the scores
    normalised over ALL experts (its coefficient alpha is the caller's).

    ``score``: what an expert's logit becomes, "softmax" over the experts or
    "sigmoid" of each alone (DeepSeek-V3). ``select_bias`` [E]: added to the
    scores for the CHOICE only (DeepSeek-V3's ``e_score_correction_bias``,
    the aux-free balancing buffer): the weights are the scores without it,
    and no gradient reaches it. ``weight_scale`` multiplies the weights
    (after the normalisation over the chosen, whose floor is then 1e-20 as
    the source's). The defaults are the softmax router every caller had.
    """
    import jax
    import jax.numpy as jnp

    if aux not in ("first_choice", "all_choices", "none", "sequence"):
        raise ValueError("moe aux must be 'first_choice', 'all_choices', "
                         f"'sequence' or 'none'; got {aux!r}")
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"moe score must be 'softmax' or 'sigmoid'; got {score!r}")
    E = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if train and noise_std > 0.0 and rng is not None:
        logits = logits + noise_std * jax.random.normal(rng, logits.shape, jnp.float32)
    if score == "softmax":
        gates = jax.nn.softmax(logits, axis=-1)
        ranked = logits              # softmax keeps the logits' order
    else:
        gates = ranked = jax.nn.sigmoid(logits)
    if select_bias is not None:
        ranked = gates + jax.lax.stop_gradient(select_bias.astype(jnp.float32))

    idxs, ws, masks = [], [], []
    masked = ranked
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        idxs.append(idx.astype(jnp.int32))
        ws.append(jnp.sum(gates * m, axis=-1))
        masks.append(m)
        masked = jnp.where(m > 0, -jnp.inf, masked)

    if aux == "none":
        aux_loss = jnp.zeros((), jnp.float32)
    elif aux == "sequence":
        per_seq = lambda a: a.reshape(sequences, -1, E).mean(axis=1)     # [B, E]
        share = gates / gates.sum(axis=-1, keepdims=True)
        aux_loss = jnp.mean(jnp.sum(
            per_seq(sum(masks)) * (E / k) * per_seq(share), axis=-1))
    else:
        chosen = masks[0] if aux == "first_choice" else sum(masks)
        aux_loss = E * jnp.sum(gates.mean(axis=0) * chosen.mean(axis=0))

    idx = jnp.stack(idxs, axis=1)
    w = jnp.stack(ws, axis=1)
    if normalize_weights and k > 1:
        floor = 1e-9 if score == "softmax" else 1e-20
        w = w / jnp.maximum(w.sum(axis=1, keepdims=True), floor)
    if weight_scale != 1.0:
        w = w * weight_scale
    return idx, w, aux_loss, masks


def topk_gating_compact(logits, k: int = 2, capacity_factor: float = 1.0,
                        min_capacity: int = 4, train: bool = True, rng=None,
                        noise_std: float = 0.0, normalize_weights: bool = True,
                        drop_tokens: bool = True,
                        aux: str = "first_choice") -> GateCompact:
    """logits [S, E] -> GateCompact: the ONE capacity-assignment rule
    (selection, buffer positions, drops, weight renormalization, aux loss).
    ``topk_gating`` densifies this into the GShard einsum contract."""
    import jax
    import jax.numpy as jnp

    S, E = logits.shape
    # weights re-normalize AFTER capacity drops below, so take them raw here
    idx, raw_w, aux_loss, masks = topk_select(
        logits, k, normalize_weights=False, train=train, rng=rng,
        noise_std=noise_std, aux=aux)
    gates = raw_w  # per-choice raw gate probabilities [S, k]

    capacity = compute_capacity(S, E, k, capacity_factor, min_capacity) if drop_tokens else S

    # Position of each token within its expert's buffer, priority: choice
    # order first (all 1st choices beat 2nd choices), token order second.
    locations = []
    running = jnp.zeros((E,), jnp.float32)
    kept_masks = []
    for m in masks:
        loc = jnp.cumsum(m, axis=0) - m + running[None, :]
        running = running + m.sum(axis=0)
        if drop_tokens:
            m = m * (loc < capacity)
        kept_masks.append(m)
        locations.append(loc)

    gate_weights = []
    for j, m in enumerate(kept_masks):
        # raw per-choice probability, zeroed when the slot was dropped
        gate_weights.append(gates[:, j] * m.sum(axis=-1))  # [S]
    if normalize_weights and k > 1:
        denom = sum(gate_weights)
        denom = jnp.maximum(denom, 1e-9)
        gate_weights = [g / denom for g in gate_weights]

    loc_idx = jnp.stack([(loc * m).sum(axis=-1).astype(jnp.int32)
                         for loc, m in zip(locations, kept_masks)], axis=1)
    kept_sk = jnp.stack([m.sum(axis=-1) > 0 for m in kept_masks], axis=1)
    w_sk = jnp.stack(gate_weights, axis=1)

    expert_counts = sum(kept_masks).sum(axis=0)
    kept = sum(m.sum() for m in kept_masks)
    total = sum(m.sum() for m in masks)
    metadata = {"expert_counts": expert_counts, "drop_fraction": 1.0 - kept / jnp.maximum(total, 1.0),
                "capacity": capacity}
    return GateCompact(idx, loc_idx, kept_sk, w_sk, capacity, aux_loss, metadata)


def topk_gating(logits, k: int = 2, capacity_factor: float = 1.0, min_capacity: int = 4,
                train: bool = True, rng=None, noise_std: float = 0.0,
                normalize_weights: bool = True, drop_tokens: bool = True,
                aux: str = "first_choice") -> GateOutput:
    """logits [S, E] -> GateOutput. top1/top2 are k=1/2 (reference dispatch
    table moe/sharded_moe.py:587-678 calls into the same machinery).
    Densifies ``topk_gating_compact`` into the [S, E, C] einsum contract."""
    import jax
    import jax.numpy as jnp

    ca = topk_gating_compact(logits, k=k, capacity_factor=capacity_factor,
                             min_capacity=min_capacity, train=train, rng=rng,
                             noise_std=noise_std,
                             normalize_weights=normalize_weights,
                             drop_tokens=drop_tokens, aux=aux)
    S, E = logits.shape
    combine = jnp.zeros((S, E, ca.capacity), jnp.float32)
    for j in range(k):
        m = jax.nn.one_hot(ca.eidx[:, j], E, dtype=jnp.float32) \
            * ca.kept[:, j, None].astype(jnp.float32)
        loc_oh = jax.nn.one_hot(ca.loc[:, j], ca.capacity, dtype=jnp.float32)
        combine = combine + ca.weights[:, j, None, None] * m[:, :, None] * loc_oh[:, None, :]
    dispatch = combine > 0
    return GateOutput(combine, dispatch, ca.aux_loss, ca.metadata)


def top1_gating(logits, **kw) -> GateOutput:
    return topk_gating(logits, k=1, **kw)


def top2_gating(logits, **kw) -> GateOutput:
    return topk_gating(logits, k=2, **kw)
