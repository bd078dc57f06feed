"""Pipeline parallelism: SPMD microbatch pipeline inside one jitted step.

Capability parity with the reference's pipeline stack (SURVEY.md §2.6 PP,
§3.4): ``PipelineModule`` layer partitioning (``runtime/pipe/module.py:86``),
the instruction-list 1F1B ``TrainSchedule`` (``runtime/pipe/schedule.py:189``),
``PipelineEngine.train_batch`` (``runtime/pipe/engine.py:338``) and the p2p
activation exchange (``runtime/pipe/p2p.py``).

TPU-native design — no host-driven schedule, no p2p process groups:

- Layer partitioning: the model's stacked per-layer params keep their
  leading L dim; the pipeline shards it over the mesh "pipe" axis, so each
  stage owns L/S contiguous layers (the analog of PipelineModule's
  partition_method="uniform").
- The schedule is a ``lax.scan`` over pipeline *ticks* inside the jitted
  train step. Each tick every stage runs its layer block and passes
  activations to the next stage with ``lax.ppermute`` — XLA schedules the
  sends on ICI and overlaps them with compute. The reference's
  SendActivation/RecvActivation instruction pairs (``schedule.py``)
  collapse into that single collective permute.
- The loop runs under a *partial-manual* ``shard_map``: only "pipe" is
  manual; data/fsdp/tensor/expert/seq stay auto, so ZeRO sharding, AutoTP
  matmul sharding and MoE dispatch inside a stage still compile through
  XLA's SPMD partitioner unchanged.
- Backward: ``jax.grad`` through the scan replays ticks in reverse with the
  transposed ppermute — the BackwardPass/SendGrad/RecvGrad instructions of
  the reference schedule, derived instead of hand-written. Activation
  memory is bounded by remat (the model's ``remat`` flag), which is the
  reference's activation-checkpoint interval analog.
- Tied weights (embed used at stage 0, tied unembed at the last stage)
  enter the shard_map replicated over "pipe"; the shard_map transpose
  psums their cotangents — the reference's tied-weight allreduce
  (``runtime/pipe/module.py:454``) by construction.

GPipe vs 1F1B: with everything traced into one XLA program, the
forward/backward interleave is the compiler's scheduling decision; the
tick loop fixes data dependencies only. Bubble fraction is the usual
(S-1)/(n_micro+S-1) — pick micro_batches ≥ 4·stages to amortize.
"""

from __future__ import annotations

from typing import Callable, Optional

from ..config.config_utils import ConfigError
from . import comm


def partition_balanced(weights, n_parts: int):
    """Contiguous partition of ``weights`` into ``n_parts`` minimizing the
    max part weight (reference ``ds_utils.partition_balanced`` used by
    PipelineModule partition_method="parameters"/"type:regex",
    runtime/pipe/module.py:378-398). Returns boundaries [n_parts + 1]."""
    L = len(weights)
    if n_parts <= 0:
        raise ConfigError(f"n_parts must be positive, got {n_parts}")
    prefix = [0]
    for w in weights:
        prefix.append(prefix[-1] + w)

    def parts_needed(cap):
        # greedy: how many contiguous parts with sum <= cap (every single
        # weight must fit — cap >= max(weights) is ensured by the caller)
        parts, cur = 1, 0
        for w in weights:
            if cur + w > cap:
                parts += 1
                cur = w
            else:
                cur += w
        return parts

    lo, hi = max(weights, default=0), prefix[-1]
    while lo < hi:
        mid = (lo + hi) // 2
        if parts_needed(mid) <= n_parts:
            hi = mid
        else:
            lo = mid + 1
    cap = lo
    bounds = [0]
    cur = 0
    for i, w in enumerate(weights):
        # keep enough layers in reserve that every later stage is nonempty
        remaining_stages = n_parts - len(bounds)
        if ((cur + w > cap or L - i <= remaining_stages)
                and cur > 0 and len(bounds) < n_parts):
            bounds.append(i)
            cur = 0
        cur += w
    while len(bounds) < n_parts:
        bounds.append(L)
    bounds.append(L)
    # zero-weight runs (sparse type:regex) can leave trailing stages empty;
    # repair to strictly increasing boundaries (requires L >= n_parts)
    for j in range(1, n_parts):
        bounds[j] = min(max(bounds[j], bounds[j - 1] + 1), L - (n_parts - j))
    return bounds


def pipeline_stage_count(topology=None) -> int:
    from .mesh import get_topology

    topo = topology or get_topology()
    return topo.axis_sizes.get("pipe", 1)


def _stage_ce(model, other_params, outputs, labels):
    """Per-device CE over the pipeline outputs buffer: head + token_loss per
    microbatch via lax.map, summed. The ONE implementation both the
    shard_map'd ``loss`` and the region-transparent ``region_loss`` call —
    any CE change lands in both paths by construction."""
    import jax
    import jax.numpy as jnp

    def one(args):
        o, lb = args
        logits = model.head(other_params, o)
        s, c = model.token_loss(logits, lb)
        return s, c.astype(jnp.float32)

    sums, counts = jax.lax.map(one, (outputs, labels))
    return sums.sum(), counts.sum()


def spmd_pipeline(stage_fn: Callable, x_micro, *, n_stages: int, axis_name: str = "pipe",
                  stage_index=None):
    """Run the microbatch pipeline. Must execute inside shard_map with
    ``axis_name`` manual.

    stage_fn: (h [mb, ...]) -> (h_out [mb, ...], aux scalar) — this stage's
      layer block.
    x_micro: [n_micro, mb, ...] microbatched stage-0 inputs (replicated over
      the pipe axis; only stage 0 reads them).
    stage_index: this device's stage number. Callers inside a PARTIAL-manual
      region thread it as a P(axis_name)-sharded arange operand, not
      ``lax.axis_index`` (which lowers to a PartitionId instruction the SPMD
      partitioner has rejected with auto axes still live).

    Returns (outputs [n_micro, mb, ...] — valid on the LAST stage, zeros
    elsewhere; aux — sum of stage_fn aux over all (stage, microbatch) pairs,
    bubble ticks masked out).
    """
    import jax
    import jax.numpy as jnp

    n_micro = x_micro.shape[0]
    stage = (stage_index if stage_index is not None
             else jax.lax.axis_index(axis_name))
    n_ticks = n_micro + n_stages - 1
    # No wrap-around edge: stage 0 always reads fresh microbatch input, so
    # the (S-1 -> 0) send would be dead traffic (devices with no source
    # receive zeros, which stage 0 never consumes).
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        state, outputs, aux_acc = carry
        idx = jnp.clip(t, 0, n_micro - 1)
        inp = jnp.where(stage == 0,
                        jax.lax.dynamic_index_in_dim(x_micro, idx, 0, keepdims=False),
                        state)
        out, aux = stage_fn(inp)
        # Tick t is a real microbatch for this stage iff stage <= t < stage+n_micro.
        active = (t >= stage) & (t < stage + n_micro)
        aux_acc = aux_acc + jnp.where(active, aux, 0.0)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        write = (stage == n_stages - 1) & (t >= n_stages - 1)
        cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, out, cur), out_idx, 0)
        state = comm.ppermute(out, axis_name, perm)
        return (state, outputs, aux_acc), None

    state0 = jnp.zeros(x_micro.shape[1:], x_micro.dtype)
    outputs0 = jnp.zeros_like(x_micro)
    # The aux carry is [1], not a 0-d scalar: when the aux genuinely
    # participates in the gradient (a mixed-MoE stack's load-balance loss),
    # grad-of-shard_map can save the scan carry as a region residual with a
    # stacked-over-devices spec on dim 0, which a rank-0 residual has not.
    carry0 = (state0, outputs0, jnp.zeros((1,), jnp.float32))
    (state, outputs, aux), _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
    return outputs, aux[0]


class PipelinedModel:
    """Wrap a model-zoo Transformer for pipeline-parallel training.

    Same surface as the wrapped model (``init`` / ``loss`` /
    ``partition_specs``), so the Engine needs no pipeline-specific code —
    the reference's separate PipelineEngine subclass (runtime/pipe/engine.py)
    collapses into a model wrapper because the schedule lives inside the
    jitted step. ``apply``/generation use the wrapped model directly
    (inference uses the non-pipelined path).

    micro_batches plays the role of the reference's gradient accumulation
    steps on the pipeline path (PipelineEngine consumes gas microbatches per
    train_batch — runtime/pipe/engine.py:338).
    """

    def __init__(self, model, n_stages: Optional[int] = None, micro_batches: int = 1,
                 axis_name: str = "pipe", partition_method: str = "uniform"):
        self.model = model
        self.config = model.config
        self.axis_name = axis_name
        self.micro_batches = int(micro_batches)
        self._n_stages = n_stages
        self.partition_method = partition_method
        self._bounds = self._layer_bounds()
        counts = [self._bounds[s + 1] - self._bounds[s]
                  for s in range(self.n_stages)]
        self.stage_size = max(counts)
        # even layout: contiguous equal stages — the stacked dim shards
        # straight over "pipe". Uneven (L % S != 0 or weighted methods):
        # stages pad to the max count with identity-masked rows.
        self._even = (len(set(counts)) == 1
                      and self._bounds == [s * counts[0]
                                           for s in range(self.n_stages + 1)])
        if self.micro_batches < 1:
            raise ConfigError(f"micro_batches must be >= 1, got {self.micro_batches}")

    def _layer_bounds(self):
        """Per-stage layer boundaries (reference PipelineModule
        _partition_layers, runtime/pipe/module.py:378-398):
        "uniform" — balanced layer counts; "parameters" — balanced per-layer
        parameter counts; "type:regex" — balance the count of layers whose
        type name matches the regex (this zoo's scanned layers are typed
        "moe" or "dense" per moe_layer_pattern)."""
        import re

        L, S = self.config.n_layers, self.n_stages
        if S > L:
            raise ConfigError(
                f"pipeline stages {S} > n_layers {L}: at least one stage "
                "would be empty (reference partition_balanced rejects this "
                "too — reduce mesh.pipe)")
        method = (self.partition_method or "uniform").lower()
        if method in ("uniform", "parameters"):
            if method == "parameters":
                # stacked scan layers are homogeneous (same shapes), so
                # per-layer param counts are equal and this reduces to
                # balanced counts — computed anyway for fidelity
                cfg = self.config
                per_layer = (4 * cfg.d_model * cfg.d_model
                             + 3 * cfg.d_model * cfg.ff_dim)
                weights = [per_layer] * L
            else:
                weights = [1] * L
            return partition_balanced(weights, S)
        if method.startswith("type:"):
            pattern = method[len("type:"):]
            mp = self.config.moe_layer_pattern
            types = [("moe" if (self.config.n_experts > 0
                                and (not mp or mp[i % len(mp)]))
                      else "dense") for i in range(L)]
            weights = [1 if re.search(pattern, t) else 0 for t in types]
            if not any(weights):
                raise ConfigError(
                    f"partition_method {self.partition_method!r} matches no "
                    f"layers (types present: {sorted(set(types))})")
            return partition_balanced(weights, S)
        raise ConfigError(
            f"Unknown pipeline partition_method {self.partition_method!r}; "
            "use 'uniform', 'parameters', or 'type:regex'")

    @property
    def n_stages(self) -> int:
        return self._n_stages if self._n_stages is not None else pipeline_stage_count()

    # -- delegation ----------------------------------------------------

    def init(self, rng):
        return self.model.init(rng)

    def apply(self, params, input_ids):
        return self.model.apply(params, input_ids)

    def partition_specs(self, params):
        """Model specs with the stacked-layer leading dim put on "pipe".

        Uneven partitions (padded stages) keep the RAW [L] stacks off the
        pipe axis — L doesn't divide S — and the loss reshards the padded
        [S * stage_size] gather instead; ZeRO still claims a free dim."""
        import jax
        from jax.sharding import PartitionSpec as P

        base = self.model.partition_specs(params)
        if not self._even:
            return base

        def pin_stage_dim(path, spec):
            keys = [getattr(e, "key", getattr(e, "name", None)) for e in path]
            if keys and keys[0] == "layers":
                rest = tuple(spec)[1:] if len(spec) else ()
                return P(self.axis_name, *rest)
            return spec

        return jax.tree_util.tree_map_with_path(pin_stage_dim, base)

    # -- the pipelined loss --------------------------------------------

    def loss(self, params, batch, rng=None):
        """Next-token CE over the pipeline; numerically matches
        ``model.loss`` (up to MoE aux averaging across microbatches)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        model = self.model
        S = self.n_stages
        n_micro = self.micro_batches

        ids = batch["input_ids"]
        if "labels" in batch:
            labels, inputs = batch["labels"], ids
        else:
            labels, inputs = ids[:, 1:], ids[:, :-1]
        B, T = inputs.shape
        if B % n_micro:
            raise ConfigError(f"Batch {B} not divisible by pipeline micro_batches {n_micro}")
        mb = B // n_micro
        inputs = inputs.reshape(n_micro, mb, T)
        labels = labels.reshape(n_micro, mb, T)
        mesh = _current_mesh()
        # Re-constrain params to their model (pipe/tensor) specs before the
        # manual region: any extra ZeRO axis on the masters is all-gathered
        # OUT HERE by XLA (one gather per stage-local stack — the PP analog
        # of the per-stage ZeRO gather), and never reaches the partial-manual
        # shard_map, whose partitioner mishandles such subgroup collectives.
        model_shardings = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), self.partition_specs(params))
        params = jax.tree_util.tree_map(jax.lax.with_sharding_constraint, params, model_shardings)

        layer_params = params["layers"]
        other_params = {k: v for k, v in params.items() if k != "layers"}
        keep_flags = ()
        # each stage's rows carry their GLOBAL layer index so per-layer
        # pattern flags (attention_pattern / moe_layer_pattern / random-LTD)
        # resolve correctly inside the stage (stage-local row numbers would
        # silently pick the wrong flags on stages > 0)
        layer_ids = jnp.arange(self.config.n_layers, dtype=jnp.int32)
        if not self._even:
            # Uneven partition (partition_method="parameters"/"type:regex"
            # or L % S != 0): each stage runs a padded [stage_size] row
            # block (pad rows = zeros, masked to identity by stack_apply's
            # layer_keep), so the manual region still scans an even count.
            S_sz = self.stage_size
            pad_idx, keep = [], []
            L_total = self.config.n_layers
            for s in range(S):
                rows = list(range(self._bounds[s], self._bounds[s + 1]))
                keep += [True] * len(rows) + [False] * (S_sz - len(rows))
                pad_idx += rows + [L_total] * (S_sz - len(rows))
            keep_flags = jnp.asarray(keep)
            layer_ids = jnp.asarray(pad_idx, jnp.int32)
            # pad rows: id == n_layers -> per-layer flags off. Gather the
            # padded [S * stage_size] stack out here and shard it over
            # "pipe" — each device holds only its stage's rows
            def pad_stack(a):
                zero_row = jnp.zeros((1,) + a.shape[1:], a.dtype)
                return jnp.concatenate([a, zero_row])[layer_ids]

            layer_params = jax.tree_util.tree_map(pad_stack, layer_params)
        layer_specs = jax.tree_util.tree_map(lambda _: P(self.axis_name),
                                             layer_params)

        # XLA's partial-manual partitioner CHECK-fails when a convert feeds a
        # replicated (P()) shard_map input whose cotangent must psum over the
        # manual axis in low precision. Route replicated params in at fp32
        # and re-cast inside the manual region (double converts cancel when
        # the engine's bf16 cast sits just outside).
        other_dtypes = jax.tree_util.tree_map(lambda v: v.dtype, other_params)
        other_params = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32) if jnp.issubdtype(v.dtype, jnp.floating) else v,
            other_params)

        def inner(layer_params, keep_flags, layer_ids, stage_ids, other_params,
                  inputs, labels):
            other_params = jax.tree_util.tree_map(
                lambda v, d: v.astype(d), other_params, other_dtypes)
            # this device's stage number, threaded as a P("pipe")-sharded
            # operand (see spmd_pipeline)
            my_stage = stage_ids[0]
            # Embed per microbatch (cheap gather; runs on every stage but
            # only stage 0's result is consumed — its cotangent is zero
            # elsewhere, so tied/embed grads stay correct).
            x, rope = model.embed(other_params, inputs)   # [n_micro, mb, T, D]

            # keep_flags (uneven partitions): pad rows are identity skips
            # via stack_apply's layer_keep masking; the even path passes
            # () so stack_apply keeps its fast unmasked scan body
            keep = keep_flags if not isinstance(keep_flags, tuple) else None

            def stage_fn(h):
                return model.stack_apply(layer_params, h, rope,
                                         layer_keep=keep,
                                         layer_ids=layer_ids)

            outputs, aux = spmd_pipeline(stage_fn, x, n_stages=S,
                                         axis_name=self.axis_name,
                                         stage_index=my_stage)

            stage = my_stage

            sp = _current_mesh().shape.get("seq", 1)
            if sp > 1:
                # seq x pipe (round 5): with an auto "seq" axis live inside
                # this region, the CE contains seq-group collectives; a
                # stage-VARYING lax.cond would run them only on the last
                # stage while its pipe partners move on to the next tick's
                # ppermute — a rendezvous deadlock (observed on the 8-dev
                # CPU mesh). Keep the collective schedule uniform: every
                # stage computes the CE (non-last stages on their zero
                # outputs) and the result is masked. Costs (S-1) wasted
                # head matmuls — the pipeline bubble already dwarfs this.
                nll_all, count_all = _stage_ce(model, other_params,
                                               outputs, labels)
                is_last = (stage == S - 1).astype(jnp.float32)
                nll_sum, count = nll_all * is_last, count_all * is_last
            else:
                nll_sum, count = jax.lax.cond(
                    stage == S - 1,
                    lambda o: _stage_ce(model, other_params, o, labels),
                    lambda o: (jnp.zeros((), jnp.float32),
                               jnp.zeros((), jnp.float32)),
                    outputs)
            # Per-stage partials, reduced OUTSIDE the manual region (the
            # reference broadcasts the aggregated loss from the last stage,
            # runtime/pipe/engine.py:584; here summing the [S] vector is
            # that broadcast — claiming replicated P() output for a psum'd
            # scalar trips XLA's partial-manual partitioner instead).
            return (nll_sum.reshape(1), count.reshape(1), aux.reshape(1))

        from .mesh import shard_map as _shard_map

        stage_ids = jnp.arange(S, dtype=jnp.int32)
        part_spec = P(self.axis_name)
        fn = _shard_map(
            inner, mesh=mesh,
            in_specs=(layer_specs,
                      P() if isinstance(keep_flags, tuple) else P(self.axis_name),
                      P(self.axis_name), P(self.axis_name), P(), P(), P()),
            out_specs=(part_spec, part_spec, part_spec),
            axis_names={self.axis_name}, check_vma=False)
        nll_parts, count_parts, aux_parts = fn(layer_params, keep_flags,
                                               layer_ids, stage_ids,
                                               other_params, inputs, labels)
        nll_sum, count, aux = nll_parts.sum(), count_parts.sum(), aux_parts.sum()
        ce = nll_sum / jnp.maximum(count, 1.0)
        # aux summed layers×micros; dense model sums layers on the full
        # batch, so average over microbatches to keep the coefficient scale.
        return ce + self.config.aux_loss_coef * aux / n_micro

    # -- region-transparent loss (for an ENCLOSING manual region) -------

    def region_loss(self, params, batch, rng, stage):
        """The pipeline CE, written to run INSIDE an enclosing manual region
        that binds {pipe, data, fsdp} (the engine's ZeRO++ wire region —
        runtime/engine.py qg/qz3 pipe paths — wraps exactly this body so the
        gradient reduction can ride the s8 collectives; nesting this class's
        own shard_map there CHECK-fails XLA's partitioner from either
        direction).

        ``params``: model-structured tree whose ``layers`` stacks are THIS
        STAGE's rows ([L/S, ...]; even partitions only) and whose other
        leaves are replicated. ``batch``: this (data, fsdp) shard's batch
        ({"input_ids": [b_local, T]}). ``stage``: this device's stage index
        (thread a P("pipe")-sharded arange — see spmd_pipeline).

        Returns this dp-shard's GLOBAL-pipeline ce (nll/count/aux psum'd
        over "pipe"); the caller owns the (data, fsdp) gradient/loss
        reduction — that is the point of the composition.
        """
        import jax
        import jax.numpy as jnp

        if not self._even:
            raise ConfigError(
                "region_loss (ZeRO++ wire x pipeline) supports even layer "
                "partitions only — L % stages == 0 with "
                "partition_method='uniform'/'parameters'")
        model = self.model
        S = self.n_stages
        n_micro = self.micro_batches
        ids = batch["input_ids"]
        if "labels" in batch:
            labels, inputs = batch["labels"], ids
        else:
            labels, inputs = ids[:, 1:], ids[:, :-1]
        b, T = inputs.shape
        if b % n_micro:
            raise ConfigError(
                f"local batch {b} not divisible by pipeline micro_batches "
                f"{n_micro}")
        mb = b // n_micro
        inputs = inputs.reshape(n_micro, mb, T)
        labels = labels.reshape(n_micro, mb, T)

        layer_params = params["layers"]
        other_params = {k: v for k, v in params.items() if k != "layers"}
        Ls = self.config.n_layers // S
        # global layer ids of this stage's rows (traced stage index is fine:
        # stack_apply's per_layer_flags jnp.takes from a global flag table)
        layer_ids = stage * Ls + jnp.arange(Ls, dtype=jnp.int32)

        x, rope = model.embed(other_params, inputs)

        def stage_fn(h):
            return model.stack_apply(layer_params, h, rope,
                                     layer_ids=layer_ids)

        outputs, aux = spmd_pipeline(stage_fn, x, n_stages=S,
                                     axis_name=self.axis_name,
                                     stage_index=stage)

        # uniform collective schedule (every stage runs the CE, masked) —
        # same rendezvous argument as loss() under a live seq axis
        nll_all, count_all = _stage_ce(model, other_params, outputs, labels)
        is_last = (stage == S - 1).astype(jnp.float32)
        nll_sum = jax.lax.psum(nll_all * is_last, self.axis_name)
        count = jax.lax.psum(count_all * is_last, self.axis_name)
        aux = jax.lax.psum(aux, self.axis_name)
        ce = nll_sum / jnp.maximum(count, 1.0)
        return ce + self.config.aux_loss_coef * aux / n_micro


def _current_mesh():
    from .mesh import get_topology

    return get_topology().mesh
