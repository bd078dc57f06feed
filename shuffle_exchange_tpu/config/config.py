"""The framework config tree — DeepSpeed-JSON compatible.

Capability parity with the reference's ``runtime/config.py`` (DeepSpeedConfig),
``runtime/constants.py`` (keys/defaults), ``runtime/zero/config.py`` and
``runtime/zero/offload_config.py``: the same JSON document a reference user
writes (train_batch_size / fp16 / bf16 / zero_optimization / optimizer /
scheduler / monitor / flops_profiler / comms_logger / elasticity /
activation_checkpointing / checkpoint ...) parses here into one typed tree,
with the same batch-size arithmetic and validation errors.

TPU-first additions live in their own sections and do not collide with
reference keys: ``mesh`` (named-axis device mesh sizes), ``shuffle_exchange``
(the fork's decentralized weight-sync settings, also settable via
``initialize()`` kwargs exactly like the reference fork).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Union

from .config_utils import ConfigError, ConfigModel, config_field
from ..utils.logging import logger

# ---------------------------------------------------------------------------
# Precision (reference: runtime/config.py fp16/bf16 sections, fp16/loss_scaler.py)
# ---------------------------------------------------------------------------


@dataclass
class FP16Config(ConfigModel):
    enabled: bool = config_field(False)
    auto_cast: bool = config_field(False)
    loss_scale: float = config_field(0.0, ge=0.0)  # 0 => dynamic
    initial_scale_power: int = config_field(16, ge=0)
    loss_scale_window: int = config_field(1000, gt=0)
    hysteresis: int = config_field(2, ge=1)
    consecutive_hysteresis: bool = config_field(False)
    min_loss_scale: float = config_field(1.0, ge=0.0)
    fp16_master_weights_and_grads: bool = config_field(False)

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0.0


@dataclass
class BF16Config(ConfigModel):
    enabled: bool = config_field(False, aliases=("bfloat16",))
    # Reference bf16 optimizer accumulates grads in fp32 (bf16_optimizer.py:35).
    immediate_grad_update: bool = config_field(True)


_DTYPE_NAMES = ("fp32", "float32", "fp16", "float16", "bf16", "bfloat16")


@dataclass
class DataTypesConfig(ConfigModel):
    grad_accum_dtype: Optional[str] = config_field(None)  # fp32|fp16|bf16

    def _validate(self, path=""):
        super()._validate(path)
        if self.grad_accum_dtype is not None and self.grad_accum_dtype not in _DTYPE_NAMES:
            raise ConfigError(f"data_types.grad_accum_dtype must be one of {_DTYPE_NAMES}, got {self.grad_accum_dtype!r}")


# ---------------------------------------------------------------------------
# ZeRO (reference: runtime/zero/config.py:86 DeepSpeedZeroConfig)
# ---------------------------------------------------------------------------


@dataclass
class OffloadConfig(ConfigModel):
    """reference: runtime/zero/offload_config.py — device none|cpu|nvme.

    TPU-first additions (no reference-key collisions):

    ``offload_overlap`` turns the cpu tier's host-resident fused-Adam step
    into the overlapped double-buffered pipeline (runtime/zero/overlap.py):
    bucketed grad D2H issued at dispatch, host fused-Adam on a worker
    concurrently with the step's tail, H2D param upload overlapped with the
    next step via delayed parameter application — bit-exact with the
    synchronous path (parity-tested). False keeps the synchronous step.

    ``overlap_bucket_mb`` sizes the transfer buckets (MB of fp32 gradient
    per bucket; 0 = one leaf per bucket). Scanned models stack per-layer
    weights, so leaves are the natural per-layer granularity.
    """

    device: str = config_field("none")
    nvme_path: Optional[str] = config_field(None)
    buffer_count: int = config_field(5, ge=1)
    buffer_size: int = config_field(100_000_000, ge=1)
    max_in_cpu: int = config_field(1_000_000_000, ge=0)
    pin_memory: bool = config_field(False)
    pipeline_read: bool = config_field(False)
    pipeline_write: bool = config_field(False)
    fast_init: bool = config_field(False)
    ratio: float = config_field(1.0, ge=0.0, le=1.0)
    offload_overlap: bool = config_field(False)
    overlap_bucket_mb: int = config_field(128, ge=0)

    @classmethod
    def from_dict(cls, data=None, path=""):
        data = dict(data or {})
        # Legacy boolean shorthand ("cpu_offload": true) means offload-to-CPU.
        if data.pop("enabled", False) and data.get("device", "none") == "none":
            data["device"] = "cpu"
        return super().from_dict(data, path=path)

    def _validate(self, path=""):
        super()._validate(path)
        if self.device not in ("none", "cpu", "nvme"):
            raise ConfigError(f"offload device must be none|cpu|nvme, got {self.device!r}")

    @property
    def enabled(self) -> bool:
        return self.device not in ("none",)


@dataclass
class ZeroConfig(ConfigModel):
    stage: int = config_field(0, ge=0, le=3)
    contiguous_gradients: bool = config_field(True)
    reduce_scatter: bool = config_field(True)
    reduce_bucket_size: int = config_field(500_000_000, ge=0)
    allgather_partitions: bool = config_field(True)
    allgather_bucket_size: int = config_field(500_000_000, ge=0)
    overlap_comm: Optional[bool] = config_field(None)  # default True for stage 3 (ref behavior)
    load_from_fp32_weights: bool = config_field(True)
    elastic_checkpoint: bool = config_field(False)
    offload_param: OffloadConfig = config_field(default_factory=OffloadConfig)
    offload_optimizer: OffloadConfig = config_field(default_factory=OffloadConfig)
    sub_group_size: int = config_field(1_000_000_000, ge=0)
    cpu_offload: Optional[bool] = config_field(None, deprecated=True, new_param="offload_optimizer")
    # stage-3 knobs
    stage3_max_live_parameters: int = config_field(1_000_000_000, ge=0)
    stage3_max_reuse_distance: int = config_field(1_000_000_000, ge=0)
    stage3_prefetch_bucket_size: int = config_field(50_000_000, ge=0)
    stage3_param_persistence_threshold: int = config_field(100_000, ge=0)
    stage3_model_persistence_threshold: int = config_field(9_223_372_036_854_775_807, ge=0)
    stage3_gather_16bit_weights_on_model_save: bool = config_field(False, aliases=("stage3_gather_fp16_weights_on_model_save",))
    stage3_use_all_reduce_for_fetch_params: bool = config_field(False)
    # ZeRO++ (hpZ secondary partition, quantized weights/gradients)
    zero_hpz_partition_size: int = config_field(1, ge=1)
    zero_quantized_weights: bool = config_field(False)
    zero_quantized_nontrainable_weights: bool = config_field(False)
    zero_quantized_gradients: bool = config_field(False)
    # MiCS
    mics_shard_size: int = config_field(-1)
    mics_hierarchical_params_gather: bool = config_field(False)
    memory_efficient_linear: bool = config_field(True)
    round_robin_gradients: bool = config_field(False)
    ignore_unused_parameters: bool = config_field(True)
    legacy_stage1: bool = config_field(False)
    override_module_apply: bool = config_field(True)
    log_trace_cache_warnings: bool = config_field(False)

    def _validate(self, path=""):
        super()._validate(path)
        if self.offload_param.enabled and self.stage != 3:
            logger.warning("offload_param is only effective with ZeRO stage 3; ignoring")

    @property
    def effective_overlap_comm(self) -> bool:
        return self.overlap_comm if self.overlap_comm is not None else (self.stage == 3)


@dataclass
class ZeroPPConfig(ConfigModel):
    """ZeRO++ wire-shaping knobs (TPU-first section; the qwZ/qgZ enables
    stay on ``zero_optimization`` for reference-JSON compatibility).

    ``hierarchical_axes``: ``[intra, inter]`` mesh axis names declaring a
    fast/slow comms split (ICI slice vs DCN). When set, qgZ's gradient
    reduction becomes the two-level schedule: full-precision reduce-scatter
    inside the intra axis (cheap, exact), int8 wire across the inter axis
    (where bytes are the step-time ceiling), full-precision all-gather back
    inside the intra axis — the reference's intra-node/inter-node qgZ
    split (runtime/comm/coalesced_collectives.py:31). Unset = the flat
    schedule: one blockwise-int8 reduction over all ZeRO axes.

    ``bucket_mb``: coalesce gradient leaves into ~this many MB of (logical
    fp32) gradient per wire collective (runtime/zero/buckets.py). Leaves
    are still QUANTIZED per leaf — bucketing changes launch count, never
    rounding — so the bucketed wire is bit-exact with the per-leaf wire.
    0 = one collective per leaf. Autotuner-visible.

    ``group_size``: blockwise-int8 quantization group (elements per scale).
    """

    hierarchical_axes: Optional[List[str]] = config_field(None)
    bucket_mb: int = config_field(32, ge=0)
    group_size: int = config_field(2048, ge=1)

    def _validate(self, path=""):
        super()._validate(path)
        if self.hierarchical_axes is not None:
            axes = list(self.hierarchical_axes)
            if len(axes) != 2 or len(set(axes)) != 2:
                raise ConfigError(
                    "zeropp.hierarchical_axes must name exactly two distinct "
                    f"mesh axes [intra, inter], got {self.hierarchical_axes!r}")
            valid = ("pipe", "data", "fsdp", "expert", "seq", "tensor")
            for ax in axes:
                if ax not in valid:
                    raise ConfigError(
                        f"zeropp.hierarchical_axes: {ax!r} is not a mesh axis "
                        f"(use one of {valid})")


@dataclass
class ContextParallelConfig(ConfigModel):
    """Ring-attention context parallelism (ISSUE 15; SURVEY §2.6's "we may
    add ring attention as the TPU-idiomatic CP", Ring Attention /
    Liu et al. + FPDT §5.7).

    ``degree`` maps onto the mesh "seq" axis (the same axis Ulysses SP
    uses; the two are mutually exclusive owners of it — set one). The
    engine then forces the model's attention onto the RING path: a
    full-manual shard_map region over {data, fsdp, seq} where each chip
    keeps its Q shard and KV blocks rotate around the ring via
    ``ppermute``, accumulating online-softmax partials (running max/sum
    + lse) — per-chip attention memory is O(seq/degree) with
    exact-softmax numerics, and causal rings skip later-source hops
    entirely (~2x; ``lax.cond`` around the hop kernel).

    ``kv_chunk``: the per-hop KV tile (flash-style) for the jnp chunked
    path; the Pallas hop-kernel path tiles itself. ``use_kernel``:
    "auto" routes each hop through the ``flash_attention_lse`` Pallas
    kernel when the shape gate passes, "pallas" forces it (errors
    surface), "xla" keeps the jnp chunked online-softmax.

    Composition: CP x fsdp/data (ZeRO 1-3) and CP x pipe compose. CP x
    the ZeRO++ quantized wire is a ConfigError (the ring's manual region
    cannot nest inside the wire region, nor the other way round), and so
    is CP x pipe x tensor (XLA's partial-manual partitioner CHECK-fails
    on the doubly-nested region with a live tensor axis).

    With ``remat_policy: save_flash_lse`` the ring's per-hop checkpoint
    saves exactly the kernel's own (out, lse) residuals, so the backward
    ring enters the dq/dkv kernels from SAVED lse — the forward kernel
    never re-runs (the PR 3 discipline, now per hop)."""

    degree: int = config_field(1, ge=1)
    kv_chunk: int = config_field(1024, ge=1)
    use_kernel: str = config_field("auto")

    def _validate(self, path=""):
        super()._validate(path)
        if self.use_kernel not in ("auto", "pallas", "xla"):
            raise ConfigError(
                f'context_parallel.use_kernel must be "auto", "pallas" or '
                f'"xla", got {self.use_kernel!r}')


# ---------------------------------------------------------------------------
# Optimizer / scheduler (reference: engine._configure_basic_optimizer, lr_schedules.py)
# ---------------------------------------------------------------------------


@dataclass
class OptimizerConfig(ConfigModel):
    type: str = config_field("AdamW")
    params: Dict[str, Any] = config_field(default_factory=dict)
    legacy_fusion: bool = config_field(False)


@dataclass
class SchedulerConfig(ConfigModel):
    type: Optional[str] = config_field(None)
    params: Dict[str, Any] = config_field(default_factory=dict)


# ---------------------------------------------------------------------------
# Activation checkpointing → remat policy (reference: runtime/activation_checkpointing/config.py)
# ---------------------------------------------------------------------------


@dataclass
class ActivationCheckpointingConfig(ConfigModel):
    partition_activations: bool = config_field(False)
    contiguous_memory_optimization: bool = config_field(False)
    cpu_checkpointing: bool = config_field(False)
    number_checkpoints: Optional[int] = config_field(None)
    synchronize_checkpoint_boundary: bool = config_field(False)
    profile: bool = config_field(False)
    # TPU-first: which jax.checkpoint policy to use when remat is on.
    # Beyond the stock jax policies, the named-seam policies from
    # models/transformer._remat_policy: "offload_kv_host" (KV residuals to
    # host RAM), "save_attn_seams"/"save_ffn" (selective [B,T,*] seams), and
    # "save_flash_lse" (save the flash kernel's OWN residuals — attention
    # output + logsumexp — so backward enters the flash bwd kernel directly
    # instead of re-running forward attention).
    policy: str = config_field("dots_saveable")
    enabled: bool = config_field(False)

    VALID_POLICIES = ("none", "full", "dots_saveable", "nothing_saveable",
                      "dots_with_no_batch_dims_saveable", "offload_kv_host",
                      "save_attn_seams", "save_ffn", "save_flash_lse")

    def _validate(self, path=""):
        super()._validate(path)
        if self.policy not in self.VALID_POLICIES:
            raise ConfigError(
                f"activation_checkpointing.policy must be one of {self.VALID_POLICIES}, got {self.policy!r}")


# ---------------------------------------------------------------------------
# Monitoring / profiling / comms logging (reference: monitor/config.py,
# profiling/config.py, comm/config.py)
# ---------------------------------------------------------------------------


@dataclass
class TensorBoardConfig(ConfigModel):
    enabled: bool = config_field(False)
    output_path: str = config_field("")
    job_name: str = config_field("DeepSpeedJobName")


@dataclass
class CometConfig(ConfigModel):
    # reference monitor/config.py CometConfig: lazy comet_ml experiment
    enabled: bool = config_field(False)
    samples_log_interval: int = config_field(100, gt=0)
    project: Optional[str] = config_field(None)
    workspace: Optional[str] = config_field(None)
    api_key: Optional[str] = config_field(None)
    experiment_name: Optional[str] = config_field(None)
    experiment_key: Optional[str] = config_field(None)
    online: Optional[bool] = config_field(None)
    mode: Optional[str] = config_field(None)


@dataclass
class WandbConfig(ConfigModel):
    enabled: bool = config_field(False)
    group: Optional[str] = config_field(None)
    team: Optional[str] = config_field(None)
    project: str = config_field("deepspeed")


@dataclass
class CSVConfig(ConfigModel):
    enabled: bool = config_field(False)
    output_path: str = config_field("")
    job_name: str = config_field("DeepSpeedJobName")


@dataclass
class FlopsProfilerConfig(ConfigModel):
    enabled: bool = config_field(False)
    recompute_fwd_factor: float = config_field(0.0, ge=0.0)
    profile_step: int = config_field(1, ge=1)
    module_depth: int = config_field(-1)
    top_modules: int = config_field(1, ge=1)
    detailed: bool = config_field(True)
    output_file: Optional[str] = config_field(None)


@dataclass
class AutotuningConfig(ConfigModel):
    """Reference parity: ``autotuning/config.py`` (DeepSpeed autotuner JSON
    section). Our tuner searches micro-batch size / gradient accumulation /
    ZeRO stage / remat policy and emits the measured-best config
    (autotuning/autotuner.py)."""

    enabled: bool = config_field(False)
    results_dir: str = config_field("autotuning_results")
    exps_dir: str = config_field("autotuning_exps")
    overwrite: bool = config_field(True)
    metric: str = config_field("throughput")  # throughput | latency | flops
    fast: bool = config_field(True)
    start_profile_step: int = config_field(3, ge=0)
    end_profile_step: int = config_field(5, ge=1)
    tuner_type: str = config_field("model_based")  # model_based | gridsearch | random
    tuner_early_stopping: int = config_field(5, ge=0)
    tuner_num_trials: int = config_field(50, ge=1)
    max_train_batch_size: Optional[int] = config_field(None, gt=0)
    min_train_micro_batch_size_per_gpu: int = config_field(1, ge=1)
    max_train_micro_batch_size_per_gpu: Optional[int] = config_field(None, gt=0)
    num_tuning_micro_batch_sizes: int = config_field(3, ge=1)
    mp_size: int = config_field(1, ge=1)
    arg_mappings: Dict[str, Any] = config_field(default_factory=dict)


@dataclass
class CommsLoggerConfig(ConfigModel):
    enabled: bool = config_field(False)
    verbose: bool = config_field(False)
    prof_all: bool = config_field(True)
    debug: bool = config_field(False)
    prof_ops: List[str] = config_field(default_factory=list)


# ---------------------------------------------------------------------------
# Elasticity (reference: elasticity/config.py:28)
# ---------------------------------------------------------------------------


@dataclass
class ElasticityConfig(ConfigModel):
    enabled: bool = config_field(False)
    max_train_batch_size: int = config_field(2000, ge=1)
    micro_batch_sizes: List[int] = config_field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = config_field(1, ge=1)
    max_gpus: int = config_field(10000, ge=1)
    min_time: int = config_field(0, ge=0)
    ignore_non_elastic_batch_info: bool = config_field(False)
    prefer_larger_batch: bool = config_field(True)
    version: float = config_field(0.2)


# ---------------------------------------------------------------------------
# Checkpoint behavior (reference: runtime/config.py checkpoint/data-parallel writes)
# ---------------------------------------------------------------------------


@dataclass
class ParallelWriteConfig(ConfigModel):
    pipeline_stage: bool = config_field(False)


@dataclass
class CheckpointConfig(ConfigModel):
    tag_validation: str = config_field("Warn")  # Ignore|Warn|Fail
    load_universal: bool = config_field(False)
    use_node_local_storage: bool = config_field(False)
    parallel_write: ParallelWriteConfig = config_field(default_factory=ParallelWriteConfig)
    writer: str = config_field("torch")  # torch|fast|decoupled (engine selection parity)
    async_save: bool = config_field(False)

    def _validate(self, path=""):
        super()._validate(path)
        if self.tag_validation not in ("Ignore", "Warn", "Fail"):
            raise ConfigError(f"checkpoint.tag_validation must be Ignore|Warn|Fail, got {self.tag_validation!r}")


# ---------------------------------------------------------------------------
# Resilience: preemption-safe saves, crash recovery, runtime guards
# (failure-recovery literature: Gemini SOSP'23, Bamboo NSDI'23 — the
# save-path atomicity + fast-restore loop is the core of training resilience)
# ---------------------------------------------------------------------------


@dataclass
class ResilienceConfig(ConfigModel):
    """Knobs for the resilience layer (runtime/resilience.py).

    ``preemption_save``: install a SIGTERM hook that runs one final
    synchronous ``save_checkpoint`` before exit (preemptible TPU pods send
    SIGTERM ahead of reclaim). The hook arms itself once the engine knows a
    checkpoint directory — ``save_dir`` here, or the first save/load's dir.

    ``keep_last_n``: checkpoint GC after each committed save — keep the N
    newest fully-committed tags; the tag ``latest`` points at is never
    deleted, and staging leftovers from crashed saves are swept. 0 keeps all.

    ``nonfinite_policy``: what the train step does when the loss or grad
    norm comes out non-finite (beyond the fp16 overflow skip):
      - ``skip``     — drop the update in-graph (free: no host sync);
      - ``rollback`` — restore the last committed checkpoint in place
                       (raises if no checkpoint exists yet, or if a second
                       rollback fires with no progress since the first);
      - ``raise``    — raise NonFiniteLossError (an ElasticAgent above can
                       restart the worker);
      - ``off``      — reference behavior: the bad update is applied.

    ``watchdog_timeout_s``: per-step watchdog; a step exceeding it is
    flagged through the monitor (``resilience/hung_steps``). 0 disables.
    """

    preemption_save: bool = config_field(True)
    save_dir: Optional[str] = config_field(None)
    keep_last_n: int = config_field(0, ge=0)
    nonfinite_policy: str = config_field("skip")
    watchdog_timeout_s: float = config_field(0.0, ge=0.0)

    def _validate(self, path=""):
        super()._validate(path)
        if self.nonfinite_policy not in ("off", "skip", "rollback", "raise"):
            raise ConfigError(
                "resilience.nonfinite_policy must be off|skip|rollback|raise, "
                f"got {self.nonfinite_policy!r}")


# ---------------------------------------------------------------------------
# Fork section: Shuffle-exchange decentralized weight sync (reference §2.1,
# stage_1_and_2.py:163-241; also settable via initialize() kwargs)
# ---------------------------------------------------------------------------


@dataclass
class PLDConfig(ConfigModel):
    """Progressive layer drop (reference runtime/progressive_layer_drop.py:10
    + constants.py:405 "progressive_layer_drop" section: theta/gamma)."""

    enabled: bool = config_field(False)
    theta: float = config_field(0.5, gt=0.0, le=1.0)
    gamma: float = config_field(0.001, ge=0.0)


@dataclass
class LoRASectionConfig(ConfigModel):
    """LoRA / OptimizedLinear section (reference ``deepspeed/linear``:
    ``LoRAConfig`` + ``QuantizationConfig``, linear/config.py:13,39 — a
    python-API config there; exposed here additionally as a DS-JSON
    section so the engine can own the split/merge wiring).

    ``quantize_base`` stores the frozen base weights int8/int4 grouped
    (QuantizedParameter analog); ``base_weight_sharding > 1`` shards the
    frozen base over the ZeRO world even at stage < 3 (reference
    base_weight_sharding; 0/1 = follow the ZeRO stage).

    ``ensemble_factor_mixing`` (default False) gates the LoRA x
    shuffle_exchange composition: the decentralized ensemble mixes the
    bit16 trainable tensors per-tensor, and with LoRA those ARE the rank-r
    factor pairs — consensus happens in FACTOR space, which is NOT
    equivalent to mixing the effective weights (``mix(A) @ mix(B) !=
    mix(A @ B)``, the same bias FedAvg-style LoRA averaging carries). The
    reference runs exactly this (stage_1_and_2.py:2231 averages whatever
    trainable partitions the optimizer holds), so the composition is
    available — but only behind this explicit opt-in; by default the
    combination raises a ``ConfigError`` so nobody gets biased
    factor-space consensus from a config that used to hard-fail
    (ADVICE r5 #5).
    """

    enabled: bool = config_field(False)
    lora_r: int = config_field(64, ge=1, aliases=("r",))
    lora_alpha: float = config_field(16.0, aliases=("alpha",))
    base_weight_sharding: int = config_field(1, ge=0)
    offload: bool = config_field(False)
    offload_ratio: float = config_field(0.0, ge=0.0, le=1.0)
    delay_lora_init: bool = config_field(False)
    target_mods: List[str] = config_field(default_factory=list)
    quantize_base: bool = config_field(False)
    q_bits: int = config_field(8)
    group_size: int = config_field(512, ge=1)
    ensemble_factor_mixing: bool = config_field(False)

    def _validate(self, path=""):
        super()._validate(path)
        if not self.enabled:
            return  # a disabled section carries no constraints
        if self.q_bits not in (4, 8):
            raise ConfigError(f"lora.q_bits must be 4 or 8, got {self.q_bits}")
        if self.delay_lora_init:
            raise ConfigError(
                "lora.delay_lora_init is a torch-module-lifecycle knob "
                "(reference optimized_linear.py:117); params here are "
                "explicit pytrees, so the factors always exist at "
                "initialize() time — drop the flag")


@dataclass
class ShuffleExchangeConfig(ConfigModel):
    method: str = config_field("RR")  # RR | shuffle | H-RR | Gossip
    rings: int = config_field(8, ge=1)
    shuffle_step: int = config_field(50, ge=1)
    slice_count: int = config_field(2, ge=1)
    # Gossip mixing weight; reference uses alpha = 1/world_size (stage_1_and_2.py:199)
    gossip_alpha: Optional[float] = config_field(None)
    gossip_prob: float = config_field(1.0, ge=0.0, le=1.0)
    enabled: bool = config_field(False)

    def _validate(self, path=""):
        super()._validate(path)
        if self.method not in ("RR", "shuffle", "H-RR", "Gossip"):
            raise ConfigError(f"shuffle_exchange.method must be RR|shuffle|H-RR|Gossip, got {self.method!r}")


# ---------------------------------------------------------------------------
# TPU-first: named-axis mesh configuration
# ---------------------------------------------------------------------------


@dataclass
class MeshConfig(ConfigModel):
    """Sizes of the named mesh axes. -1 on `data` means "absorb remaining devices".

    Axis order is the physical layout order (ICI-contiguous innermost-last):
    (pipe, data, fsdp, expert, seq, tensor).
    """

    data: int = config_field(-1)
    fsdp: int = config_field(1, ge=1)
    tensor: int = config_field(1, ge=1)
    expert: int = config_field(1, ge=1)
    seq: int = config_field(1, ge=1)
    pipe: int = config_field(1, ge=1)


@dataclass
class TensorParallelConfig(ConfigModel):
    autotp_size: int = config_field(0, ge=0)
    tp_size: int = config_field(1, ge=1)
    tp_grain_size: int = config_field(64, ge=1)


@dataclass
class PipelineParallelConfig(ConfigModel):
    """Pipeline section (reference: PipelineModule kwargs + config
    "pipeline" keys, runtime/pipe/module.py:86, runtime/config.py).

    stages=0 reads the mesh "pipe" axis; micro_batches=0 uses
    gradient_accumulation_steps (the reference equivalence: PipelineEngine
    consumes gas microbatches per train_batch, runtime/pipe/engine.py:338).
    """

    stages: int = config_field(0, ge=0)
    micro_batches: int = config_field(0, ge=0)
    partition_method: str = config_field("uniform", aliases=("partition",))
    activation_checkpoint_interval: int = config_field(0, ge=0)
    seed_layers: bool = config_field(False)
    pipe_partitioned: bool = config_field(True)
    grad_partitioned: bool = config_field(True)


# ---------------------------------------------------------------------------
# Root config
# ---------------------------------------------------------------------------

TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"


@dataclass
class SXConfig(ConfigModel):
    """Root config. Construct via ``SXConfig.load(path_or_dict, world_size)``."""

    train_batch_size: Optional[int] = config_field(None, gt=0)
    train_micro_batch_size_per_gpu: Optional[int] = config_field(None, gt=0)
    gradient_accumulation_steps: Optional[int] = config_field(None, gt=0)
    steps_per_print: int = config_field(10, gt=0)
    wall_clock_breakdown: bool = config_field(False)
    dump_state: bool = config_field(False)
    prescale_gradients: bool = config_field(False)
    gradient_predivide_factor: float = config_field(1.0, gt=0.0)
    gradient_clipping: float = config_field(0.0, ge=0.0)
    sparse_gradients: bool = config_field(False)
    memory_breakdown: bool = config_field(False)
    seed: int = config_field(1234)
    communication_data_type: Optional[str] = config_field(None)
    disable_allgather: bool = config_field(False)
    zero_allow_untested_optimizer: bool = config_field(True)
    zero_force_ds_cpu_optimizer: bool = config_field(True)
    graph_harvesting: bool = config_field(False)

    fp16: FP16Config = config_field(default_factory=FP16Config)
    bf16: BF16Config = config_field(default_factory=BF16Config, aliases=("bfloat16",))
    data_types: DataTypesConfig = config_field(default_factory=DataTypesConfig)
    zero_optimization: ZeroConfig = config_field(default_factory=ZeroConfig)
    zeropp: ZeroPPConfig = config_field(default_factory=ZeroPPConfig)
    # None (absent section or explicit null) means "client supplies the
    # optimizer", exactly like the reference's initialize(optimizer=...).
    optimizer: Optional[OptimizerConfig] = config_field(None, model=OptimizerConfig)
    scheduler: SchedulerConfig = config_field(default_factory=SchedulerConfig)
    activation_checkpointing: ActivationCheckpointingConfig = config_field(default_factory=ActivationCheckpointingConfig)

    tensorboard: TensorBoardConfig = config_field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = config_field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = config_field(default_factory=CSVConfig)
    comet: CometConfig = config_field(default_factory=CometConfig)
    flops_profiler: FlopsProfilerConfig = config_field(default_factory=FlopsProfilerConfig)
    comms_logger: CommsLoggerConfig = config_field(default_factory=CommsLoggerConfig)
    elasticity: ElasticityConfig = config_field(default_factory=ElasticityConfig)
    checkpoint: CheckpointConfig = config_field(default_factory=CheckpointConfig)
    resilience: ResilienceConfig = config_field(default_factory=ResilienceConfig)

    lora: LoRASectionConfig = config_field(default_factory=LoRASectionConfig,
                                           aliases=("optimized_linear",))
    progressive_layer_drop: PLDConfig = config_field(default_factory=PLDConfig)
    shuffle_exchange: ShuffleExchangeConfig = config_field(default_factory=ShuffleExchangeConfig)
    mesh: MeshConfig = config_field(default_factory=MeshConfig)
    tensor_parallel: TensorParallelConfig = config_field(default_factory=TensorParallelConfig, aliases=("autotp",))
    sequence_parallel_size: int = config_field(1, ge=1)
    pipeline_parallel_size: int = config_field(1, ge=1)
    context_parallel: ContextParallelConfig = config_field(default_factory=ContextParallelConfig)

    autotuning: AutotuningConfig = config_field(default_factory=AutotuningConfig)

    # Accepted-but-gated sections (feature handled elsewhere or N/A on TPU).
    compression_training: Dict[str, Any] = config_field(default_factory=dict)
    data_efficiency: Dict[str, Any] = config_field(default_factory=dict)
    curriculum_learning: Dict[str, Any] = config_field(default_factory=dict)
    pipeline: PipelineParallelConfig = config_field(default_factory=PipelineParallelConfig)
    hybrid_engine: Dict[str, Any] = config_field(default_factory=dict)
    amp: Dict[str, Any] = config_field(default_factory=dict)
    aio: Dict[str, Any] = config_field(default_factory=dict)
    nebula: Dict[str, Any] = config_field(default_factory=dict)
    compile: Dict[str, Any] = config_field(default_factory=dict)
    timers: Dict[str, Any] = config_field(default_factory=dict)

    # ------------------------------------------------------------------
    # Loading & batch arithmetic (reference: runtime/config.py:93 + engine sanity checks)
    # ------------------------------------------------------------------

    @classmethod
    def load(cls, config: Union[str, os.PathLike, Dict[str, Any], None], world_size: int = 1) -> "SXConfig":
        if config is None:
            config = {}
        if isinstance(config, (str, os.PathLike)):
            if not os.path.exists(config):
                raise ConfigError(f"Config file not found: {config}")
            with open(config) as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise ConfigError(f"Expected config dict or path, got {type(config).__name__}")
        obj = cls.from_dict(config)
        obj._map_parallel_sizes()
        if obj.elasticity.enabled:
            obj._apply_elastic_plan(world_size)
        obj._resolve_batch_sizes(world_size)
        obj._sanity_check()
        return obj

    def _map_parallel_sizes(self) -> None:
        """Size-style parallelism knobs (reference tp_size / sp size /
        pipeline stages) map onto mesh axes left at default."""
        def merge(axis: str, knob_name: str, value: int) -> None:
            current = getattr(self.mesh, axis)
            if value > 1 and current == 1:
                setattr(self.mesh, axis, value)
            elif value > 1 and current != value:
                raise ConfigError(
                    f"conflicting parallelism config: {knob_name}={value} but "
                    f"mesh.{axis}={current}; set one or make them agree")

        merge("pipe", "pipeline.stages", self.pipeline.stages)
        merge("pipe", "pipeline_parallel_size", self.pipeline_parallel_size)
        if (self.context_parallel.degree > 1
                and self.sequence_parallel_size > 1):
            # both claim the "seq" axis with DIFFERENT attention shapes
            # (ring KV rotation vs Ulysses a2a) — one owner only
            raise ConfigError(
                f"context_parallel.degree={self.context_parallel.degree} and "
                f"sequence_parallel_size={self.sequence_parallel_size} both "
                f"claim the mesh 'seq' axis; set exactly one (ring CP and "
                f"Ulysses SP are alternative attention shapes over the same "
                f"axis)")
        merge("seq", "sequence_parallel_size", self.sequence_parallel_size)
        merge("seq", "context_parallel.degree", self.context_parallel.degree)
        merge("tensor", "tensor_parallel.tp_size", self.tensor_parallel.tp_size)

    @property
    def model_parallel_size(self) -> int:
        """Axes that do NOT consume batch: pipe × tensor × seq × expert."""
        return max(1, self.mesh.pipe * self.mesh.tensor * self.mesh.seq * self.mesh.expert)

    def _apply_elastic_plan(self, world_size: int) -> None:
        """Elasticity overrides user batch config (reference: runtime/config.py
        elasticity handling — explicit batch keys are an error unless
        ignore_non_elastic_batch_info, and the plan must admit world_size)."""
        from ..runtime.elasticity import get_best_candidates

        has_batch_info = any(v is not None for v in (
            self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps))
        if has_batch_info and not self.elasticity.ignore_non_elastic_batch_info:
            raise ConfigError(
                "Elasticity is enabled, but the config contains batch parameters "
                f"({TRAIN_BATCH_SIZE}/{TRAIN_MICRO_BATCH_SIZE_PER_GPU}/{GRADIENT_ACCUMULATION_STEPS}). "
                "Remove them or set elasticity.ignore_non_elastic_batch_info")
        batch, micro, gas = get_best_candidates(self.elasticity, max(1, world_size))
        self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps = batch, micro, gas

    def _resolve_batch_sizes(self, world_size: int) -> None:
        """train = micro × gas × dp_world; infer any single missing value.

        Mirrors the reference's DeepSpeedConfig._configure_train_batch_size /
        _batch_assertion (runtime/config.py).
        """
        self.world_size = max(1, world_size)
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        # The batch splits over the data-parallel world only — devices on
        # pipe/tensor/seq/expert axes see the same samples (reference:
        # dp_world = world // (pp * mp), runtime/config.py batch arithmetic).
        if self.world_size % self.model_parallel_size:
            raise ConfigError(
                f"World size {self.world_size} not divisible by model-parallel axes "
                f"product {self.model_parallel_size} (mesh={self.mesh.to_dict()})")
        ws = max(1, self.world_size // self.model_parallel_size)
        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * ws)
        elif train is not None and gas is not None:
            micro = train // (gas * ws)
        elif micro is not None:
            gas = gas or 1
            train = micro * gas * ws
        elif train is not None:
            gas = 1
            micro = train // ws
        else:
            raise ConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu needs to be provided")
        self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps = train, micro, gas
        if train <= 0 or micro <= 0 or gas <= 0:
            raise ConfigError(f"Batch sizes must be >0: train={train} micro={micro} gas={gas}")
        if train != micro * gas * ws:
            raise ConfigError(
                f"Check batch related parameters. train_batch_size is not equal to micro_batch_per_gpu * "
                f"gradient_acc_step * world_size {train} != {micro} * {gas} * {ws}")

    def _sanity_check(self) -> None:
        if self.fp16.enabled and self.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        if (self.zeropp.hierarchical_axes is not None
                and not self.zero_optimization.zero_quantized_gradients):
            # the two-level schedule only shapes the qgZ gradient wire —
            # without the flag the declaration is inert; say so instead of
            # letting the user believe the split is active
            logger.warning(
                "zeropp.hierarchical_axes is set but "
                "zero_optimization.zero_quantized_gradients is off — the "
                "two-level schedule shapes the qgZ gradient wire only and "
                "has no effect in this config")
        if self.zero_optimization.stage >= 2 and self.fp16.enabled and self.fp16.fp16_master_weights_and_grads \
                and not self.zero_optimization.offload_optimizer.enabled:
            raise ConfigError("fp16_master_weights_and_grads requires optimizer offload with ZeRO-2")
        # Elasticity was already planned + world-size-validated in
        # _apply_elastic_plan; only the version gate remains here.
        if self.elasticity.enabled and self.elasticity.version not in (0.1, 0.2):
            raise ConfigError(f"Unsupported elasticity version {self.elasticity.version}")

    # ------------------------------------------------------------------

    @property
    def train_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    @property
    def grad_accum_dtype(self):
        import jax.numpy as jnp

        name = self.data_types.grad_accum_dtype
        if name is None:
            return jnp.float32
        return {"fp32": jnp.float32, "float32": jnp.float32, "fp16": jnp.float16,
                "float16": jnp.float16, "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}[name]

    def print_config(self) -> None:
        logger.info("SXConfig:\n" + self.dump())
