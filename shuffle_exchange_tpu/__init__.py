"""shuffle_exchange_tpu — a TPU-native training/inference framework.

A ground-up JAX/XLA/Pallas re-design with the capabilities of the reference
DeepSpeed fork "Shuffle-exchange" (see SURVEY.md): ``initialize`` returns an
engine with forward/backward/step semantics, ZeRO-style memory partitioning
becomes mesh sharding policy, and the fork's decentralized weight-sync
methods (RR / shuffle / H-RR / Gossip) are first-class
(``deepspeed/__init__.py:69-85`` is the API being mirrored).
"""

from __future__ import annotations

import time as _time

_import_began = _time.perf_counter()

from typing import Any, Callable, Optional

__version__ = "0.1.0"
__version_major__, __version_minor__, __version_patch__ = 0, 1, 0

from .profiling import trace as _trace

# the package's own import is the first phase of start-up (profiling/trace.py)
with _trace.phase("init/import", t0=_import_began):
    from . import zero  # noqa: F401  (reference deepspeed.zero surface: Init, GatheredParameters)
    from .config import SXConfig, ConfigError
    from .parallel import comm  # noqa: F401  (dist facade: sxt.comm.psum etc.)
    from .parallel.mesh import (MeshTopology, get_topology, initialize_topology,
                                topology_is_initialized)

# Reference exposes `deepspeed.dist` after init; our facade is importable always.
dist = comm


def initialize(
    args=None,
    model: Any = None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    distributed_port: int = 29500,
    mpu=None,
    dist_init_required: Optional[bool] = None,
    collate_fn=None,
    config=None,
    mesh_param=None,
    config_params=None,
    # fork kwargs (reference deepspeed/__init__.py:82-85)
    shuffle_step: Optional[int] = None,
    rings: Optional[int] = None,
    method: Optional[str] = None,
    slice_count: Optional[int] = None,
    # TPU-native extras
    loss_fn: Optional[Callable] = None,
    params: Any = None,
    seed: int = 0,
):
    """Initialize the engine. Returns (engine, optimizer, dataloader, lr_scheduler).

    ``model`` may be:
      - an object with ``init(rng) -> params`` and ``loss(params, batch, rng)``
        (our model zoo), optionally ``partition_specs(params)``;
      - a params pytree, with ``loss_fn`` passed separately;
      - None, with ``params`` + ``loss_fn`` passed explicitly.

    ``config`` is a dict or JSON path in the reference's format. The fork
    kwargs mirror ``deepspeed.initialize(..., shuffle_step, rings, method,
    slice_count)``: passing ``method`` enables decentralized sync, and
    ``slice_count`` sets the fsdp (slice-group) axis size when the config's
    mesh section didn't.
    """
    import jax

    from .runtime.engine import Engine

    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and getattr(args, "deepspeed_config", None) is not None:
        config = args.deepspeed_config

    # the backend's first touch, the config as loaded for this world, the mesh
    with _trace.phase("init/config"):
        n_devices = len(jax.devices())
        comm.init_distributed(dist_init_required=dist_init_required)

        cfg = SXConfig.load(config, world_size=n_devices)

        # Fork kwargs override/enable the shuffle_exchange config section.
        if method is not None:
            cfg.shuffle_exchange.method = method
            cfg.shuffle_exchange.enabled = True
        if shuffle_step is not None:
            cfg.shuffle_exchange.shuffle_step = int(shuffle_step)
            cfg.shuffle_exchange.enabled = True
        if rings is not None:
            cfg.shuffle_exchange.rings = int(rings)
            cfg.shuffle_exchange.enabled = True
        if slice_count is not None:
            cfg.shuffle_exchange.slice_count = int(slice_count)
        cfg.shuffle_exchange._validate()
        if cfg.shuffle_exchange.enabled:
            sc = cfg.shuffle_exchange.slice_count
            if n_devices % sc:
                raise ConfigError(f"slice_count {sc} must divide device count {n_devices} "
                                  "(reference: 'slice_count cannot be divided by real world size')")
            # slice group = fsdp axis; logical nodes = data axis.
            if cfg.mesh.fsdp == 1:
                cfg.mesh.fsdp = sc
                cfg.mesh.data = -1

        # ZeRO++ hpZ / MiCS: both express "shard over a small fast group,
        # replicate across groups" (reference zero_hpz_partition_size /
        # mics_shard_size, runtime/zero/config.py + mics.py). On the mesh this is
        # an fsdp axis of the group size with the remaining DP factor on data —
        # param all-gathers then ride the (ICI-contiguous) fsdp axis only.
        z = cfg.zero_optimization
        group = None
        if z.mics_shard_size and z.mics_shard_size > 0:
            group = z.mics_shard_size
        elif z.stage == 3 and z.zero_hpz_partition_size > 1:
            group = z.zero_hpz_partition_size
        if group is not None and cfg.mesh.fsdp == 1:
            if n_devices % group:
                raise ConfigError(f"hpZ/MiCS shard group {group} must divide "
                                  f"device count {n_devices}")
            cfg.mesh.fsdp = group
            cfg.mesh.data = -1

        topology = initialize_topology(cfg.mesh, force=True)

    # Context parallelism (ISSUE 15): ``context_parallel.degree`` maps
    # onto the mesh "seq" axis (config._map_parallel_sizes) and ring
    # attention is the one CP attention shape — route zoo models onto it
    # here, carrying the section's kv_chunk/use_kernel knobs into the
    # model config the attention region reads.
    if cfg.context_parallel.degree > 1:
        tcfg = getattr(model, "config", None)
        if tcfg is not None and hasattr(tcfg, "sp_attention"):
            import dataclasses as _dc

            model.config = _dc.replace(
                tcfg, sp_attention="ring",
                cp_kv_chunk=cfg.context_parallel.kv_chunk,
                cp_use_kernel=cfg.context_parallel.use_kernel)
        else:
            from .utils.logging import logger

            logger.warning(
                "context_parallel.degree=%d but the model exposes no "
                "sp_attention config — the seq axis will shard activations "
                "without ring attention (zoo Transformer models route "
                "automatically)", cfg.context_parallel.degree)

    # Activation checkpointing: the section's ``enabled`` / ``policy`` are the
    # zoo model's per-layer remat (``TransformerConfig.remat`` /
    # ``remat_policy``; models/transformer._remat_policy names the policies).
    # A model built with remat on keeps its own policy.
    ac = cfg.activation_checkpointing
    tcfg = getattr(model, "config", None)
    if ac.enabled and tcfg is not None and hasattr(tcfg, "remat") and not tcfg.remat:
        import dataclasses as _dc

        model.config = _dc.replace(tcfg, remat=True, remat_policy=ac.policy)

    # Pipeline parallelism: wrap zoo models so the 1F1B microbatch loop runs
    # inside the jitted step (the reference's PipelineEngine path,
    # runtime/pipe/engine.py:338 — here a model wrapper, see parallel/pipeline.py).
    if topology.axis_sizes.get("pipe", 1) > 1:
        from .parallel.pipeline import PipelinedModel

        if isinstance(model, PipelinedModel):
            pass
        elif model is not None and hasattr(model, "stack_apply"):
            n_micro = cfg.pipeline.micro_batches or cfg.gradient_accumulation_steps
            model = PipelinedModel(model, n_stages=topology.axis_sizes["pipe"],
                                   micro_batches=n_micro,
                                   partition_method=cfg.pipeline.partition_method)
            # Microbatching moves inside the pipeline; the engine sees one
            # macro batch per step. Keep train = micro * gas * dp consistent.
            cfg.pipeline.micro_batches = n_micro
            cfg.gradient_accumulation_steps = 1
            dp = max(1, cfg.world_size // cfg.model_parallel_size)
            cfg.train_micro_batch_size_per_gpu = cfg.train_batch_size // dp
        else:
            from .utils.logging import logger

            logger.warning(
                "mesh.pipe=%d but the model does not expose stack_apply — the pipe "
                "axis will only replicate compute. Wrap your loss in "
                "parallel.PipelinedModel (or use a model-zoo Transformer) for real "
                "pipeline parallelism.", topology.axis_sizes["pipe"])

    # Random-LTD needs BOTH the schedule (engine config) and the model flag
    # (Transformer random_ltd) — catch the silent half-configured case.
    de = dict(cfg.data_efficiency or {})
    ltd_on = dict(de.get("data_routing", {}).get("random_ltd", {})).get("enabled", False)
    if ltd_on and hasattr(model, "config") and not getattr(model.config, "random_ltd", False):
        from .utils.logging import logger

        logger.warning(
            "data_efficiency.data_routing.random_ltd is enabled but the model was built "
            "with random_ltd=False — no tokens will be dropped. Set "
            "TransformerConfig(random_ltd=True) to activate it.")

    # Resolve model/params/loss. When the model exposes init() and no
    # concrete params were passed, initialization is DEFERRED (zero.Init
    # analog, reference runtime/zero/partition_parameters.py:879): the
    # engine traces init under jit with sharded outputs, so the full model
    # is never materialized unsharded — bring-up peaks at O(shard).
    resolved_params = params
    params_init_fn = None
    partition_specs = None
    if model is not None and hasattr(model, "loss"):
        if resolved_params is None:
            params_init_fn = model.init
            resolved_params = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
        loss_fn = loss_fn or model.loss
        if hasattr(model, "partition_specs"):
            partition_specs = model.partition_specs(resolved_params)
    elif model is not None and loss_fn is not None and resolved_params is None:
        resolved_params = model  # model positional arg was actually a params pytree
    if resolved_params is None or loss_fn is None:
        raise ConfigError("initialize() needs a model object (init+loss) or params + loss_fn")

    engine = Engine(
        config=cfg,
        topology=topology,
        loss_fn=loss_fn,
        params=resolved_params,
        params_init_fn=params_init_fn,
        optimizer=optimizer,
        lr_scheduler=lr_scheduler,
        model_partition_specs=partition_specs,
        training_data=training_data,
        collate_fn=collate_fn,
        seed=seed,
    )

    if model is not None and hasattr(model, "loss"):
        # reference engine.module is the wrapped nn.Module; expose the model
        # object the same way (engine.module.config etc.)
        engine.module = model

    # RLHF hybrid engine (reference runtime/hybrid_engine.py:30, selected by
    # the hybrid_engine config section): wrap so generate() runs rollouts
    # through the paged serving fleet on the current consensus weights
    # (the v1 class is a shim over rlhf.HybridEngineV2 since ISSUE 11).
    if dict(cfg.hybrid_engine or {}).get("enabled", False):
        from .runtime.hybrid_engine import HybridEngine

        if model is None or not hasattr(model, "head"):
            raise ConfigError("hybrid_engine.enabled requires a model-zoo "
                              "Transformer model (generate() needs its "
                              "prefill/decode path)")
        engine = HybridEngine(engine, model)
    return engine, engine.tx, engine.training_dataloader, engine.lr_schedule


def init_inference(model=None, params=None, config=None, **kwargs):
    """Inference engine bring-up (reference deepspeed/__init__.py:299).

    Delegates to :func:`shuffle_exchange_tpu.inference.init_inference`, which
    accepts a reference-format config dict (or InferenceConfig) and requires
    the weights pytree via ``params``.
    """
    from .inference.engine import init_inference as _init_inference

    return _init_inference(model=model, params=params, config=config, **kwargs)


def add_config_arguments(parser):
    """argparse plumbing parity (reference deepspeed/__init__.py:241-289)."""
    group = parser.add_argument_group("DeepSpeed-compatible", "configuration")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str)
    group.add_argument("--deepscale", default=False, action="store_true")  # legacy alias
    group.add_argument("--deepscale_config", default=None, type=str)
    return parser
