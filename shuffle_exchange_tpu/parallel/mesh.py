"""Named-axis device mesh topology.

This is the TPU-native replacement for the reference's process-group
machinery: ``utils/groups.py`` (MP/DP/EP/SP group registry),
``runtime/pipe/topology.py`` (ProcessTopology rank grid) and
``comm/comm.py:616`` (``initialize_mesh_device``). Instead of NCCL process
groups, every parallel dimension is a named axis of one
``jax.sharding.Mesh``; collectives ride ICI when the axis maps onto
physically-adjacent chips and DCN across slices/hosts.

Axes (reference strategy → mesh axis):
  DP / decentralized-sync replicas  → "data"
  ZeRO partitioning (stages 1-3)    → "fsdp"
  Tensor parallel (AutoTP)          → "tensor"
  Expert parallel (MoE)             → "expert"
  Ulysses / ring sequence parallel  → "seq"
  Pipeline stages                   → "pipe"

Axis order is (pipe, data, fsdp, expert, seq, tensor): innermost axes get
ICI-contiguous device ranges, so tensor/seq/expert collectives (latency
sensitive, per-layer) ride ICI while pipe/data (less frequent) may cross DCN.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config.config_utils import ConfigError
from ..profiling import trace
from ..utils.logging import log_dist, logger

AXIS_ORDER: Tuple[str, ...] = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# ZeRO parameter/optimizer partitioning shards over both data-like axes: the
# reference partitions over the whole DP world; here the DP world is
# data × fsdp (fsdp is the dedicated shard axis, data may add replicas).
ZERO_AXES: Tuple[str, ...] = ("data", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Resolved axis sizes for a device count."""

    sizes: Dict[str, int]

    @property
    def total(self) -> int:
        out = 1
        for v in self.sizes.values():
            out *= v
        return out


def resolve_axis_sizes(mesh_config, n_devices: int) -> MeshSpec:
    """Fill in data=-1 from the device count and validate divisibility."""
    sizes = {ax: getattr(mesh_config, ax) for ax in AXIS_ORDER}
    fixed = 1
    for ax, v in sizes.items():
        if v == 0 or v < -1:
            raise ConfigError(f"mesh.{ax} must be positive or -1, got {v}")
        if v != -1:
            fixed *= v
    wildcard = [ax for ax, v in sizes.items() if v == -1]
    if len(wildcard) > 1:
        raise ConfigError(f"Only one mesh axis may be -1, got {wildcard}")
    if wildcard:
        if n_devices % fixed:
            raise ConfigError(
                f"Device count {n_devices} not divisible by fixed mesh axes product {fixed} ({sizes})")
        sizes[wildcard[0]] = n_devices // fixed
    else:
        if fixed != n_devices:
            raise ConfigError(f"Mesh sizes {sizes} multiply to {fixed} != device count {n_devices}")
    return MeshSpec(sizes)


class MeshTopology:
    """The one device mesh + axis bookkeeping for a run.

    Construction: ``MeshTopology.build(mesh_config)`` uses all visible
    devices. Thin API mirrors the reference groups registry (§2.7) so
    engine/moe/sequence code asks topology questions in one place.
    """

    def __init__(self, mesh: "jax.sharding.Mesh"):
        self.mesh = mesh
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    # -- construction -------------------------------------------------

    @classmethod
    def build(cls, mesh_config=None, n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> "MeshTopology":
        import jax

        if devices is None:
            devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
        if mesh_config is None:
            from ..config.config import MeshConfig

            mesh_config = MeshConfig()
        spec = resolve_axis_sizes(mesh_config, len(devices))
        shape = tuple(spec.sizes[ax] for ax in AXIS_ORDER)
        if devices[0].platform == "tpu" and len(devices) > 1:
            # jax.devices() is in id order, which on a 2x2 torus puts
            # diagonal chips next to each other; mesh_utils lays the logical
            # axes along the physical ones, so the innermost live axis
            # rides neighbouring ICI links
            from jax.experimental import mesh_utils

            dev_array = mesh_utils.create_device_mesh(
                shape, devices=list(devices), allow_split_physical_axes=True)
        else:
            dev_array = np.asarray(devices).reshape(shape)
        mesh = jax.sharding.Mesh(dev_array, AXIS_ORDER)
        log_dist(f"Mesh built: {dict(zip(AXIS_ORDER, shape))} over {len(devices)} devices", ranks=[0])
        return cls(mesh)

    # -- axis queries (reference utils/groups.py getters) --------------

    def size(self, *axes: str) -> int:
        out = 1
        for ax in axes:
            out *= self.axis_sizes[ax]
        return out

    @property
    def world_size(self) -> int:
        return self.size(*AXIS_ORDER)

    @property
    def data_parallel_world_size(self) -> int:
        # ZeRO/DP world = data × fsdp (see ZERO_AXES).
        return self.size(*ZERO_AXES)

    @property
    def replica_world_size(self) -> int:
        return self.size("data")

    @property
    def model_parallel_world_size(self) -> int:
        return self.size("tensor")

    @property
    def expert_parallel_world_size(self) -> int:
        return self.size("expert")

    @property
    def sequence_parallel_world_size(self) -> int:
        return self.size("seq")

    @property
    def pipe_parallel_world_size(self) -> int:
        return self.size("pipe")

    def active_axes(self) -> List[str]:
        return [ax for ax in AXIS_ORDER if self.axis_sizes[ax] > 1]

    # -- shardings -----------------------------------------------------

    def named_sharding(self, *spec) -> "jax.sharding.NamedSharding":
        import jax
        from jax.sharding import PartitionSpec

        return jax.sharding.NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> "jax.sharding.NamedSharding":
        return self.named_sharding()

    def batch_sharding(self, extra_axes: Sequence[str] = ()) -> "jax.sharding.NamedSharding":
        """Global batch dim sharded over every data-like axis (+ optional)."""
        axes = tuple(ax for ax in ("data", "fsdp", *extra_axes) if self.axis_sizes.get(ax, 1) >= 1)
        return self.named_sharding(axes)

    # -- pipeline grid (reference runtime/pipe/topology.py) ------------

    def pipe_coord(self, device_index: int) -> Dict[str, int]:
        """Axis coordinates of a flat device index in the mesh grid."""
        shape = tuple(self.axis_sizes[ax] for ax in AXIS_ORDER)
        coords = np.unravel_index(device_index, shape)
        return dict(zip(AXIS_ORDER, (int(c) for c in coords)))

    def __repr__(self) -> str:
        return f"MeshTopology({self.axis_sizes})"


# ----------------------------------------------------------------------
# Module-level registry (reference utils/groups.py singleton pattern)
# ----------------------------------------------------------------------

_TOPOLOGY: Optional[MeshTopology] = None


def initialize_topology(mesh_config=None, n_devices: Optional[int] = None, devices=None, force: bool = False) -> MeshTopology:
    global _TOPOLOGY
    if _TOPOLOGY is not None and not force:
        logger.warning("MeshTopology already initialized; reusing (pass force=True to rebuild)")
        return _TOPOLOGY
    _TOPOLOGY = MeshTopology.build(mesh_config, n_devices=n_devices, devices=devices)
    return _TOPOLOGY


def get_topology() -> MeshTopology:
    if _TOPOLOGY is None:
        raise RuntimeError("MeshTopology not initialized; call initialize_topology() or sxt.initialize() first")
    return _TOPOLOGY


def topology_is_initialized() -> bool:
    return _TOPOLOGY is not None


def reset_topology() -> None:
    global _TOPOLOGY
    _TOPOLOGY = None


# Reference-compatible getter names (utils/groups.py:57-749).

def shard_map(f, mesh=None, in_specs=None, out_specs=None, axis_names=None,
              check_vma: bool = True):
    """``jax.shard_map`` with ``axis_names`` (the MANUAL axes of a
    partial-manual region; None = every mesh axis) taken as any iterable."""
    import jax

    kw = {}
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def _manual_axes() -> frozenset:
    """Mesh axes that are manual at this point of the trace (empty outside
    any shard_map region)."""
    import jax

    ctx = jax.sharding.get_abstract_mesh()
    return frozenset(name for name, t in zip(ctx.axis_names, ctx.axis_types)
                     if t == jax.sharding.AxisType.Manual)


def inside_manual_region() -> bool:
    """True when tracing inside a (partial-)manual shard_map region."""
    return bool(_manual_axes())


def constraint_mesh(default=None):
    """Mesh to use for in-trace sharding constraints / nested shard_maps.

    Inside a (partial-)manual region, constraints must be built on the
    CONTEXT abstract mesh (whose enclosing axes are typed Manual) — a
    NamedSharding over the concrete topology mesh (all-Auto) trips the
    mesh-equality check. Outside any region returns ``default`` (or the
    topology mesh)."""
    import jax

    if _manual_axes():
        return jax.sharding.get_abstract_mesh()
    if default is not None:
        return default
    return get_topology().mesh


# ----------------------------------------------------------------------
# Pallas kernels inside programs that span several devices
# ----------------------------------------------------------------------

_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "sxt_kernel_mesh", default=None)
_PARAM_SPECS: contextvars.ContextVar = contextvars.ContextVar(
    "sxt_param_specs", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh, param_specs=None):
    """While tracing under this, Pallas kernel call sites wrap themselves in
    a shard_map over ``mesh`` (:func:`shard_kernel`), and the chunked loss
    gathers its weights by hand (:func:`zero_batch_axes`), each along the dim
    that ``param_specs`` (top-level parameter name -> the PartitionSpec its
    master is stored with) shards over a ZeRO axis. The training engine
    enters it around the loss and the optimizer update of its mesh-wide
    programs; a serving engine's one-device programs never do, so a live
    training topology in the same process does not reach into them. A
    context variable, so a thread that traces concurrently sees its own."""
    token = _KERNEL_MESH.set(mesh)
    specs = _PARAM_SPECS.set(param_specs)
    try:
        yield
    finally:
        _PARAM_SPECS.reset(specs)
        _KERNEL_MESH.reset(token)


def kernel_activation_spec(shape, seq_dim: Optional[int] = None,
                           heads_dim: Optional[int] = None,
                           head_counts: Sequence[int] = ()):
    """PartitionSpec of a batch-major activation inside a kernel's
    shard_map, following the training layout: dim 0 over the live data-like
    axes (``data``, ``fsdp``), ``seq_dim`` over ``seq`` and ``heads_dim``
    over ``tensor`` - each only where the axis is live and divides the
    dimension (and every count in ``head_counts``, for GQA's two head
    numbers). A dimension left out is whole on every device: more work than
    needed, never a wrong answer."""
    from jax.sharding import PartitionSpec

    mesh = _KERNEL_MESH.get()
    spec = [None] * len(shape)
    if mesh is None or len(shape) < 2:
        return PartitionSpec(*spec)
    axes = tuple(ax for ax in ZERO_AXES if mesh.shape[ax] > 1)
    if axes and shape[0] % int(np.prod([mesh.shape[ax] for ax in axes])) == 0:
        spec[0] = axes
    sp, tp = mesh.shape["seq"], mesh.shape["tensor"]
    if seq_dim is not None and sp > 1 and shape[seq_dim] % sp == 0:
        spec[seq_dim] = "seq"
    if heads_dim is not None and tp > 1 and all(
            h % tp == 0 for h in (shape[heads_dim], *head_counts)):
        spec[heads_dim] = "tensor"
    return PartitionSpec(*spec)


def batch_rows_a_device(batch: int) -> int:
    """Rows of a batch-major activation of ``batch`` rows that one device
    holds inside a kernel's shard_map (:func:`kernel_activation_spec`'s
    dim 0): all of them outside :func:`kernel_mesh`."""
    axes = kernel_activation_spec((batch, 1))[0]
    if not axes:
        return batch
    mesh = _KERNEL_MESH.get()
    axes = (axes,) if isinstance(axes, str) else axes
    return batch // int(np.prod([mesh.shape[ax] for ax in axes]))


def kernel_mesh_devices() -> int:
    """Devices of the mesh :func:`shard_kernel` lays a kernel call out over:
    1 outside :func:`kernel_mesh`. The mesh's size, not whether
    ``shard_kernel`` wraps: inside a region that is manual over every axis
    it hands the kernel back as it is, and the call still runs per shard."""
    mesh = _KERNEL_MESH.get()
    return 1 if mesh is None else int(mesh.size)


def shard_kernel(fn, in_specs, out_specs):
    """``fn`` (a Pallas kernel call), made safe inside a program that spans
    several devices. XLA cannot partition a Mosaic kernel: there the call
    must sit in a shard_map that is manual over EVERY mesh axis, each device
    running the kernel on its own block as the specs lay them out. Outside
    :func:`kernel_mesh`, or on a one-device mesh, ``fn`` is returned as it
    is. Inside an enclosing (partial-)manual region only the remaining axes
    are taken, on the region's own mesh, and the specs lose the axes that
    are manual already (their blocks are local by then)."""
    if kernel_mesh_devices() == 1:
        return fn
    import jax
    from jax.sharding import PartitionSpec

    mesh = _KERNEL_MESH.get()
    taken = _manual_axes()
    free = [ax for ax in mesh.axis_names if ax not in taken]
    if not free:
        return fn

    def keep(spec):
        return spec_subset(spec, free)

    is_spec = lambda x: isinstance(x, PartitionSpec)
    return shard_map(fn, mesh=constraint_mesh(mesh),
                     in_specs=jax.tree.map(keep, in_specs, is_leaf=is_spec),
                     out_specs=jax.tree.map(keep, out_specs, is_leaf=is_spec),
                     axis_names=free, check_vma=False)


# ----------------------------------------------------------------------
# ZeRO-sharded weights inside a region that is manual over the ZeRO axes
# ----------------------------------------------------------------------

def entry_subset(entry, allowed):
    """One PartitionSpec entry cut down to its axes in ``allowed`` (None
    when none is left)."""
    if entry is None:
        return None
    axes = entry if isinstance(entry, tuple) else (entry,)
    keep = tuple(a for a in axes if a in allowed)
    if not keep:
        return None
    return keep if len(keep) > 1 else keep[0]


def spec_subset(spec, allowed):
    """``spec`` with every entry cut down to its axes in ``allowed``: the
    in/out spec of a leaf for a region that is manual over ``allowed``."""
    from jax.sharding import PartitionSpec

    return PartitionSpec(*(entry_subset(e, allowed) for e in spec))


def zero_sharded_dim(spec, zero_axes):
    """(dim, entry) of the first dim of ``spec`` sharded over any of
    ``zero_axes``, the entry cut down to them; None when no dim is."""
    for dim, e in enumerate(spec):
        ze = entry_subset(e, zero_axes)
        if ze is not None:
            return dim, ze
    return None


def gather_zero_sharded(x, spec, zero_axes, wire=None):
    """Inside a region manual over ``zero_axes``: the local block ``x`` of a
    leaf stored with ``spec``, gathered whole along its ZeRO-sharded dim
    (other axes of that dim, ``tensor`` say, stay automatic), under the
    scope ``zero3_gather``. ``wire(x, entry, dim)`` replaces the plain tiled
    all-gather (the int8 wire of qwZ). A leaf no ZeRO axis shards is
    returned as it is. The one gather of the engine's streamed ZeRO-3 wire,
    its LoRA frozen base and the chunked loss's head."""
    import jax

    hit = zero_sharded_dim(spec, zero_axes)
    if hit is None:
        return x
    dim, entry = hit
    with trace.scope("zero3_gather"):
        if wire is not None:
            return wire(x, entry, dim)
        return jax.lax.all_gather(x, entry, axis=dim, tiled=True)


def _reduce_to_shard(g, spec, zero_axes, dtype):
    """A gathered leaf's cotangent ``g`` (this device's own unreduced sum)
    back on its shard: one float32 reduce-scatter under the scope
    ``zero3_reduce_scatter``, handed on in ``dtype``. A leaf no ZeRO axis
    shards entered the region whole; the region's transpose sums it."""
    import jax
    import jax.numpy as jnp

    hit = zero_sharded_dim(spec, zero_axes)
    if hit is None:
        return g.astype(dtype)
    dim, entry = hit
    with trace.scope("zero3_reduce_scatter"):
        return jax.lax.psum_scatter(g.astype(jnp.float32), entry,
                                    scatter_dimension=dim, tiled=True).astype(dtype)


def gather_for_loop(x, spec, zero_axes):
    """:func:`gather_zero_sharded` as a differentiable unit for a weight
    that a loop closes over: forward, one all-gather before the loop;
    backward, the loop's carry is this device's own unreduced sum (in the
    weight's dtype) and ONE reduce-scatter takes it back to the shard after
    the loop, whatever the number of trips. Left to XLA's partitioner the
    gather and the reduction sit in the loop's body, once a trip."""
    import jax

    def gather(x):
        return gather_zero_sharded(x, spec, zero_axes)

    def fwd(x):
        return gather(x), None

    def bwd(_, g):
        return (_reduce_to_shard(g, spec, zero_axes, g.dtype),)

    gathered = jax.custom_vjp(gather)
    gathered.defvjp(fwd, bwd)
    return gathered(x)


def grad_accumulator(x, spec, zero_axes):
    """Float32 zeros in the shape of the gathered leaf, made for their
    COTANGENT: a loop that sends the leaf's gradient here and not to the
    (bf16) gathered leaf sums it trip after trip in float32, and backward
    takes that sum to the shard by :func:`_reduce_to_shard`. The value is
    never read, so the forward pass drops it."""
    import jax
    import jax.numpy as jnp

    whole = jax.eval_shape(lambda x: gather_zero_sharded(x, spec, zero_axes), x)

    def zeros(x):
        return jnp.zeros(whole.shape, jnp.float32)

    def fwd(x):
        return zeros(x), None

    def bwd(_, g):
        return (_reduce_to_shard(g, spec, zero_axes, x.dtype),)

    accumulator = jax.custom_vjp(zeros)
    accumulator.defvjp(fwd, bwd)
    return accumulator(x)


def zero_batch_axes(batch: int) -> Tuple[str, ...]:
    """The ZeRO axes that split the ``batch`` rows of an activation in the
    mesh-wide program being traced, where a region may still take them as
    manual: those of :func:`kernel_mesh`'s mesh that are larger than 1. Empty
    outside ``kernel_mesh`` (a one-device program, an ensemble's vmapped
    replicas), on a mesh with no such axis, inside a region that took one
    already (the streamed ZeRO-3 wire, the qgZ and flat pipeline regions:
    their weights are whole), where they do not divide ``batch``, and on a
    ``seq`` axis larger than 1 (the loss's chunks run along that dim)."""
    mesh = _KERNEL_MESH.get()
    if mesh is None:
        return ()
    axes = tuple(ax for ax in ZERO_AXES if mesh.shape[ax] > 1)
    if (not axes or mesh.shape["seq"] > 1 or _manual_axes() & set(ZERO_AXES)
            or batch % int(np.prod([mesh.shape[ax] for ax in axes]))):
        return ()
    return axes


def zero_region(fn, accumulated, zero_axes):
    """``fn(leaves, accumulators, *batch_args) -> sums`` run in a region
    manual over ``zero_axes`` only: ``leaves`` (a dict of top-level
    parameters) enter as their ZeRO shards and are gathered once
    (:func:`gather_for_loop`); those named in ``accumulated`` are gathered
    plainly and come with a :func:`grad_accumulator` for ``fn`` to direct
    their gradient to. A leaf no ZeRO axis shards (stage 0, or too small
    to divide) enters whole, in float32: the region's transpose sums its
    cotangent over the axes in the dtype it entered with. Each ``batch_arg``
    enters as this device's rows of dim 0, and every output is summed over
    the axes. Other axes (``tensor``, ``expert``) stay automatic inside."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    given = _PARAM_SPECS.get() or {}

    def spec_of(name):
        # a leaf the engine gave no spec for (a LoRA-merged weight) enters whole
        spec = given.get(name)
        return (spec_subset(spec, zero_axes)
                if isinstance(spec, PartitionSpec) else PartitionSpec())

    def local(specs, dtypes, leaves, *batch_args):
        accumulators = {k: grad_accumulator(leaves[k], specs[k], zero_axes)
                        for k in accumulated}
        leaves = {k: v.astype(dtypes[k]) for k, v in leaves.items()}
        # an accumulated leaf's gradient goes by its accumulator alone
        leaves = {k: (jax.lax.stop_gradient(
                          gather_zero_sharded(v, specs[k], zero_axes))
                      if k in accumulated else
                      gather_for_loop(v, specs[k], zero_axes))
                  for k, v in leaves.items()}
        return jax.tree.map(lambda s: jax.lax.psum(s, zero_axes),
                            fn(leaves, accumulators, *batch_args))

    def region(leaves, *batch_args):
        specs = {k: spec_of(k) for k in leaves}
        dtypes = {k: v.dtype for k, v in leaves.items()}
        leaves = {k: v.astype(jnp.float32)
                  if zero_sharded_dim(specs[k], zero_axes) is None
                  and jnp.issubdtype(v.dtype, jnp.floating) else v
                  for k, v in leaves.items()}
        rows = PartitionSpec(zero_axes)
        return shard_map(functools.partial(local, specs, dtypes),
                         mesh=constraint_mesh(_KERNEL_MESH.get()),
                         in_specs=(specs,) + (rows,) * len(batch_args),
                         out_specs=PartitionSpec(), axis_names=zero_axes,
                         check_vma=False)(leaves, *batch_args)

    return region


def get_data_parallel_world_size() -> int:
    return get_topology().data_parallel_world_size


def get_model_parallel_world_size() -> int:
    return get_topology().model_parallel_world_size


def get_expert_parallel_world_size() -> int:
    return get_topology().expert_parallel_world_size


def get_sequence_parallel_world_size() -> int:
    return get_topology().sequence_parallel_world_size


def get_pipe_parallel_world_size() -> int:
    return get_topology().pipe_parallel_world_size
