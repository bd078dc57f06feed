"""Mesh topology + comm facade tests on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

from shuffle_exchange_tpu.config import ConfigError
from shuffle_exchange_tpu.config.config import MeshConfig
from shuffle_exchange_tpu.parallel import MeshTopology, comm, resolve_axis_sizes


def test_resolve_axis_sizes_wildcard():
    spec = resolve_axis_sizes(MeshConfig(), 8)
    assert spec.sizes["data"] == 8 and spec.total == 8


def test_resolve_axis_sizes_fixed():
    cfg = MeshConfig(data=2, fsdp=2, tensor=2)
    spec = resolve_axis_sizes(cfg, 8)
    assert spec.sizes == {"pipe": 1, "data": 2, "fsdp": 2, "expert": 1, "seq": 1, "tensor": 2}


def test_resolve_axis_sizes_indivisible():
    with pytest.raises(ConfigError, match="not divisible"):
        resolve_axis_sizes(MeshConfig(fsdp=3), 8)


def test_mesh_build_and_queries(devices8):
    topo = MeshTopology.build(MeshConfig(data=2, fsdp=4), devices=devices8)
    assert topo.world_size == 8
    assert topo.data_parallel_world_size == 8  # data × fsdp
    assert topo.replica_world_size == 2
    assert topo.active_axes() == ["data", "fsdp"]
    sh = topo.named_sharding("fsdp")
    assert sh.mesh.shape["fsdp"] == 4


def test_collectives_in_shard_map(devices8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from shuffle_exchange_tpu.parallel.mesh import shard_map

    topo = MeshTopology.build(MeshConfig(data=4, fsdp=2), devices=devices8)
    mesh = topo.mesh

    def f(x):
        s = comm.psum(x, "data")
        g = comm.all_gather(x, "fsdp", axis=0, tiled=True)
        r = comm.reduce_scatter(g, "fsdp", scatter_dimension=0, tiled=True)
        return s, r

    x = jnp.arange(16.0).reshape(8, 2)
    fm = shard_map(f, mesh=mesh, in_specs=P(("data", "fsdp")), out_specs=(P(("data", "fsdp")), P(("data", "fsdp"))))
    s, r = jax.jit(fm)(x)
    assert s.shape == x.shape
    # psum over "data": device (d, f) holds global row d*2+f; its sum is over
    # rows with the same fsdp coordinate f.
    xs = np.asarray(x)
    expected_s = np.stack([xs[f::2].sum(axis=0) for f in range(2)])  # [f, col]
    for d in range(4):
        for f in range(2):
            np.testing.assert_allclose(np.asarray(s)[d * 2 + f], expected_s[f])
    # all_gather then reduce_scatter over the same axis: every device holds an
    # identical gathered copy, so each scattered chunk sums to world_size × x.
    np.testing.assert_allclose(np.asarray(r), 2.0 * xs)


def test_comms_logger_records(devices8):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from shuffle_exchange_tpu.parallel.mesh import shard_map
    import jax

    comm.comms_logger.enabled = True
    comm.comms_logger.reset()
    topo = MeshTopology.build(MeshConfig(data=8), devices=devices8)
    f = shard_map(lambda x: comm.psum(x, "data"), mesh=topo.mesh, in_specs=P("data"), out_specs=P("data"))
    jax.jit(f)(jnp.ones((8, 4)))
    assert comm.comms_logger.stats["all_reduce"]["count"] >= 1
    report = comm.log_summary()
    assert "all_reduce" in report
    comm.comms_logger.enabled = False


# ---------------------------------------------------------------------------
# Pallas kernels inside programs that span several devices (shard_kernel).
# XLA cannot partition a Mosaic kernel; on the chip the 4-device ZeRO-3 step
# failed to lower until every kernel call sat in a full-manual shard_map.
# The kernels themselves need a TPU; what is checked here is the wrapper:
# same numbers as the unwrapped function, on the layouts training uses.
# ---------------------------------------------------------------------------


def _rms(x, w):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5) * w


def test_shard_kernel_is_the_function_itself_outside_a_kernel_mesh(devices8):
    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.parallel.mesh import (kernel_activation_spec,
                                                    kernel_mesh, shard_kernel)

    assert shard_kernel(_rms, (P(), P()), P()) is _rms
    assert kernel_activation_spec((8, 16, 32), seq_dim=1) == P(None, None, None)
    one = MeshTopology.build(MeshConfig(data=1), devices=devices8[:1])
    with kernel_mesh(one.mesh):       # a one-device mesh needs no wrapping
        assert shard_kernel(_rms, (P(), P()), P()) is _rms
    with kernel_mesh(None):           # ensemble engines pass None
        assert shard_kernel(_rms, (P(), P()), P()) is _rms


def test_kernel_activation_spec_follows_the_training_layout(devices8):
    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.parallel.mesh import (kernel_activation_spec,
                                                    kernel_mesh)

    topo = MeshTopology.build(MeshConfig(data=2, fsdp=2, tensor=2),
                              devices=devices8)
    with kernel_mesh(topo.mesh):
        assert kernel_activation_spec((8, 16, 32)) == P(("data", "fsdp"), None, None)
        # a batch the data-like axes do not divide stays whole
        assert kernel_activation_spec((6, 16, 32)) == P(None, None, None)
        # heads over tensor only when BOTH head counts divide (GQA)
        assert kernel_activation_spec((8, 16, 4, 64), heads_dim=2,
                                      head_counts=(2,)) == P(
            ("data", "fsdp"), None, "tensor", None)
        assert kernel_activation_spec((8, 16, 4, 64), heads_dim=2,
                                      head_counts=(1,)) == P(
            ("data", "fsdp"), None, None, None)
    seq = MeshTopology.build(MeshConfig(data=4, seq=2), devices=devices8)
    with kernel_mesh(seq.mesh):
        assert kernel_activation_spec((8, 16, 32), seq_dim=1) == P(
            ("data",), "seq", None)


def test_shard_kernel_matches_the_unwrapped_function_under_jit(devices8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.parallel.mesh import (kernel_activation_spec,
                                                    kernel_mesh, shard_kernel)

    topo = MeshTopology.build(MeshConfig(data=2, fsdp=4), devices=devices8)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 12, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64,)), jnp.float32)

    def step(x, w):
        with kernel_mesh(topo.mesh):
            rows = kernel_activation_spec(x.shape)
            fn = shard_kernel(_rms, (rows, P(None)), rows)
            assert fn is not _rms
            return fn(x, w)

    xs = jax.device_put(x, topo.batch_sharding())
    got = jax.jit(step)(xs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_rms(x, w)),
                               rtol=1e-6, atol=1e-6)
    # differentiable straight through the wrapper (training's backward)
    g = jax.jit(jax.grad(lambda x, w: step(x, w).sum(), argnums=(0, 1)))(xs, w)
    gr = jax.grad(lambda x, w: _rms(x, w).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_shard_kernel_inside_a_partial_manual_region_takes_the_rest(devices8):
    """Inside a region that is already manual over data/fsdp (the ZeRO++
    wire, Ulysses) the wrapper takes only the remaining axes and drops the
    taken ones from the specs - the blocks are local by then."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.parallel.mesh import (kernel_activation_spec,
                                                    kernel_mesh, shard_kernel,
                                                    shard_map)

    topo = MeshTopology.build(MeshConfig(data=2, fsdp=2, tensor=2),
                              devices=devices8)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 6, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32,)), jnp.float32)
    seen = {}

    def inner(x, w):
        with kernel_mesh(topo.mesh):
            rows = kernel_activation_spec(x.shape)
            fn = shard_kernel(_rms, (rows, P(None)), rows)
            seen["wrapped"] = fn is not _rms
            return fn(x, w)

    region = shard_map(inner, mesh=topo.mesh,
                       in_specs=(P(("data", "fsdp")), P()),
                       out_specs=P(("data", "fsdp")),
                       axis_names={"data", "fsdp"}, check_vma=False)
    got = jax.jit(region)(x, w)
    assert seen["wrapped"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(_rms(x, w)),
                               rtol=1e-6, atol=1e-6)
