"""MoE tests: gating invariants, layer numerics, EP sharding, Mixtral-style training."""

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.moe import moe_layer, topk_gating


def test_gating_invariants():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    S, E = 64, 4
    logits = jnp.asarray(rng.normal(size=(S, E)), jnp.float32)
    out = topk_gating(logits, k=2, capacity_factor=2.0)
    # every kept token's combine weights sum to <= 1 (== 1 when normalized & kept)
    sums = np.asarray(out.combine_weights.sum(axis=(1, 2)))
    assert (sums <= 1.0 + 1e-5).all()
    # dispatch consistent with combine
    assert bool(jnp.all((out.combine_weights > 0) == out.dispatch_mask))
    # capacity respected: per (expert, slot) at most one token
    per_slot = np.asarray(out.dispatch_mask.sum(axis=0))
    assert per_slot.max() <= 1
    assert float(out.aux_loss) > 0


def test_gating_top1_capacity_drop():
    import jax.numpy as jnp

    # all tokens prefer expert 0 -> capacity forces drops
    logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (16, 1))
    out = topk_gating(logits, k=1, capacity_factor=0.5, min_capacity=4)
    # capacity = max(min_capacity, ceil(S*k*cf/E)) = max(4, ceil(16*0.5/2)) = 4;
    # all 16 tokens prefer expert 0, so exactly 4 are kept and 12 dropped.
    assert int(out.dispatch_mask.sum()) == 4
    assert abs(float(out.metadata["drop_fraction"]) - 0.75) < 1e-6


def test_moe_layer_matches_dense_single_expert():
    """One expert, top-1, generous capacity == plain MLP."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.moe.layer import expert_mlp, init_expert_mlp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    params = init_expert_mlp(jax.random.PRNGKey(0), 1, 16, 32, "swiglu")
    gate_w = jnp.zeros((16, 1), jnp.float32)
    res = moe_layer(gate_w, params, x, k=1, capacity_factor=64.0)
    dense = expert_mlp(params, x.reshape(1, -1, 16), "swiglu").reshape(x.shape)
    np.testing.assert_allclose(np.asarray(res.output), np.asarray(dense), rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_moe_expert_parallel_matches_single(devices8):
    """EP over 4 devices == single-device numerics."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.config.config import MeshConfig
    from shuffle_exchange_tpu.moe.layer import init_expert_mlp
    from shuffle_exchange_tpu.parallel import MeshTopology
    from shuffle_exchange_tpu.parallel.mesh import reset_topology

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    params = init_expert_mlp(jax.random.PRNGKey(1), 4, 16, 32, "swiglu")
    gate_w = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)

    got_single = moe_layer(gate_w, params, x, k=2, capacity_factor=2.0)

    topo = MeshTopology.build(MeshConfig(expert=4, data=-1), devices=devices8)
    from jax.sharding import NamedSharding, PartitionSpec as P

    params_sharded = {k: jax.device_put(v, NamedSharding(topo.mesh, P("expert", None, None)))
                      for k, v in params.items()}
    out = jax.jit(lambda g, p, x: moe_layer(g, p, x, k=2, capacity_factor=2.0, mesh=topo.mesh).output)(
        gate_w, params_sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(got_single.output), rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_mixtral_style_training(devices8):
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.models.transformer import tiny_moe
    from shuffle_exchange_tpu.parallel.mesh import reset_topology

    reset_topology()
    model = Transformer(tiny_moe(vocab=128, d=32, layers=2, heads=2, experts=4))
    engine, *_ = sxt.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "mesh": {"expert": 4, "data": -1},
    })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(8, 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(10)]
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# Dropless ragged grouped-GEMM experts (reference cutlass moe_gemm /
# megablocks; SURVEY §2.13 — r2 VERDICT missing #6 "grouped GEMM kernels")
# ---------------------------------------------------------------------------


def test_ragged_matches_capacity_when_nothing_drops():
    """With generous capacity the GShard einsum path and the ragged
    grouped-GEMM path compute the same mixture (same top-k rule, same
    normalization)."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.moe.layer import init_expert_mlp, moe_layer

    rng = np.random.default_rng(0)
    E, M, F, S = 4, 32, 64, 24
    params = init_expert_mlp(jax.random.PRNGKey(0), E, M, F, "swiglu")
    gate_w = jnp.asarray(rng.standard_normal((M, E)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((S, M)), jnp.float32)

    cap = moe_layer(gate_w, params, x, k=2, capacity_factor=64.0, impl="capacity")
    rag = moe_layer(gate_w, params, x, k=2, impl="ragged")
    np.testing.assert_allclose(np.asarray(rag.output), np.asarray(cap.output),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(rag.aux_loss), float(cap.aux_loss), rtol=1e-5)
    assert float(rag.metadata["drop_fraction"]) == 0.0
    np.testing.assert_array_equal(np.asarray(rag.metadata["expert_counts"]),
                                  np.asarray(cap.metadata["expert_counts"]))


def test_ragged_never_drops_under_pressure():
    """At capacity_factor=1 with skewed routing the capacity path drops
    tokens; ragged keeps them all."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.moe.layer import init_expert_mlp, moe_layer

    E, M, F, S = 4, 16, 32, 64
    params = init_expert_mlp(jax.random.PRNGKey(1), E, M, F, "swiglu")
    # gate that routes everything to expert 0
    gate_w = jnp.zeros((M, E), jnp.float32).at[:, 0].set(1.0)
    x = jnp.abs(jnp.asarray(np.random.default_rng(1).standard_normal((S, M)), jnp.float32))
    cap = moe_layer(gate_w, params, x, k=1, capacity_factor=1.0, impl="capacity")
    rag = moe_layer(gate_w, params, x, k=1, impl="ragged")
    assert float(cap.metadata["drop_fraction"]) > 0.5
    assert float(rag.metadata["drop_fraction"]) == 0.0
    assert int(np.asarray(rag.metadata["expert_counts"])[0]) == S


def test_moe_model_trains_with_ragged_impl(devices8):
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer, tiny_moe
    from shuffle_exchange_tpu.parallel import reset_topology

    reset_topology()
    model = Transformer(tiny_moe(vocab=64, d=32, layers=2, heads=2, seq=32,
                                 experts=4, moe_impl="ragged"))
    engine, *_ = sxt.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10**9})
    b = {"input_ids": np.random.default_rng(0).integers(0, 64, size=(8, 32)).astype(np.int32)}
    l0 = float(engine.train_batch(b))
    for _ in range(3):
        l1 = float(engine.train_batch(b))
    assert np.isfinite(l1) and l1 < l0


def test_grouped_matmul_matches_pergroup_einsum():
    """grouped_matmul contract: rows sorted by group, one matmul per group
    against that group's weight slice (CPU path = ragged_dot; the TPU
    megablox path is parity-checked in tests/tpu_smoke.py)."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.grouped_gemm import grouped_matmul

    rng = np.random.default_rng(0)
    E, K, F = 4, 16, 24
    sizes = np.array([5, 0, 9, 2], np.int32)          # uneven, one empty
    N = int(sizes.sum())
    x = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, F)), jnp.float32)
    got = grouped_matmul(x, w, jnp.asarray(sizes))
    want = np.zeros((N, F), np.float32)
    start = 0
    for e, n in enumerate(sizes):
        want[start:start + n] = np.asarray(x[start:start + n] @ w[e])
        start += n
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    # gradient flows (the custom-vjp / transpose path)
    g = jax.grad(lambda xx: grouped_matmul(xx, w, jnp.asarray(sizes)).sum())(x)
    assert np.isfinite(np.asarray(g)).all()


def _gmm_against_ragged_dot(K, F, sizes, dtype, tol, pad=0, interpret=True):
    """megablox under the tile rule against ``lax.ragged_dot``, value and
    both gradients, on rows the groups hold (what stands past them is
    nobody's: ``moe/layer.expert_mlp_ragged``)."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.ops.grouped_gemm import _grouped_matmul_gmm

    rng = np.random.default_rng(K + F)
    sizes = np.asarray(sizes, np.int32)
    held, N, E = int(sizes.sum()), int(sizes.sum()) + pad, len(sizes)
    x = jnp.asarray(rng.standard_normal((N, K)), dtype)
    w = jnp.asarray(rng.standard_normal((E, K, F)) * K ** -0.5, dtype)
    gs = jnp.asarray(sizes)

    def loss(mm):
        return lambda x, w: (mm(x, w)[:held].astype(jnp.float32) ** 2).mean()

    got = lambda x, w: _grouped_matmul_gmm(x, w, gs, interpret=interpret)
    want = lambda x, w: jax.lax.ragged_dot(x, w, gs)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(got(x, w))[:held], f32(want(x, w))[:held], rtol=tol, atol=tol)
    (dx, dw), (rx, rw) = (jax.grad(loss(mm), argnums=(0, 1))(x, w) for mm in (got, want))
    for g, r in ((f32(dx)[:held], f32(rx)[:held]), (f32(dw), f32(rw))):
        np.testing.assert_allclose(g, r, rtol=tol, atol=tol * float(np.abs(r).max()))


@pytest.mark.parametrize("K, F, sizes, pad", [
    (256, 384, [300, 0, 450, 250], 0),          # rows padded 1000 -> 1024, four row tiles
    (2048, 512, [130, 0, 401, 97, 12], 0),      # the whole contraction one k-step; tgmm's whole output
    (1856, 256, [200, 0, 263], 0),              # 14.5 lane tiles whole as k, in tiles of 640 as n (clipped)
    (768, 2560, [100, 330, 0, 210], 128),       # the whole output one tile forward, tgmm's in two; an empty tail
    (14336, 256, [90, 0, 166], 0),              # no one k-step fits: the cap's k-steps of 1024
], ids=["small", "one-k-step", "half-lane-tile", "buffer-tail", "cap"])
def test_megablox_under_the_tile_rule_matches_ragged_dot(K, F, sizes, pad):
    """The kernel route's NUMBERS under the tile rule (PR 66), in the library's own
    interpreter (the dispatch seam never sends a CPU call there): uneven
    groups, an empty group, a partial last row tile; float32 operands, so the
    comparison holds the tiling (pads, masks, clipped tiles) and not a
    rounding."""
    import jax.numpy as jnp

    _gmm_against_ragged_dot(K, F, sizes, jnp.float32, 2e-4, pad=pad)


def test_megablox_under_the_tile_rule_matches_ragged_dot_on_the_chip():
    """The same at bf16 through the compiled kernels (on the chip
    ``testing/kernel_parity.py`` runs it for ``chip_smoke.py`` too)."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        pytest.skip("the compiled megablox kernels need a TPU")

    _gmm_against_ragged_dot(2048, 512, [130, 0, 401, 97, 12], jnp.bfloat16, 5e-2,
                            interpret=False)


@pytest.mark.slow   # 10s: impl parity; nightly via ci_full (ISSUE 13 tier-1 budget)
def test_index_dispatch_matches_einsum_dispatch():
    """The round-5 index-form capacity path (scalar slot scatter + row
    gathers) must be BIT-equivalent in routing to the GShard dense-einsum
    oracle — same drops, same weights, same output, same gradients —
    including under capacity pressure (capacity_factor < 1 forces drops)."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.moe.layer import moe_layer

    rng = np.random.default_rng(5)
    S, M, E, k = 64, 32, 4, 2
    gate_w = jnp.asarray(rng.normal(size=(M, E)), jnp.float32)
    params = {
        "w_up": jnp.asarray(rng.normal(size=(E, M, 64)) * 0.1, jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(E, M, 64)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(E, 64, M)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(2, S // 2, M)), jnp.float32)

    for cf in (1.0, 0.5):   # 0.5: guaranteed overflow drops
        r_idx = moe_layer(gate_w, params, x, k=k, capacity_factor=cf,
                          impl="capacity", train=False)
        r_ein = moe_layer(gate_w, params, x, k=k, capacity_factor=cf,
                          impl="capacity_einsum", train=False)
        np.testing.assert_allclose(np.asarray(r_idx.output),
                                   np.asarray(r_ein.output),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(r_idx.aux_loss), float(r_ein.aux_loss),
                                   rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(r_idx.metadata["expert_counts"]),
            np.asarray(r_ein.metadata["expert_counts"]))

        def loss(impl):
            def f(gw, p, xx):
                return (moe_layer(gw, p, xx, k=k, capacity_factor=cf,
                                  impl=impl, train=False).output ** 2).sum()
            return f

        g1 = jax.grad(loss("capacity"), argnums=(0, 1, 2))(gate_w, params, x)
        g2 = jax.grad(loss("capacity_einsum"), argnums=(0, 1, 2))(gate_w, params, x)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_moe_layer_rejects_unknown_impl():
    """ADVICE r5 #1: a typo'd impl must raise, not silently fall through
    to the index-dispatch capacity path."""
    import pytest

    from shuffle_exchange_tpu.moe.layer import moe_layer

    rng = np.random.default_rng(0)
    gate_w = np.zeros((16, 4), np.float32)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    for bad in ("einsum", "index", "gshard", ""):
        # validation fires before any expert params are touched
        with pytest.raises(ValueError, match="impl must be one of"):
            moe_layer(gate_w, {}, x, impl=bad)


def test_resolve_moe_impl_auto_matrix():
    """What "auto" means: GShard capacity semantics under a scanned stack or
    an expert axis, dropless ragged standalone. (A choice of semantics, not of
    speed: the megablox kernel is as fast under a scan as outside one once it
    is tiled, PERF.md section 6, PR 28; a model whose source routes without
    drops is given moe_impl="ragged" by config_from_hf, tests/test_olmoe.py.)
    Explicit impls always pass through untouched."""
    from shuffle_exchange_tpu.moe import resolve_moe_impl

    # (ep_size, scanned) -> resolution
    assert resolve_moe_impl("auto", 1, scanned=False) == "ragged"
    assert resolve_moe_impl("auto", 1, scanned=True) == "capacity"
    assert resolve_moe_impl("auto", 2, scanned=False) == "capacity"
    assert resolve_moe_impl("auto", 2, scanned=True) == "capacity"
    for explicit in ("capacity", "capacity_einsum", "ragged"):
        for ep in (1, 2):
            for sc in (False, True):
                assert resolve_moe_impl(explicit, ep, sc) == explicit


def test_moe_layer_auto_scanned_takes_capacity_path(devices8):
    """auto + scanned resolves to the capacity path end-to-end: the result
    carries capacity/drop metadata (drop_fraction from the gating path),
    not the ragged path's zero-drop constant-with-capacity-S signature."""
    import jax

    from shuffle_exchange_tpu.moe.layer import init_expert_mlp, moe_layer

    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    E, M, S = 4, 16, 32
    gate_w = rng.standard_normal((M, E)).astype(np.float32)
    params = init_expert_mlp(key, E, M, 32)
    x = rng.standard_normal((S, M)).astype(np.float32)

    scanned = moe_layer(gate_w, params, x, impl="auto", scanned=True,
                        capacity_factor=1.0)
    unscanned = moe_layer(gate_w, params, x, impl="auto", scanned=False,
                          capacity_factor=1.0)
    cap_ref = moe_layer(gate_w, params, x, impl="capacity",
                        capacity_factor=1.0)
    rag_ref = moe_layer(gate_w, params, x, impl="ragged",
                        capacity_factor=1.0)
    np.testing.assert_allclose(np.asarray(scanned.output),
                               np.asarray(cap_ref.output), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(unscanned.output),
                               np.asarray(rag_ref.output), rtol=1e-5)
    # capacity metadata present on the scanned resolution
    assert int(scanned.metadata["capacity"]) < S  # E*C slots, not S tokens
