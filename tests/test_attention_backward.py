"""The splash routes' backward as one kernel (``ops/splash_backward``, PR 43):
interpreted on the CPU against the float32 reference's gradients and against
the library's two kernels on the same inputs, the list of visited block pairs
against the library's own mask info, and the route read off a call's inputs."""

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shuffle_exchange_tpu.ops import splash_backward as sb

fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")   # the module, not the function

_BF16 = jnp.bfloat16

# the five cells' head geometry at a T of several blocks (640 = 5 x 128):
# (query heads, key heads, score width, value width, window)
GEOMETRY = {
    "mha-192-128": (2, 2, 192, 128, 0),        # kanana2-train
    "8-over-2-at-64": (8, 2, 64, 64, 0),       # lfm2-train
    "8-over-2-at-128": (8, 2, 128, 128, 0),    # mistral7b-zero3-x4
    "6-over-1-at-128": (6, 1, 128, 128, 0),    # laguna-train, full layers
    "8-over-1-window": (8, 1, 128, 128, 200),  # laguna-train, window layers
    "8-over-1-at-256": (8, 1, 256, 256, 0),    # qwen3next-train
    "mha-at-64": (4, 4, 64, 64, 0),            # gpt2m-train (PR 56)
    "mha-at-128": (4, 4, 128, 128, 0),         # olmoe-train (PR 56)
}


def _inputs(T, H, KV, D, Dv, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((B, T, H, D), (B, T, KV, D), (B, T, KV, Dv), (B, T, H, Dv))
    return [jax.random.normal(kk, s, jnp.float32).astype(_BF16)
            for kk, s in zip(ks, shapes)]


def _gradients(attend, q, k, v, do):
    loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                   * do.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _launches(jaxpr, kernel):
    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    return sum(1 for e in equations(jaxpr) if e.primitive.name == "pallas_call"
               and e.params["name"] == kernel)


def _distances(got, want):
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(got, want)]


@pytest.mark.parametrize("geometry, T", [(g, 640) for g in GEOMETRY] + [
    ("mha-192-128", 600), ("8-over-1-window", 600)])   # 600: the causal pad
def test_fused_backward_matches_float32_and_the_two_kernels(
        geometry, T, monkeypatch):
    """dq, dk and dv of the fused kernel, interpreted, at bf16 inputs: each
    within the two-kernel route's own distance from the float32 reference's
    gradients (of the same rounded inputs), and next to the two kernels'
    own values; the gradient's program holds the fused kernel and neither of
    the library's. T = 600 goes through ``pallas_attention``'s causal pad."""
    H, KV, D, Dv, window = GEOMETRY[geometry]
    q, k, v, do = _inputs(T, H, KV, D, Dv)
    monkeypatch.setattr(fa, "splash_attention_gqa", functools.partial(
        fa.splash_attention_gqa, interpret=True))
    attend = lambda q, k, v: fa.pallas_attention(q, k, v, causal=True, window=window)
    assert fa._pallas_kernel(q, k, v, window) != "stock_flash"

    want = _gradients(lambda q, k, v: fa.reference_attention(
        q, k, v, window=window), *(x.astype(jnp.float32) for x in (q, k, v)), do)
    fused = _gradients(attend, q, k, v, do)
    jaxpr = jax.make_jaxpr(functools.partial(_gradients, attend))(q, k, v, do).jaxpr
    assert _launches(jaxpr, sb.KERNEL_NAME) == 1
    assert _launches(jaxpr, "splash_mqa_fwd_residuals") == 1
    assert not _launches(jaxpr, "splash_mqa_dkv_no_residuals")
    assert not _launches(jaxpr, "splash_mqa_dq_no_residuals")

    monkeypatch.setattr(fa, "attention_backward_route",
                        lambda *a, **kw: "splash_two_kernels")
    two = _gradients(attend, q, k, v, do)
    jaxpr = jax.make_jaxpr(functools.partial(_gradients, attend))(q, k, v, do).jaxpr
    assert not _launches(jaxpr, sb.KERNEL_NAME)
    assert _launches(jaxpr, "splash_mqa_dkv_no_residuals") == 1

    for name, near, far, a, b in zip("qkv", _distances(fused, want),
                                     _distances(two, want), fused, two):
        assert a.dtype == _BF16 and a.shape == b.shape
        assert 0 < near <= 1.05 * far + 1e-4, (name, near, far)
        # bf16's step is 2**-8 of a value: the two routes round the same sums
        assert _distances([a], [b.astype(jnp.float32)])[0] < 2 ** -7, name


@pytest.mark.parametrize("T, window", [(16384, 512), (16384, 0), (8192, 0),
                                       (4096, 0), (2048, 768), (640, 200)])
def test_visited_pairs_are_the_ones_the_librarys_mask_visits(T, window):
    """The grid of the fused kernel has one step for each (query block, key
    block) pair the library's mask info marks non-empty at the same blocks
    (63 of 528 for a window of 512 at 16,384: ``block_visit_share``), the
    partly masked ones flagged as the library flags them."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    blk = fa._splash_blocks(T, T, window, 2)[0]
    pairs = sb.visited_pairs(T, T, blk, blk, window)
    kernel = sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([fa.splash_mask(T, T, True, window)]),
        block_sizes=sa.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk))
    n = T // blk
    dense = np.zeros((n, n), np.int64)
    dense[pairs[:, 0], pairs[:, 1]] = np.where(pairs[:, 2] & 4, 1, 2)
    info = kernel.fwd_mask_info
    if info.block_mask is not None and info.block_mask.shape[-1] == n:
        np.testing.assert_array_equal(dense, np.asarray(info.block_mask)[0])
    share = 100.0 * len(pairs) / (n * (n + 1) // 2)
    assert share == pytest.approx(fa.block_visit_share(T, window))
    # query-major, each query block's first and last pair flagged once
    assert (np.diff(pairs[:, 0]) >= 0).all()
    assert (pairs[:, 2] & 1 > 0).sum() == (pairs[:, 2] & 2 > 0).sum() == n


def _call(T, H, KV, D, Dv=None, B=1, dtype=_BF16):
    sds = jax.ShapeDtypeStruct
    return (sds((B, T, H, D), dtype), sds((B, T, KV, D), dtype),
            sds((B, T, KV, Dv or D), dtype))


CELLS = {
    "mistral7b-zero3-x4": (_call(4096, 32, 8, 128), 0, "splash"),
    "qwen3next-train": (_call(8192, 16, 2, 256), 0, "splash"),
    "kanana2-train": (_call(8192, 32, 32, 192, 128, B=2), 0, "splash_own_v"),
    "laguna-train-full": (_call(16384, 48, 8, 128), 0, "splash"),
    "laguna-train-window": (_call(16384, 64, 8, 128), 512, "splash_window"),
    "lfm2-train": (_call(4096, 32, 8, 64, B=8), 0, "splash"),
    # MHA on one device: the same kernels at a group of one (PR 56)
    "gpt2m-train": (_call(1024, 16, 16, 64, B=4), 0, "splash"),
    "olmoe-train": (_call(4096, 16, 16, 128, B=4), 0, "splash"),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cells_real_shapes_take_the_fused_backward(cell):
    (q, k, v), window, route = CELLS[cell]
    assert fa.attention_backward_route(q, k, v, True, window) == "fused_resident_dkv"
    # the forward's route, which four benchmark drivers print, is what it was
    assert fa.attention_route(q, k, v, True, "pallas", window) == route
    assert sb.vmem_bytes(k.shape[1], q.shape[-1], v.shape[-1], *fa._splash_blocks(
        q.shape[1], k.shape[1], window, 2)) <= sb.VMEM_BUDGET_BYTES


def test_the_per_shard_mha_call_keeps_the_stock_kernels(devices8):
    """``olmohybrid-zero3-x4``'s attention layer, 30 heads of 128 at 8,192 a
    shard of ZeRO-3's mesh over four devices: the stock family, whose
    backward nothing here names; the same call off the mesh is "splash"."""
    from shuffle_exchange_tpu.config.config import MeshConfig
    from shuffle_exchange_tpu.parallel.mesh import MeshTopology, kernel_mesh

    q, k, v = _call(8192, 30, 30, 128, B=2)
    assert fa.attention_route(q, k, v, True, "pallas") == "splash"
    with kernel_mesh(MeshTopology.build(MeshConfig(fsdp=4),
                                        devices=devices8[:4]).mesh):
        assert fa.attention_route(q, k, v, True, "pallas") == "stock_flash"
        # GQA on the same mesh: what it was (mistral7b-zero3-x4)
        assert fa.attention_route(*CELLS["mistral7b-zero3-x4"][0], True,
                                  "pallas") == "splash"


@pytest.mark.parametrize("heads", [(32, 8, 128), (16, 16, 64), (16, 16, 128)],
                         ids=["gqa", "mha-at-64", "mha-at-128"])
@pytest.mark.parametrize("why", ["segment_ids", "mask_np", "float32",
                                 "non_causal", "beyond_vmem", "queries_past_keys"])
def test_what_the_fused_backward_does_not_take_keeps_the_two_kernels(why, heads):
    """Read off the call, not set: segment ids, a blocksparse layout's
    ``mask_np``, 4-byte inputs, a non-causal call, a sequence whose dk and dv
    would not fit VMEM, and more queries than keys keep the library's two
    kernels; GQA, and MHA off a kernel mesh (PR 56) alike."""
    call = lambda T, **kw: _call(T, *heads, **kw)
    q, k, v = call(4096)
    causal, extra = True, {}
    if why == "segment_ids":
        extra["segment_ids"] = np.zeros((1, 4096), np.int32)
    elif why == "mask_np":
        extra["mask_np"] = np.ones((4096, 4096), bool)
    elif why == "float32":
        q, k, v = call(4096, dtype=jnp.float32)
    elif why == "non_causal":
        causal = False
    elif why == "beyond_vmem":
        q, k, v = call(65536 * (2 if heads[2] == 64 else 1))
    else:
        k = v = call(2048)[1]
    assert fa.attention_backward_route(
        q, k, v, causal, 0, **extra) == "splash_two_kernels"
    assert fa.attention_route(q, k, v, causal, "pallas") == "splash"


def test_float32_inputs_run_the_two_kernels_untouched():
    """The two-kernel route is today's code path: a float32 call's gradient
    program holds the library's kernels and not the fused one."""
    q, k, v, do = (x.astype(jnp.float32) for x in _inputs(256, 4, 2, 128, 128))
    attend = lambda q, k, v: fa.splash_attention_gqa(q, k, v, interpret=True)
    jaxpr = jax.make_jaxpr(functools.partial(_gradients, attend))(q, k, v, do).jaxpr
    assert not _launches(jaxpr, sb.KERNEL_NAME)
    for kernel in ("splash_mqa_fwd_residuals", "splash_mqa_dkv_no_residuals",
                   "splash_mqa_dq_no_residuals"):
        assert _launches(jaxpr, kernel) == 1, kernel


@pytest.mark.parametrize("policy", ["listed", "not_listed"])
def test_a_policy_that_lists_the_residuals_enters_the_fused_backward_from_them(
        policy):
    """Under a ``jax.checkpoint`` whose policy lists ``SPLASH_RESIDUALS`` the
    gradient's program runs the forward kernel once and the fused backward
    from the kept ``out`` / ``logsumexp``; under one that does not, the
    forward kernel runs again in the replay (PR 36's contract, kept)."""
    from shuffle_exchange_tpu.models.transformer import _keeping_splash_residuals

    q, k, v, do = _inputs(256, 4, 2, 128, 128)
    nothing = jax.checkpoint_policies.nothing_saveable
    layer = jax.checkpoint(
        lambda q, k, v: fa.splash_attention_gqa(q, k, v, interpret=True),
        policy=_keeping_splash_residuals(nothing) if policy == "listed" else nothing)

    def stack(q, k, v):
        # two layers as one scan body, as the model's: the scan's partial
        # evaluation drops what the replay does not need
        return jax.lax.scan(lambda x, _: (x + layer(x, k, v), None), q, None,
                            length=2)[0]

    jaxpr = jax.make_jaxpr(functools.partial(_gradients, stack))(q, k, v, do).jaxpr
    assert _launches(jaxpr, "splash_mqa_fwd_residuals") == (
        1 if policy == "listed" else 2)
    assert _launches(jaxpr, sb.KERNEL_NAME) == 1
