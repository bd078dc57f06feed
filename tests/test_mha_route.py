"""Multi-head attention's kernel family follows the kernel mesh (PR 56): on
one device the splash forward and the one fused backward kernel at a group
of one, per shard of a mesh of several devices the stock flash kernels
(``ops/flash_attention._pallas_kernel`` says why). ``attention_route`` and
the dispatcher are held to one answer by the kernels' own names in the
gradient's program, and the group of one to the float32 reference in
interpret mode at ``gpt2m-train``'s shape, the ragged one beside it, and the
calls that fall back to the library's two backward kernels."""

import contextlib
import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_attention_backward import _distances, _gradients, _inputs

from shuffle_exchange_tpu.config.config import MeshConfig
from shuffle_exchange_tpu.ops import splash_backward as sb
from shuffle_exchange_tpu.parallel import mesh as mesh_lib
from shuffle_exchange_tpu.parallel.mesh import MeshTopology

fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")   # the module, not the function

_BF16 = jnp.bfloat16
SPLASH = {"splash_mqa_fwd_residuals", sb.KERNEL_NAME}
# the stock family's launches carry no ``name``: by their kernel functions
STOCK = {"_flash_attention_kernel", "_flash_attention_dkv_kernel",
         "_flash_attention_dq_kernel"}


def kernel_mesh_of(devices, n):
    """``kernel_mesh`` over ``n`` host devices (ZeRO-3's layout, as
    ``olmohybrid-zero3-x4``'s), or no kernel mesh at all for ``n`` = 0."""
    if not n:
        return contextlib.nullcontext()
    return mesh_lib.kernel_mesh(
        MeshTopology.build(MeshConfig(fsdp=n), devices=devices[:n]).mesh)


def _sds(B, T, H, D, dtype=_BF16):
    return jax.ShapeDtypeStruct((B, T, H, D), dtype)


def _kernels(jaxpr):
    """The ``name`` of every ``pallas_call`` in ``jaxpr``, nested ones too
    (the kernel function's own name where the call gave none)."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"]
                      or eqn.params["jaxpr"].debug_info.func_name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _kernels(sub)
    return names


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("n_devices", [0, 1, 4], ids=["no_mesh", "mesh_of_1", "mesh_of_4"])
def test_the_route_and_the_dispatcher_agree_on_and_off_a_mesh(
        monkeypatch, devices8, n_devices, D):
    """MHA at a head of 64 and of 128: "splash" and "fused_resident_dkv"
    where the kernel mesh is one device (or there is none), "stock_flash"
    over four; and the gradient's program of ``flash_attention`` holds the
    kernels of the family ``attention_route`` names, per shard too."""
    from shuffle_exchange_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    q = _sds(4, 256, 4, D)
    want = "stock_flash" if n_devices == 4 else "splash"

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    with kernel_mesh_of(devices8, n_devices):
        assert mesh_lib.kernel_mesh_devices() == max(n_devices, 1)
        assert fa.attention_route(q, q, q) == want
        assert fa.attention_route(q, q, q, impl="pallas") == want
        assert fa.attention_backward_route(q, q, q) == "fused_resident_dkv"
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr
    assert _kernels(jaxpr) == (STOCK if want == "stock_flash" else SPLASH)
    assert mesh_lib.kernel_mesh_devices() == 1            # the context is left


def test_inside_a_region_manual_over_every_axis_the_mesh_still_decides(
        monkeypatch, devices8):
    """``shard_kernel`` hands a kernel back unwrapped inside a region that is
    manual over every axis already; the call there still runs per shard, and
    the route reads the mesh's size, not the wrapping."""
    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    topo = MeshTopology.build(MeshConfig(fsdp=4), devices=devices8[:4])
    q = _sds(4, 256, 4, 64)
    seen = {}

    def inner(q, k, v):
        probe = lambda *a: None
        seen["unwrapped"] = mesh_lib.shard_kernel(probe, (P(),) * 3, P()) is probe
        seen["route"] = fa.attention_route(q, k, v)
        return fa.flash_attention(q, k, v)

    with mesh_lib.kernel_mesh(topo.mesh):
        region = mesh_lib.shard_map(
            inner, mesh=topo.mesh, in_specs=(P("fsdp"),) * 3, out_specs=P("fsdp"),
            axis_names=set(topo.mesh.axis_names), check_vma=False)
        jaxpr = jax.make_jaxpr(region)(q, q, q).jaxpr
    assert seen == {"unwrapped": True, "route": "stock_flash"}
    assert _kernels(jaxpr) == {"_flash_attention_kernel"}


@pytest.mark.parametrize("n_devices", [0, 4], ids=["no_mesh", "mesh_of_4"])
def test_the_kill_switch_and_the_other_routes_do_not_read_the_mesh(
        monkeypatch, devices8, n_devices):
    """``SXT_DISABLE_SPLASH`` keeps its meaning (repeat-KV + the stock
    kernel, GQA too); GQA, values of their own width and a window took a
    splash route before and take it on a mesh as off it."""
    q, kv, v128 = _sds(4, 256, 8, 128), _sds(4, 256, 2, 128), _sds(4, 256, 8, 128)
    wide = _sds(4, 256, 8, 192)
    with kernel_mesh_of(devices8, n_devices):
        assert fa._pallas_kernel(q, kv, kv) == "splash"
        assert fa._pallas_kernel(wide, wide, v128) == "splash_own_v"
        assert fa._pallas_kernel(q, q, q, window=128) == "splash_window"
        monkeypatch.setenv("SXT_DISABLE_SPLASH", "1")
        assert fa._pallas_kernel(q, kv, kv) == "stock_flash"
        assert fa._pallas_kernel(q, q, q) == "stock_flash"
        assert fa._pallas_kernel(wide, wide, v128) == "splash_own_v"


# name -> (B, T, heads, head size, dtype, causal, segments, backward route);
# the distances PR 53's chip run read for these calls are 0.0024-0.0052 of
# the float32 reference's largest value in bf16 (chiprun_out/pr53/attn_bench.jsonl)
GROUP_OF_ONE = {
    "gpt2m-4x1024x16x64": (4, 1024, 16, 64, _BF16, True, False, "fused_resident_dkv"),
    "ragged-4x1023x16x64": (4, 1023, 16, 64, _BF16, True, False, "fused_resident_dkv"),
    "short-2x128x4x64": (2, 128, 4, 64, _BF16, True, False, "fused_resident_dkv"),
    "at-128-2x640x4x128": (2, 640, 4, 128, _BF16, True, False, "fused_resident_dkv"),
    "float32-2x512x4x64": (2, 512, 4, 64, jnp.float32, True, False, "splash_two_kernels"),
    "segments-2x512x4x64": (2, 512, 4, 64, _BF16, True, True, "splash_two_kernels"),
    "non_causal-2x512x4x128": (2, 512, 4, 128, _BF16, False, False, "splash_two_kernels"),
}


@pytest.mark.parametrize("call", list(GROUP_OF_ONE))
def test_the_group_of_one_matches_the_float32_reference(call, monkeypatch):
    """Forward and dq, dk, dv of a one-device MHA call through
    ``pallas_attention``, the kernels interpreted, against the float32
    reference on the same rounded inputs: bf16 within 8e-3 of the
    reference's largest value (a bf16 step is 2**-8 of a value), float32
    within 1e-5; the backward is the route ``attention_backward_route``
    names, by the kernels in the gradient's program."""
    B, T, H, D, dtype, causal, segments, backward = GROUP_OF_ONE[call]
    q, k, v, do = (x.astype(dtype) for x in _inputs(T, H, H, D, D, B=B))
    kw = {"causal": causal}
    if segments:
        kw["segment_ids"] = jnp.repeat(jnp.arange(2), T // 2)[None].repeat(B, 0)
    attend = functools.partial(fa.pallas_attention, **kw)
    reference = functools.partial(fa.reference_attention, **kw)
    monkeypatch.setattr(fa, "splash_attention_gqa", functools.partial(
        fa.splash_attention_gqa, interpret=True))
    assert fa._pallas_kernel(q, k, v) == "splash"
    assert fa.attention_backward_route(
        q, k, v, causal, 0, kw.get("segment_ids")) == backward

    wide = [x.astype(jnp.float32) for x in (q, k, v)]
    out = jax.jit(attend)(q, k, v)
    got = jax.jit(functools.partial(_gradients, attend))(q, k, v, do)
    want_out = reference(*wide)
    want = _gradients(reference, *wide, do)
    assert out.shape == q.shape and out.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 8e-3
    for name, d in zip(("out", "dq", "dk", "dv"),
                       _distances([out, *got], [want_out, *want])):
        assert 0 <= d < tol, (name, d)
    jaxpr = jax.make_jaxpr(functools.partial(_gradients, attend))(q, k, v, do).jaxpr
    seg = "_segmented" if segments else ""
    assert _kernels(jaxpr) == (SPLASH if backward == "fused_resident_dkv" else {
        f"splash_mqa_fwd{seg}_residuals", f"splash_mqa_dkv{seg}_no_residuals",
        f"splash_mqa_dq{seg}_no_residuals"})


def test_an_ensembles_vmapped_call_takes_the_group_of_one(monkeypatch):
    """Ensemble replicas trace with no kernel mesh and call the kernels
    under ``jax.vmap``: the group of one batches like any splash call."""
    q, k, v, do = (jnp.stack([x, x[::-1]]) for x in _inputs(256, 2, 2, 64, 64, B=2))
    monkeypatch.setattr(fa, "splash_attention_gqa", functools.partial(
        fa.splash_attention_gqa, interpret=True))
    got = jax.jit(jax.vmap(functools.partial(_gradients, fa.pallas_attention)))(q, k, v, do)
    want = jax.vmap(functools.partial(_gradients, fa.reference_attention))(
        *(x.astype(jnp.float32) for x in (q, k, v)), do)
    assert max(_distances(got, want)) < 8e-3


@pytest.mark.parametrize("n_devices", [1, 4], ids=["mesh_of_1", "mesh_of_4"])
def test_the_trainers_mha_step_runs_the_family_its_mesh_names(
        monkeypatch, devices8, n_devices):
    """Through ``sxt.initialize``: a GPT-2-shaped model's train step in bf16
    holds the splash forward and the fused backward on a one-device mesh and
    the stock kernels per shard under ZeRO-3 over four."""
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.models.transformer import TransformerConfig
    from shuffle_exchange_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devices8[:n_devices])
    model = Transformer(TransformerConfig(
        vocab_size=64, d_model=128, n_layers=2, n_heads=2, max_seq_len=128))
    engine = sxt.initialize(
        model=model,
        config={"train_batch_size": 4, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3 if n_devices > 1 else 0},
                "mesh": {"fsdp": n_devices}})[0]
    batch = {"input_ids": np.zeros((4, 129), np.int32)}
    jaxpr = jax.make_jaxpr(engine._train_step)(
        engine.state, engine._reshape_batch(batch), engine._mix_matrix(),
        engine._next_rng_peek(), np.asarray(1.0, np.float32)).jaxpr
    attention = {n for n in _kernels(jaxpr) if "flash" in n or "splash" in n}
    assert attention == (SPLASH if n_devices == 1 else STOCK)
