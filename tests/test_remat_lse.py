"""``save_flash_lse`` remat policy: the backward enters the flash bwd
kernels from SAVED residuals (attention output + logsumexp, named inside
the kernel's custom-vjp forward) instead of re-running forward attention.

CPU-runnable via ``SXT_LSE_INTERPRET=1`` (the lse kernel family executes
under the Pallas interpreter); the TPU Mosaic lowering of the policy path
is gated hostless in ``tests/test_mosaic_lowering.py``.
"""

import dataclasses
import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import shuffle_exchange_tpu.models.transformer as tr
from shuffle_exchange_tpu.models import Transformer, tiny
from shuffle_exchange_tpu.models.transformer import (TransformerConfig,
                                                      _remat_policy)

fa = importlib.import_module("shuffle_exchange_tpu.ops.flash_attention")   # the module, not the function


def _cfg(policy):
    # d=256/heads=4 -> head_dim 64 (kernel-eligible); seq 129 so the
    # label-shifted model T-1 = 128 exercises the exact-tile path while
    # tiny ragged shapes go through the pad-to-128 route in other tests
    return tiny(vocab=128, d=256, layers=2, heads=4, seq=129,
                activation="swiglu", norm="rmsnorm", position="rope",
                remat=True, remat_policy=policy)


def _loss_grads(cfg, batch, rng):
    m = Transformer(cfg)
    params = m.init(jax.random.PRNGKey(0))
    loss = float(m.loss(params, batch, rng))
    grads = jax.grad(lambda p: m.loss(p, batch, rng))(params)
    return loss, grads, m, params


def test_save_flash_lse_gradients_match_default(monkeypatch, devices8):
    """Gradients under save_flash_lse (interpret-mode lse kernels) match
    the default remat policy (reference attention) to tolerance."""
    monkeypatch.setenv("SXT_LSE_INTERPRET", "1")
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, size=(2, 129)).astype(np.int32)}
    rng = jax.random.PRNGKey(1)
    l_lse, g_lse, _, _ = _loss_grads(_cfg("save_flash_lse"), batch, rng)
    monkeypatch.delenv("SXT_LSE_INTERPRET")
    l_ref, g_ref, _, _ = _loss_grads(_cfg("dots_saveable"), batch, rng)
    assert l_lse == pytest.approx(l_ref, rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_lse),
                    jax.tree_util.tree_leaves(g_ref)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-4 * (np.abs(b).max() + 1e-12))


def test_save_flash_lse_skips_forward_recompute(monkeypatch, devices8):
    """The structural claim (why save_attn_seams lost a point and this
    policy does not): under save_flash_lse the flash FORWARD kernel appears
    exactly once in the grad program (primal pass; the recompute's copy is
    DCE'd because both of its outputs — out and lse — are saved residuals),
    so the backward holds only the dq/dkv kernels: 3 pallas calls total.
    Without the save the forward re-runs: 4."""
    monkeypatch.setenv("SXT_LSE_INTERPRET", "1")
    from shuffle_exchange_tpu.ops.flash_attention import flash_attention_remat

    q = jnp.ones((1, 128, 4, 64), jnp.float32)

    def body(q):
        return flash_attention_remat(q, q, q, True, True).astype(
            jnp.float32).sum()

    counts = {}
    for pol in ("save_flash_lse", "nothing_saveable"):
        f = jax.checkpoint(body, policy=_remat_policy(pol))
        counts[pol] = str(jax.make_jaxpr(jax.grad(f))(q)).count("pallas_call")
    assert counts["save_flash_lse"] == 3
    assert counts["nothing_saveable"] == 4

    # and the model-level wiring routes through the kernel: the rematted
    # scan body carries the lse kernels (3 per layer body), while a policy
    # that does not engage the route carries none (reference attention)
    batch = {"input_ids": np.zeros((2, 129), np.int32)}
    rng = jax.random.PRNGKey(1)
    for pol, expect in (("save_flash_lse", 3), ("nothing_saveable", 0)):
        m = Transformer(_cfg(pol))
        params = m.init(jax.random.PRNGKey(0))
        s = str(jax.make_jaxpr(
            jax.grad(lambda p: m.loss(p, batch, rng)))(params))
        assert s.count("pallas_call") == expect, pol


def test_save_flash_lse_ragged_seq_pads(monkeypatch, devices8):
    """Label-shifted ragged T (not a 128 multiple) rides the pad-to-tile
    route; forward matches the unpadded reference attention exactly on the
    real rows."""
    monkeypatch.setenv("SXT_LSE_INTERPRET", "1")
    from shuffle_exchange_tpu.ops.flash_attention import (
        flash_attention_remat, reference_attention)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 100, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 100, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 100, 2, 64)), jnp.float32)
    out = flash_attention_remat(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_save_flash_lse_falls_back_when_ineligible(devices8):
    """Without the interpret knob on a CPU backend the route falls back to
    the standard attention path (policy saves nothing, training still
    correct) — the warning documents it."""
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, size=(2, 65)).astype(np.int32)}
    rng = jax.random.PRNGKey(1)
    cfg = dataclasses.replace(_cfg("save_flash_lse"), max_seq_len=65)
    m = Transformer(cfg)
    params = m.init(jax.random.PRNGKey(0))
    loss = float(m.loss(params, batch, rng))
    assert np.isfinite(loss)
    g = jax.grad(lambda p: m.loss(p, batch, rng))(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))


def test_activation_checkpointing_config_accepts_named_policies():
    from shuffle_exchange_tpu.config.config import SXConfig

    cfg = SXConfig.load({
        "train_batch_size": 8,
        "activation_checkpointing": {"enabled": True,
                                     "policy": "save_flash_lse"},
    })
    assert cfg.activation_checkpointing.policy == "save_flash_lse"


# -- per-half remat keeps the splash kernels' own residuals (PR 36) ---------
#
# The pattern's mixers (``mla``, ``gated_attn``) run the splash kernels, whose
# forward rule names ``out`` and ``logsumexp`` (``SPLASH_RESIDUALS``); under
# per-half remat the mixer half's policy keeps that name, so the replay's
# forward kernel is dead code. The CPU's "reference" route would test nothing
# (PRs 29-30): the tests steer the splash route itself onto the CPU,
# interpreted.

_BLOCK = dict(vocab_size=64, d_model=64, n_layers=2, max_seq_len=257,
              activation="swiglu", norm="rmsnorm", position="rope",
              rope_theta=1e6, norm_eps=1e-6, tie_embeddings=False,
              remat=True, remat_policy="full")
MIXERS = {
    # scores 192 (128 + 64 rotary) / values 128, MHA: "splash_own_v"
    "mla": dict(n_heads=2, head_size=192, rotary_dim=64, rope_interleaved=True,
                mla_kv_rank=32, mla_qk_content_dim=128, mla_qk_rope_dim=64,
                mla_v_dim=128, layer_pattern=(("mla", "mlp"),)),
    # 4 query heads over 2 KV heads: "splash"
    "gated_attn": dict(n_heads=4, n_kv_heads=2, head_size=64,
                       layer_pattern=(("gated_attn", "mlp"),)),
    "gdn": dict(n_heads=4, head_size=16, layer_pattern=(("gdn", "mlp"),),
                gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
                gdn_value_dim=16),
    # the softmax family's layer, checkpointed whole, GQA: "splash"
    "attn": dict(n_heads=4, n_kv_heads=2, head_size=64),
}


@pytest.fixture
def splash_on_cpu(monkeypatch):
    """Every shape takes its Pallas attention route, the splash kernels
    interpreted (steered here, not through an option of the program)."""
    monkeypatch.setattr(fa, "_pallas_ok", lambda q, k, causal=True: True)
    monkeypatch.setattr(fa, "splash_attention_gqa", functools.partial(
        fa.splash_attention_gqa, interpret=True))


def _as_the_parent(monkeypatch, name_too=True):
    """The mixer half's policy without the name; ``name_too``: and a kernel
    that names nothing (the program before PR 36)."""
    monkeypatch.setattr(tr, "_keeping_splash_residuals", lambda policy: policy)
    if name_too:
        monkeypatch.setattr(fa, "SPLASH_RESIDUALS", None)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _launches(jaxpr, kernel):
    return sum(1 for e in _equations(jaxpr) if e.primitive.name == "pallas_call"
               and e.params["name"] == kernel)


def _shape(jaxpr):
    """The program modulo its ``name`` equations."""
    return [(e.primitive.name, [str(v.aval) for v in e.outvars])
            for e in _equations(jaxpr) if e.primitive.name != "name"]


def _model_step(mixer, policy="full"):
    block = dict(_BLOCK, remat_policy=policy)
    model = Transformer(TransformerConfig(**block, **MIXERS[mixer]))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 64, (2, 257)).astype(np.int32)}
    return jax.value_and_grad(lambda p: model.loss(p, batch)), params


@pytest.mark.parametrize("mixer, policy", [
    ("mla", "full"), ("gated_attn", "full"), ("gdn", "full"), ("attn", "full"),
    # a policy that answers Offloadable / Recompute, not a bool (k and v are
    # named "kv" in both mixers): the union is the program's own
    ("mla", "offload_kv_host"), ("gated_attn", "offload_kv_host"),
    ("mla", "dots_saveable"), ("gated_attn", "save_attn_seams"),
    # no policy at all: jax's default keeps nothing, the literal minimum
    ("mla", "none"), ("gated_attn", "none")])
def test_per_half_remat_keeps_the_splash_kernels_residuals(
        mixer, policy, monkeypatch, splash_on_cpu):
    """A mixer half with a splash route (``mla``, ``gated_attn``) under every
    kind of policy: ONE forward launch a layer in the gradient's program
    where the policy without the name holds two, loss and every gradient leaf
    bit-equal; under "none" (no policy: nothing is kept) two, as before. A
    ``gdn`` half (no attention kernel) and a layer of the softmax family (one
    checkpoint a layer, its policy untouched) are the program they were
    before the name existed."""
    step, params = _model_step(mixer, policy)
    jaxpr = jax.make_jaxpr(step)(params).jaxpr
    if mixer in ("gdn", "attn"):
        text = jax.jit(step).lower(params).as_text()
        with monkeypatch.context() as parent:
            _as_the_parent(parent)
            step, params = _model_step(mixer, policy)
            assert _shape(jax.make_jaxpr(step)(params).jaxpr) == _shape(jaxpr)
            if mixer == "gdn":
                # (the interpreter's helper functions are numbered by the
                # state of jax's caches: no text to compare for "attn")
                assert jax.jit(step).lower(params).as_text() == text
        # the softmax family's splash route was there to be named, and its
        # policy does not list the name: forward and replay
        assert _launches(jaxpr, "splash_mqa_fwd_residuals") == (
            2 if mixer == "attn" else 0)
        return
    loss, grads = jax.jit(step)(params)
    with monkeypatch.context() as parent:
        _as_the_parent(parent, name_too=False)
        step, params = _model_step(mixer, policy)
        parent_jaxpr = jax.make_jaxpr(step)(params).jaxpr
        parent_loss, parent_grads = jax.jit(step)(params)
    # the two layers are one scan body: launches a layer
    assert _launches(jaxpr, "splash_mqa_fwd_residuals") == (
        2 if policy == "none" else 1)
    assert _launches(parent_jaxpr, "splash_mqa_fwd_residuals") == 2
    for kernel in ("splash_mqa_dq_no_residuals", "splash_mqa_dkv_no_residuals"):
        assert _launches(jaxpr, kernel) == _launches(parent_jaxpr, kernel) == 1
    assert float(loss) == float(parent_loss) and np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) > 8
    for (path, a), b in zip(leaves, jax.tree.leaves(parent_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), str(path))
    # (a bias leaf the block does not use has a zero gradient in both)
    assert sum(bool(np.any(np.asarray(a))) for _, a in leaves) > 8


@pytest.mark.parametrize("around", ["no_checkpoint", "dots_saveable",
                                    "offload_kv_host"])
def test_the_splash_residuals_name_is_inert_elsewhere(around, monkeypatch):
    """``splash_attention_gqa`` outside any checkpoint, and inside one whose
    policy does not list the name: the program it gave (modulo the ``name``
    equations), the values it gave."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k, v = (jax.random.normal(kk, (1, 256, 2, 64), jnp.float32) for kk in ks[1:])

    def grads():
        def body(q, k, v):
            return jnp.sum(fa.splash_attention_gqa(
                q, k, v, causal=True, interpret=True) ** 2)

        if around != "no_checkpoint":
            body = jax.checkpoint(body, policy=_remat_policy(around))
        f = jax.value_and_grad(body, argnums=(0, 1, 2))
        return jax.make_jaxpr(f)(q, k, v).jaxpr, jax.jit(f)(q, k, v)

    jaxpr, got = grads()
    monkeypatch.setattr(fa, "SPLASH_RESIDUALS", None)
    parent_jaxpr, want = grads()
    names = [e for e in _equations(jaxpr) if e.primitive.name == "name"]
    assert names and not any(
        e.primitive.name == "name" for e in _equations(parent_jaxpr))
    assert _shape(jaxpr) == _shape(parent_jaxpr)
    assert _launches(jaxpr, "splash_mqa_fwd_residuals") == (
        1 if around == "no_checkpoint" else 2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("bf16", [False, True], ids=["float32", "bf16"])
@pytest.mark.parametrize("mixer", ["mla", "gated_attn"])
def test_the_trainer_runs_the_forward_kernel_once_a_layer(
        mixer, bf16, splash_on_cpu, devices8):
    """The same through ``sxt.initialize``: ``activation_checkpointing`` with
    policy "full" from the train_config, ZeRO-3, the kernels inside the
    8-device mesh's ``shard_kernel``: the policy reads the name through the
    shard_map, and the step trains. In bf16 the backward is the one fused
    kernel (``ops/splash_backward``, PR 43), in float32 the library's two."""
    import shuffle_exchange_tpu as sxt

    block = {k: v for k, v in _BLOCK.items() if not k.startswith("remat")}
    model = Transformer(TransformerConfig(**block, **MIXERS[mixer]))
    ids = np.random.default_rng(0).integers(0, 64, (8, 257)).astype(np.int32)
    engine = sxt.initialize(
        model=model,
        config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "bf16": {"enabled": bf16},
                "zero_optimization": {"stage": 3}}, seed=0)[0]
    assert model.config.remat and model.config.remat_policy == "full"
    jaxpr = jax.make_jaxpr(engine._train_step)(
        engine.state, engine._reshape_batch({"input_ids": ids}),
        engine._mix_matrix(), engine._next_rng_peek(),
        np.asarray(1.0, np.float32)).jaxpr
    assert any(e.primitive.name == "shard_map" for e in _equations(jaxpr))
    backward = {"sxt_splash_bwd_fused": int(bf16),
                "splash_mqa_dq_no_residuals": int(not bf16),
                "splash_mqa_dkv_no_residuals": int(not bf16)}
    for kernel, launches in {"splash_mqa_fwd_residuals": 1, **backward}.items():
        assert _launches(jaxpr, kernel) == launches, kernel
    assert np.isfinite(float(engine.train_batch({"input_ids": ids})))
