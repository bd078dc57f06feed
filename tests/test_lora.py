"""LoRA / OptimizedLinear subsystem (reference deepspeed/linear:
optimized_linear.py:76 LoRAOptimizedLinear, quantization.py
QuantizedParameter, config.py LoRAConfig/QuantizationConfig).

Key contracts:
* only LoRA factors and non-target leaves train — the frozen base never
  moves and takes no optimizer state (the requires_grad split + memory win);
* at init (B = 0) the fused forward equals the un-LoRA'd model exactly;
* module_weights()/generate fuse W + (alpha/r) A @ B (reference
  fuse_lora-before-rollout in the hybrid engine);
* the frozen base can be stored int8-quantized (QuantizedParameter analog);
* checkpoints carry the base separately and can drop it
  (exclude_frozen_parameters -> adapter-only checkpoint).
"""

import numpy as np
import pytest


def _build(vocab=64, d=32, layers=2, heads=2, seq=32, **cfg_extra):
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer, tiny

    model = Transformer(tiny(vocab=vocab, d=d, layers=layers, heads=heads, seq=seq,
                             activation="swiglu", norm="rmsnorm", position="rope"))
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "steps_per_print": 10**9,
    }
    cfg.update(cfg_extra)
    engine, *_ = sxt.initialize(model=model, config=cfg)
    return model, engine


def _batch(vocab=64, b=8, t=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(b, t)).astype(np.int32)}


def _leaf_paths(tree):
    import jax

    return {"/".join(str(getattr(p, "key", p)) for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# -- config ----------------------------------------------------------------

def test_lora_config_aliases_and_validation():
    from shuffle_exchange_tpu.config import ConfigError, SXConfig

    c = SXConfig.load({"train_batch_size": 8,
                       "lora": {"enabled": True, "r": 8, "alpha": 32}}, 1)
    assert c.lora.lora_r == 8 and c.lora.lora_alpha == 32.0
    with pytest.raises(ConfigError):
        SXConfig.load({"train_batch_size": 8,
                       "lora": {"enabled": True, "q_bits": 3}}, 1)
    with pytest.raises(ConfigError):
        SXConfig.load({"train_batch_size": 8,
                       "lora": {"enabled": True, "delay_lora_init": True}}, 1)


def test_reference_target_mod_names_map():
    from shuffle_exchange_tpu.linear import normalize_targets

    t = normalize_targets(["q_proj", "down_proj", "wk"])
    assert t == frozenset({"wq", "w_down", "wk"})


# -- pure transforms -------------------------------------------------------

def test_split_merge_identity_at_init():
    """B = 0 => merged weights equal the base exactly (reference init:
    lora_weight_2 zeros, optimized_linear.py:157)."""
    import jax.numpy as jnp

    from shuffle_exchange_tpu.linear import (LoRAConfig, dequantize_frozen,
                                             lora_merge, lora_split)

    rng = np.random.default_rng(0)
    p = {"layers": {"wq": rng.standard_normal((2, 16, 24)).astype(np.float32),
                    "ln1_w": np.ones((2, 16), np.float32)}}
    t, f = lora_split(p, LoRAConfig(lora_r=4), rng=rng)
    assert set(t["layers"]["wq"].keys()) == {"lora_a", "lora_b"}
    t16 = {"layers": {"wq": {k: jnp.asarray(v) for k, v in t["layers"]["wq"].items()},
                      "ln1_w": jnp.asarray(t["layers"]["ln1_w"])}}
    merged = lora_merge(t16, dequantize_frozen(f, jnp.float32), 2.0)
    np.testing.assert_allclose(np.asarray(merged["layers"]["wq"]), p["layers"]["wq"],
                               rtol=1e-6)
    # nonzero B shifts by scaling * A @ B
    t16["layers"]["wq"]["lora_b"] = jnp.ones_like(t16["layers"]["wq"]["lora_b"])
    merged2 = lora_merge(t16, dequantize_frozen(f, jnp.float32), 2.0)
    want = p["layers"]["wq"] + 2.0 * np.asarray(
        jnp.matmul(t16["layers"]["wq"]["lora_a"], t16["layers"]["wq"]["lora_b"]))
    np.testing.assert_allclose(np.asarray(merged2["layers"]["wq"]), want, rtol=1e-5)


def test_split_requires_a_target_hit():
    from shuffle_exchange_tpu.linear import LoRAConfig, lora_split

    with pytest.raises(ValueError):
        lora_split({"embed": np.ones((4, 4), np.float32)}, LoRAConfig())


def test_optimized_linear_standalone_parity():
    """Single-matrix OptimizedLinear API: fresh lora output == plain linear
    (B = 0); quantized base stays close."""
    import jax
    import jax.numpy as jnp

    from shuffle_exchange_tpu.linear import (LoRAConfig, QuantizationConfig,
                                             apply_optimized_linear,
                                             init_optimized_linear)

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32), jnp.float32)
    plain, _ = init_optimized_linear(key, 32, 16, dtype=jnp.float32)
    y0 = apply_optimized_linear(x, plain, {})
    lc = LoRAConfig(lora_r=4)
    t, f = init_optimized_linear(key, 32, 16, lora_config=lc, dtype=jnp.float32)
    y1 = apply_optimized_linear(x, t, f, lc)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-5, atol=1e-5)
    tq, fq = init_optimized_linear(key, 32, 16, lora_config=lc,
                                   quantization_config=QuantizationConfig(group_size=16),
                                   dtype=jnp.float32)
    y2 = apply_optimized_linear(x, tq, fq, lc)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=0.1, atol=0.15)


# -- engine integration ----------------------------------------------------

def test_lora_only_factors_and_nontargets_update():
    import jax

    _, engine = _build(lora={"enabled": True, "r": 4, "alpha": 8})
    m0 = _leaf_paths(jax.device_get(engine.state.master))
    f0 = _leaf_paths(jax.device_get(engine.state.frozen))
    assert any("lora_a" in k for k in m0)
    # target bases left the trainable tree entirely
    assert not any(k.endswith(("layers/wq", "layers/w_up")) for k in m0)
    assert any(k.endswith("layers/wq") for k in f0)

    batch = _batch()
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0]

    m1 = _leaf_paths(jax.device_get(engine.state.master))
    f1 = _leaf_paths(jax.device_get(engine.state.frozen))
    lora_moved = [k for k in m0 if "lora_a" in k and not np.allclose(m0[k], m1[k])]
    assert lora_moved, "lora A factors never updated"
    for k in f0:  # frozen base is bit-identical after training
        np.testing.assert_array_equal(np.asarray(f0[k]), np.asarray(f1[k]))


def test_lora_optimizer_state_excludes_base():
    """The Adam moments cover ONLY the trainable tree — no leaf in the
    optimizer state has the shape of a frozen base weight (the reference's
    optimizer-memory win from requires_grad=False)."""
    import jax

    _, engine = _build(lora={"enabled": True, "r": 4})
    base_shapes = {np.asarray(l).shape
                   for l in jax.tree_util.tree_leaves(jax.device_get(engine.state.frozen))}
    opt_shapes = {tuple(l.shape)
                  for l in jax.tree_util.tree_leaves(engine.state.opt_state)
                  if hasattr(l, "shape") and getattr(l, "ndim", 0) >= 2}
    assert base_shapes and not (base_shapes & opt_shapes)


def test_lora_init_loss_matches_plain_model():
    """At init the fused model IS the plain model (B = 0) — same eval loss
    to bf16 tolerance, proving the merge produces the right forward."""
    _, plain = _build()
    _, lora = _build(lora={"enabled": True, "r": 4, "alpha": 16})
    b = _batch(seed=3)
    l0 = float(plain.eval_batch(b))
    l1 = float(lora.eval_batch(b))
    assert abs(l0 - l1) < 0.05, (l0, l1)


def test_lora_quantized_base_trains():
    _, engine = _build(lora={"enabled": True, "r": 4, "quantize_base": True,
                             "group_size": 16})
    from shuffle_exchange_tpu.ops.quant_matmul import QuantizedMatrix

    import jax

    leaves = jax.tree_util.tree_leaves(
        engine.state.frozen, is_leaf=lambda x: isinstance(x, QuantizedMatrix))
    assert any(isinstance(l, QuantizedMatrix) for l in leaves)
    batch = _batch()
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    # module_weights dequantizes + fuses into dense model-structured weights
    w = engine.module_weights()
    assert np.asarray(w["layers"]["wq"]).ndim == 3


def test_lora_zero3_mesh(devices8):
    """LoRA under ZeRO-3 fsdp sharding: frozen base sharded over fsdp
    (base_weight_sharding analog), training runs on the 8-device mesh."""
    _, engine = _build(
        lora={"enabled": True, "r": 4},
        zero_optimization={"stage": 3},
        mesh={"fsdp": 4, "data": -1},
    )
    batch = _batch()
    l0 = float(engine.train_batch(batch))
    l1 = float(engine.train_batch(batch))
    assert np.isfinite(l0) and np.isfinite(l1)


def test_lora_checkpoint_roundtrip_and_adapter_only(tmp_path):
    import jax

    _, engine = _build(lora={"enabled": True, "r": 4})
    batch = _batch()
    for _ in range(3):
        engine.train_batch(batch)
    loss_before = float(engine.eval_batch(batch))
    engine.save_checkpoint(str(tmp_path / "full"))
    # adapter-only: no frozen item on disk
    engine.save_checkpoint(str(tmp_path / "adapter"), exclude_frozen_parameters=True)
    full_tag_dir = next(d for d in (tmp_path / "full").iterdir() if d.is_dir())
    adapter_tag_dir = next(d for d in (tmp_path / "adapter").iterdir() if d.is_dir())
    assert (full_tag_dir / "frozen").exists()
    assert not (adapter_tag_dir / "frozen").exists()

    _, fresh = _build(lora={"enabled": True, "r": 4})
    fresh.load_checkpoint(str(tmp_path / "full"))
    np.testing.assert_allclose(float(fresh.eval_batch(batch)), loss_before,
                               rtol=1e-5)
    f_old = jax.tree_util.tree_leaves(jax.device_get(engine.state.frozen))
    f_new = jax.tree_util.tree_leaves(jax.device_get(fresh.state.frozen))
    for a, b in zip(f_old, f_new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lora_hybrid_engine_fused_rollout_parity():
    """RLHF story (reference hybrid engine fuse_lora/unfuse_lora): rollouts
    generate from the FUSED current weights — identical to a fresh inference
    engine built from module_weights()."""
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.inference import InferenceConfig, InferenceEngine
    from shuffle_exchange_tpu.models import Transformer, tiny

    model = Transformer(tiny(vocab=64, d=32, layers=2, heads=2, seq=32))
    engine, *_ = sxt.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "lora": {"enabled": True, "r": 4, "alpha": 8},
        "hybrid_engine": {"enabled": True, "max_out_tokens": 8,
                          "inference_config": {"dtype": "float32"}},
        "steps_per_print": 10**9,
    })
    for _ in range(4):
        engine.train_batch(_batch(seed=2))
    prompts = _batch(t=8, seed=1)["input_ids"]
    out = engine.generate(prompts, max_new_tokens=6)
    ref = InferenceEngine(model, engine.module_weights(consensus=True),
                          InferenceConfig(dtype="float32", max_seq_len=32))
    ref_out = ref.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


def test_lora_ensemble_requires_explicit_opt_in(devices8):
    """The default config REJECTS lora x shuffle_exchange (ADVICE r5 #5):
    factor-space per-tensor mixing is a semantic change from the round-4
    hard fail, so it must be asked for by name."""
    import pytest
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.config import ConfigError
    from shuffle_exchange_tpu.models import Transformer, tiny

    model = Transformer(tiny(vocab=64, d=32, layers=2, heads=2, seq=32))
    with pytest.raises(ConfigError, match="ensemble_factor_mixing"):
        sxt.initialize(model=model, config={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "lora": {"enabled": True, "lora_r": 4},
            "steps_per_print": 10**9,
        }, method="RR", rings=2)


def test_lora_composes_with_ensemble_mode(devices8):
    """lora x shuffle_exchange (round 5, lifted from document-and-reject;
    round 6: behind lora.ensemble_factor_mixing): the reference's sync
    averages the trainable bit16 partitions — with deepspeed/linear LoRA
    those ARE the factor tensors — so factor-space per-tensor mixing is the
    reference behavior. Frozen base stays replica-free; synchronization()
    converges the factor replicas."""
    import jax
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer, tiny

    model = Transformer(tiny(vocab=64, d=32, layers=2, heads=2, seq=32))
    engine, *_ = sxt.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "lora": {"enabled": True, "lora_r": 4,
                 "ensemble_factor_mixing": True},
        "steps_per_print": 10**9,
    }, method="RR", rings=2)
    assert engine.ensemble and engine.replicas > 1
    R = engine.replicas

    # factors carry the replica dim; the frozen base must NOT
    f_leaves = jax.tree_util.tree_leaves(engine.state.master)
    assert all(l.shape[0] == R for l in f_leaves)
    froz_shapes = [l.shape for l in jax.tree_util.tree_leaves(engine.state.frozen)
                   if hasattr(l, "shape")]
    assert froz_shapes and all(s[0] != R or len(s) < 2 for s in froz_shapes)

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, size=(8, 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(10)]
    assert losses[-1] < losses[0], losses

    engine.synchronization()
    m = jax.device_get(jax.tree_util.tree_leaves(engine.state.master)[0])
    for r in range(1, R):
        np.testing.assert_allclose(m[0], m[r], rtol=1e-5, atol=1e-6)


def test_disabled_lora_section_skips_validation():
    """A ported reference config can carry delay_lora_init/odd q_bits as
    long as the section is off."""
    from shuffle_exchange_tpu.config import SXConfig

    c = SXConfig.load({"train_batch_size": 8,
                       "lora": {"enabled": False, "delay_lora_init": True,
                                "q_bits": 3}}, 1)
    assert not c.lora.enabled


def test_lora_with_qw_emulation_targets_base_not_factors():
    """ZeRO++ qwZ under lora rounds the FROZEN BASE (the tensor the real
    wire would gather), not the rank-r factors: at init (B=0) the qw run
    differs from the no-qw run by base rounding only."""
    _, eng_plain = _build(lora={"enabled": True, "r": 4})
    _, eng_qw = _build(lora={"enabled": True, "r": 4},
                       zero_optimization={"stage": 2,
                                          "zero_quantized_weights": True})
    b = _batch(seed=5)
    l_plain = float(eng_plain.eval_batch(b))
    l_qw = float(eng_qw.eval_batch(b))
    # int8 group-2048 rounding moves the loss a little but not wildly
    assert abs(l_plain - l_qw) < 0.2
    losses = [float(eng_qw.train_batch(b)) for _ in range(4)]
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("mesh", [{"tensor": 2, "data": -1},
                                  {"seq": 2, "data": -1}])
def test_lora_composes_with_model_axes(devices8, mesh):
    """LoRA x tensor and LoRA x sequence parallelism track the plain-DP
    LoRA trajectory exactly (the merge happens at the params level before
    the model's sharded compute, so model axes are orthogonal)."""
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer, tiny
    from shuffle_exchange_tpu.parallel import reset_topology

    def run(m):
        reset_topology()
        model = Transformer(tiny(vocab=64, d=64, layers=2, heads=4, seq=32,
                                 n_kv_heads=2))
        engine, *_ = sxt.initialize(model=model, config={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "mesh": m, "lora": {"enabled": True, "r": 4, "alpha": 8},
            "steps_per_print": 10**9})
        b = _batch()
        return [float(engine.train_batch(b)) for _ in range(3)]

    # bf16 trajectories under a resharded mesh drift ~0.7%/step on the
    # CPU backend (different reduction schedules); the trajectory is what
    # is being pinned, not the last bit
    np.testing.assert_allclose(run(mesh), run({"data": -1}), rtol=2e-2)


def test_lora_composes_with_pipeline(devices8):
    """LoRA x pipeline parallelism: the fused weights thread through the
    pipe stage loss unchanged — exact DP parity."""
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer, tiny
    from shuffle_exchange_tpu.parallel import reset_topology

    def run(m):
        reset_topology()
        model = Transformer(tiny(vocab=64, d=32, layers=4, heads=2, seq=32))
        engine, *_ = sxt.initialize(model=model, config={
            "train_batch_size": 32, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "mesh": m, "lora": {"enabled": True, "r": 4},
            "steps_per_print": 10**9})
        b = _batch(b=32)
        return [float(engine.train_batch(b)) for _ in range(3)]

    # the pipeline region reduces the CE with a different
    # association than the auto-sharded dense step; lr=1e-2 Adam amplifies
    np.testing.assert_allclose(run({"pipe": 2, "data": -1}),
                               run({"data": -1}), rtol=2e-2)
