"""ZeRO++ (hpZ / qwZ / qgZ) and MiCS sharding policies (SURVEY.md §2.6
ZeRO++ row; runtime/zero/config.py knobs; mics.py)."""

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.parallel import reset_topology
from shuffle_exchange_tpu.models import Transformer, tiny


def _base_config(**zero):
    z = {"stage": 3}
    z.update(zero)
    return {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": z,
        "steps_per_print": 10**9,
    }


def _model():
    return Transformer(tiny(vocab=128, d=64, layers=2, heads=4, seq=32))


def _batch(b=8, t=32):
    return {"input_ids": np.random.default_rng(0).integers(0, 128, size=(b, t)).astype(np.int32)}


def _leaf_axes(tree, topo):
    """Mesh axes (with size > 1) that actually shard any leaf."""
    import jax

    axes = set()
    for sh in jax.tree_util.tree_leaves(tree):
        for entry in sh.spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                if topo.axis_sizes.get(ax, 1) > 1:
                    axes.add(ax)
    return axes


@pytest.mark.slow
def test_hpz_mesh_derivation_and_param_gather_group(devices8):
    reset_topology()
    engine, *_ = sxt.initialize(model=_model(),
                                config=_base_config(zero_hpz_partition_size=2))
    topo = engine.topology
    assert topo.axis_sizes["fsdp"] == 2 and topo.axis_sizes["data"] == 4
    # params (forward copies) shard over fsdp only; master/opt over both.
    assert _leaf_axes(engine.param_shardings, topo) <= {"fsdp"}
    assert "data" in _leaf_axes(engine.master_shardings, topo)
    loss = engine.train_batch(_batch())
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_mics_shards_stay_in_group(devices8):
    reset_topology()
    engine, *_ = sxt.initialize(model=_model(),
                                config=_base_config(mics_shard_size=4))
    topo = engine.topology
    assert topo.axis_sizes["fsdp"] == 4 and topo.axis_sizes["data"] == 2
    # MiCS: master/opt replicated across groups (no "data" sharding at all).
    assert "data" not in _leaf_axes(engine.master_shardings, topo)
    loss = engine.train_batch(_batch())
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_qwz_quantized_weights_close_to_exact(devices8):
    reset_topology()
    e_exact, *_ = sxt.initialize(model=_model(), config=_base_config())
    w_exact = e_exact.module_weights()
    reset_topology()
    e_q, *_ = sxt.initialize(model=_model(), config=_base_config(zero_quantized_weights=True))
    w_q = e_q.module_weights()
    import jax

    for a, b in zip(jax.tree_util.tree_leaves(w_exact), jax.tree_util.tree_leaves(w_q)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # quantization rounding is small but (usually) nonzero
        np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)
    loss = e_q.train_batch(_batch())
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_qgz_quantized_gradients_trains(devices8):
    reset_topology()
    engine, *_ = sxt.initialize(model=_model(),
                                config=_base_config(zero_quantized_gradients=True))
    l0 = float(engine.train_batch(_batch()))
    for _ in range(3):
        l1 = float(engine.train_batch(_batch()))
    assert np.isfinite(l1) and l1 < l0


def test_qgz_wire_is_int8(devices8):
    """qgZ must COMPRESS THE WIRE, not just round the numerics: the compiled
    train step's gradient reduction collectives carry s8 operands (reference
    quantized two-level all-to-all, runtime/comm/coalesced_collectives.py:31).
    """
    import jax

    reset_topology()
    engine, *_ = sxt.initialize(model=_model(), config=_base_config(
        stage=2, zero_quantized_gradients=True))
    batch = _batch()
    shaped = engine._reshape_batch(batch)
    low = engine._train_step.lower(engine.state, shaped, engine._mix_matrix(),
                                   jax.random.PRNGKey(0),
                                   np.asarray(1.0, np.float32))
    hlo = low.compile().as_text()
    s8_gathers = [l for l in hlo.splitlines() if "all-gather" in l and "s8" in l]
    assert s8_gathers, "no s8 all-gather in compiled HLO — qgZ wire compression inactive"


def test_qgz_loss_parity_with_exact(devices8):
    reset_topology()
    eq, *_ = sxt.initialize(model=_model(),
                            config=_base_config(stage=2, zero_quantized_gradients=True))
    losses_q = [float(eq.train_batch(_batch())) for _ in range(4)]
    reset_topology()
    ee, *_ = sxt.initialize(model=_model(), config=_base_config(stage=2))
    losses_e = [float(ee.train_batch(_batch())) for _ in range(4)]
    np.testing.assert_allclose(losses_q, losses_e, rtol=0.02)


def test_hpz_group_must_divide_world(devices8):
    reset_topology()
    with pytest.raises(sxt.ConfigError):
        sxt.initialize(model=_model(), config=_base_config(zero_hpz_partition_size=3))


def test_stage3_wire_is_int8(devices8):
    """ZeRO-3 real wire compression (round 3, VERDICT r2 #5): with qwZ+qgZ
    on, the compiled stage-3 step's param gathers AND gradient reductions
    carry s8 operands — the north-star config no longer falls back to
    quantize-dequantize emulation (reference partition_parameters.py:824 +
    coalesced_collectives.py:31)."""
    import jax

    reset_topology()
    engine, *_ = sxt.initialize(model=_model(), config=_base_config(
        stage=3, zero_quantized_weights=True, zero_quantized_gradients=True))
    batch = _batch()
    shaped = engine._reshape_batch(batch)
    low = engine._train_step.lower(engine.state, shaped, engine._mix_matrix(),
                                   jax.random.PRNGKey(0),
                                   np.asarray(1.0, np.float32))
    hlo = low.compile().as_text()
    s8_gathers = [l for l in hlo.splitlines() if "all-gather" in l and "s8" in l]
    s8_a2a = [l for l in hlo.splitlines() if "all-to-all" in l and "s8" in l]
    assert s8_gathers, "no s8 all-gather — qwZ stage-3 wire inactive"
    assert s8_a2a, "no s8 all-to-all — qgZ stage-3 reduce-scatter wire inactive"


def test_stage3_wire_loss_parity_with_exact(devices8):
    """The int8-wire stage-3 step trains to ~the same loss as exact stage 3."""
    reset_topology()
    eq, *_ = sxt.initialize(model=_model(), config=_base_config(
        stage=3, zero_quantized_weights=True, zero_quantized_gradients=True))
    reset_topology()
    ex, *_ = sxt.initialize(model=_model(), config=_base_config(stage=3))
    lq = lx = None
    for s in range(4):
        b = {"input_ids": np.random.default_rng(s).integers(0, 128, size=(8, 32)).astype(np.int32)}
        lq, lx = float(eq.train_batch(b)), float(ex.train_batch(b))
    assert np.isfinite(lq) and abs(lq - lx) / abs(lx) < 0.05


def _s8_lines(hlo, kind):
    return [l for l in hlo.splitlines() if kind in l and "s8" in l]


def _train_step_hlo(engine):
    import jax

    shaped = engine._reshape_batch(_batch())
    low = engine._train_step.lower(engine.state, shaped, engine._mix_matrix(),
                                   jax.random.PRNGKey(0),
                                   np.asarray(1.0, np.float32))
    return low.compile().as_text()


def test_stage3_wire_on_tensor_mesh(devices8):
    """VERDICT r4 #3: the int8 wire must survive a model-parallel mesh —
    the reference applies qwZ/qgZ wherever ZeRO runs, TP active or not
    (coalesced_collectives.py:31 called from stage_1_and_2.py under MP;
    partition_parameters.py:824). tensor=2 x fsdp=4: the compiled step
    still carries s8 gathers AND s8 reduce collectives."""
    reset_topology()
    cfg = _base_config(stage=3, zero_quantized_weights=True,
                       zero_quantized_gradients=True)
    cfg["mesh"] = {"tensor": 2, "fsdp": 4}
    engine, *_ = sxt.initialize(model=_model(), config=cfg)
    assert engine.topology.axis_sizes["tensor"] == 2
    hlo = _train_step_hlo(engine)
    assert _s8_lines(hlo, "all-gather"), "no s8 all-gather under tensor mesh"
    assert _s8_lines(hlo, "all-to-all"), "no s8 reduce wire under tensor mesh"
    loss = engine.train_batch(_batch())
    assert np.isfinite(float(loss))


def test_stage3_wire_tensor_mesh_loss_parity(devices8):
    """Same mesh, wire vs exact stage-3: the partial-manual region must not
    change the optimization trajectory beyond quantization rounding."""
    cfg_q = _base_config(stage=3, zero_quantized_weights=True,
                         zero_quantized_gradients=True)
    cfg_q["mesh"] = {"tensor": 2, "fsdp": 4}
    cfg_x = _base_config(stage=3)
    cfg_x["mesh"] = {"tensor": 2, "fsdp": 4}
    reset_topology()
    eq, *_ = sxt.initialize(model=_model(), config=cfg_q)
    reset_topology()
    ex, *_ = sxt.initialize(model=_model(), config=cfg_x)
    lq = lx = None
    for s in range(4):
        b = {"input_ids": np.random.default_rng(s).integers(0, 128, size=(8, 32)).astype(np.int32)}
        lq, lx = float(eq.train_batch(b)), float(ex.train_batch(b))
    assert np.isfinite(lq) and abs(lq - lx) / abs(lx) < 0.05


def test_qgz_stage2_wire_on_tensor_mesh(devices8):
    """qgZ's hierarchical int8 reduce under TP (stage <= 2): the reference
    reduces quantized with model parallelism active."""
    reset_topology()
    cfg = _base_config(stage=2, zero_quantized_gradients=True)
    cfg["mesh"] = {"tensor": 2, "data": -1}
    engine, *_ = sxt.initialize(model=_model(), config=cfg)
    hlo = _train_step_hlo(engine)
    assert _s8_lines(hlo, "all-gather"), "no s8 gather — qgZ wire fell back under TP"
    l0 = float(engine.train_batch(_batch()))
    for _ in range(3):
        l1 = float(engine.train_batch(_batch()))
    assert np.isfinite(l1) and l1 < l0


def test_stage3_wire_on_expert_mesh(devices8):
    """Expert-parallel meshes keep the real wire too — and the expert
    placement must survive the partial-manual region (moe/layer.py's
    constraint is try/except-guarded, so a silent drop would only show as
    replicated experts; assert the s8 wire AND a finite decreasing loss)."""
    from shuffle_exchange_tpu.models import Transformer as T, tiny_moe

    reset_topology()
    cfg = _base_config(stage=3, zero_quantized_weights=True,
                       zero_quantized_gradients=True)
    cfg["mesh"] = {"expert": 2, "fsdp": 2, "data": -1}
    model = T(tiny_moe(vocab=128, d=64, layers=2, heads=4, seq=32, experts=4))
    engine, *_ = sxt.initialize(model=model, config=cfg)
    assert engine.topology.axis_sizes["expert"] == 2
    hlo = _train_step_hlo(engine)
    assert _s8_lines(hlo, "all-gather"), "no s8 gather under expert mesh"
    l0 = float(engine.train_batch(_batch()))
    for _ in range(3):
        l1 = float(engine.train_batch(_batch()))
    assert np.isfinite(l1) and l1 < l0


def test_lora_qwz_real_wire(devices8):
    """VERDICT r4 #3: LoRA must not disable the wire — the frozen base
    gathers through the quantized collective inside the region (reference
    gathers quantized regardless of LoRA, partition_parameters.py:824)."""
    reset_topology()
    cfg = _base_config(stage=3, zero_quantized_weights=True,
                       zero_quantized_gradients=True)
    cfg["lora"] = {"enabled": True, "lora_r": 8, "lora_alpha": 16}
    engine, *_ = sxt.initialize(model=_model(), config=cfg)
    hlo = _train_step_hlo(engine)
    assert _s8_lines(hlo, "all-gather"), "no s8 gather — LoRA disabled the wire"
    assert _s8_lines(hlo, "all-to-all"), "no s8 reduce — LoRA disabled the wire"
    l0 = float(engine.train_batch(_batch()))
    for _ in range(3):
        l1 = float(engine.train_batch(_batch()))
    assert np.isfinite(l1) and l1 < l0


@pytest.mark.slow   # 14s: compression x qz3 compose; nightly via ci_full (ISSUE 13 tier-1 budget)
def test_compression_qz3_real_wire(devices8):
    """VERDICT r4 #3: compression_training composes with the stage-3 wire —
    the transform applies to the gathered tree inside the region instead of
    silently downgrading to emulation."""
    reset_topology()
    cfg = _base_config(stage=3, zero_quantized_weights=True,
                       zero_quantized_gradients=True)
    cfg["compression_training"] = {
        "weight_quantization": {
            "shared_parameters": {"enabled": True, "schedule_offset": 0,
                                  "quantize_groups": 1,
                                  "quantization_type": "symmetric"},
            "different_groups": {
                "wq1": {"params": {"start_bits": 8, "target_bits": 8},
                        "modules": [r"layers\.wq", r"layers\.wk"]}}}}
    engine, *_ = sxt.initialize(model=_model(), config=cfg)
    hlo = _train_step_hlo(engine)
    assert _s8_lines(hlo, "all-gather"), "no s8 gather — compression disabled the wire"
    loss = engine.train_batch(_batch())
    assert np.isfinite(float(loss))


def test_stage3_wire_streams_per_leaf(devices8):
    """VERDICT r3 weak #4: the int8 wire must not trade away ZeRO-3's
    memory story. The streamed per-leaf custom_vjp design (a) reduces each
    leaf's cotangent through its own s8 collective — one per sharded leaf,
    visible in HLO — and (b) keeps the step's temp allocation within a
    small factor of the PLAIN auto-sharded ZeRO-3 step (the old whole-tree
    shard_map region materialized the full fp32 grad tree on top)."""
    import jax

    def _temp_bytes(engine):
        batch = _batch()
        shaped = engine._reshape_batch(batch)
        low = engine._train_step.lower(engine.state, shaped, engine._mix_matrix(),
                                       jax.random.PRNGKey(0),
                                       np.asarray(1.0, np.float32))
        compiled = low.compile()
        return compiled.memory_analysis().temp_size_in_bytes, compiled

    big = lambda: Transformer(tiny(vocab=128, d=128, layers=8, heads=8, seq=32))
    reset_topology()
    e_wire, *_ = sxt.initialize(model=big(), config=_base_config(
        stage=3, zero_quantized_weights=True, zero_quantized_gradients=True))
    wire_tmp, compiled = _temp_bytes(e_wire)
    reset_topology()
    e_auto, *_ = sxt.initialize(model=big(), config=_base_config(stage=3))
    auto_tmp, _ = _temp_bytes(e_auto)

    # (a) per-leaf s8 reduce: at least one s8 collective per big sharded
    # leaf class (wq, wk, wv, wo, w_gate, w_up, w_down, embed...)
    hlo = compiled.as_text()
    s8_reduces = [l for l in hlo.splitlines()
                  if ("all-to-all" in l or "reduce-scatter" in l) and "s8" in l]
    assert len(s8_reduces) >= 4, f"only {len(s8_reduces)} s8 reduce collectives"
    # (b) no whole-tree blowup vs the auto path
    assert wire_tmp < 3.0 * auto_tmp, (wire_tmp, auto_tmp)
