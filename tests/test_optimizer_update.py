"""One AdamW on every backend, applied by one update function.

``optimizer.type: FusedAdam`` (what every benchmark cell sets) is a name for
``optax.adamw``: the same rule and the same state tree on the CPU and on the
chip. The engine applies it through ONE sequence normalize -> overflow ->
update -> select -> loss scale -> step, shared by ``train_batch`` and the
staged ``forward``/``backward``/``step`` path.
"""

import re

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.parallel import reset_topology
from tests.test_engine import _batch, _toy_model

ADAMW_NAMES = [("FusedAdam", {}), ("AdamW", {}), ("CPUAdam", {}),
               ("Adam", {"adam_w_mode": True})]


def _engine(monkeypatch=None, devices=None, **extra):
    """A toy-model engine with ``FusedAdam``; ``devices`` narrows the mesh
    (``sxt.initialize`` builds it from ``jax.devices()``)."""
    import jax

    if devices is not None:
        monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    reset_topology()
    cfg = {"train_batch_size": 32, "steps_per_print": 10**9,
           "optimizer": {"type": "FusedAdam",
                         "params": {"lr": 1e-2, "weight_decay": 0.1}}}
    cfg.update(extra)
    return sxt.initialize(model=_toy_model(), config=cfg)[0]


def _host(tree):
    """A host copy that outlives the step's donation of the state."""
    import jax

    return jax.tree.map(lambda x: np.array(x), tree)


def _adam_state(opt_state):
    """The two-line access ``chipbench``'s ``first_moment`` makes: the one
    node of the optimizer state that has a ``.mu``."""
    import jax

    has = lambda s: hasattr(s, "mu")
    found = [s for s in jax.tree.leaves(opt_state, is_leaf=has) if has(s)]
    assert len(found) == 1
    return found[0]


@pytest.mark.parametrize("name,extra", ADAMW_NAMES,
                         ids=[n for n, _ in ADAMW_NAMES])
def test_adamw_names_build_one_rule(name, extra):
    """Every AdamW-mode name builds optax.adamw: one state tree, and under a
    warm-up the first update is lr(0) = 0 (the first chip run found a fused
    form one step ahead of the schedule)."""
    import jax
    import jax.numpy as jnp
    import optax

    from shuffle_exchange_tpu.config.config import OptimizerConfig
    from shuffle_exchange_tpu.runtime.optimizers import build_optimizer

    sched = optax.linear_schedule(0.0, 1e-2, transition_steps=2)
    ours = build_optimizer(OptimizerConfig(
        type=name, params={"lr": 1e-2, "weight_decay": 0.1, **extra}), sched)
    theirs = optax.adamw(sched, weight_decay=0.1)
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    so, st, po, pt = ours.init(p), theirs.init(p), p, p
    assert jax.tree.structure(so) == jax.tree.structure(st)
    for step in range(3):
        g = jax.tree.map(lambda x: jnp.sin(x + step), po)
        uo, so = ours.update(g, so, po)
        ut, st = theirs.update(g, st, pt)
        if step == 0:
            assert float(jnp.abs(uo["w"]).max()) == 0.0
        for k in p:
            np.testing.assert_allclose(np.asarray(uo[k]), np.asarray(ut[k]),
                                       rtol=0, atol=1e-6)
        po, pt = optax.apply_updates(po, uo), optax.apply_updates(pt, ut)
    assert int(_adam_state(so).count) == 3


@pytest.mark.parametrize("mesh", ["one-device", "zero3-devices8"])
def test_fused_adam_engine_trains_on_the_masters_shards(mesh, monkeypatch, devices8):
    """An engine built with ``FusedAdam`` trains, and every leaf of both
    moments lives where its master lives: XLA partitions the elementwise
    update by the operands' own shardings, no wrapper tells it how."""
    import jax

    if mesh == "one-device":
        engine = _engine(monkeypatch, devices8[:1])
    else:
        engine = _engine(zero_optimization={"stage": 3},
                         mesh={"fsdp": 4, "data": 2})
        specs = [l.sharding.spec for l in jax.tree.leaves(engine.state.master)]
        assert any(any(e is not None for e in s) for s in specs)
    batch = _batch()
    losses = [float(engine.train_batch(batch)) for _ in range(11)]
    assert losses[-1] < losses[0]
    adam = _adam_state(engine.state.opt_state)
    assert int(adam.count) == 11
    for moment in (adam.mu, adam.nu):
        assert jax.tree.structure(moment) == jax.tree.structure(engine.state.master)
        for m, p in zip(jax.tree.leaves(moment), jax.tree.leaves(engine.state.master)):
            assert m.sharding == p.sharding


@pytest.mark.parametrize("case", ["nonfinite-train_batch", "fp16-overflow-train_batch",
                                  "fp16-overflow-staged"])
def test_skipped_step_leaves_the_state_bit_identical(case):
    """The one update sequence through both of its entry points: a skipped
    step hands back master, both moments, the optimizer's count and
    ``state.step`` exactly as they were (and an overflow still reaches the
    loss scale)."""
    import jax

    fp16 = case.startswith("fp16")
    engine = _engine(**({"fp16": {"enabled": True, "initial_scale_power": 4,
                                  "hysteresis": 1}} if fp16
                        else {"resilience": {"nonfinite_policy": "skip"}}))
    batch = _batch()
    for _ in range(2):
        engine.train_batch(batch)
    before = _host((engine.state.master, engine.state.opt_state, engine.state.step))
    assert int(before[2]) == 2 and int(_adam_state(before[1]).count) == 2
    bad = dict(batch, x=np.full_like(batch["x"], np.nan))
    if case.endswith("staged"):
        engine.forward(bad)
        engine.backward()
        engine.step()
    else:
        engine.train_batch(bad)
    after = _host((engine.state.master, engine.state.opt_state, engine.state.step))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    if fp16:
        assert engine.skipped_steps == 1 and engine.loss_scale() == 8.0
    # and the next clean step is taken
    assert np.isfinite(float(engine.train_batch(batch)))
    assert int(np.asarray(engine.state.step)) == 3


@pytest.mark.parametrize("mesh", ["one-device", "zero3-devices8"])
def test_train_step_updates_master_and_optimizer_state_in_place(mesh, monkeypatch, devices8):
    """The compiled train step aliases every leaf of the master weights and
    of the optimizer state to an output: donation makes the update in place,
    with no kernel and no padded temporary in between."""
    import jax

    engine = (_engine(monkeypatch, devices8[:1]) if mesh == "one-device"
              else _engine(zero_optimization={"stage": 3}, mesh={"fsdp": 4, "data": 2}))
    text = engine.compile(_batch()).as_text()
    aliases = text[text.index("input_output_alias={"):text.index("entry_computation_layout")]
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", aliases)}
    # the state is the step's first argument and its leaves the first parameters
    n = len(jax.tree.leaves((engine.state.master, engine.state.opt_state)))
    assert n == 4 + 1 + 4 + 4 + 1      # master, count, mu, nu, schedule count
    assert set(range(n)) <= aliased, sorted(set(range(n)) - aliased)


def test_first_moment_is_a_tenth_of_the_first_gradient():
    """What the benchmark's correctness check leans on: ``.mu`` is found in
    the engine's optimizer state, starts at 0, and after ONE update is
    (1 - beta1) x that step's gradient."""
    import jax
    import jax.numpy as jnp

    engine = _engine()
    model, batch = _toy_model(), _batch()
    master = _host(engine.state.master)
    assert all(not np.any(np.asarray(m)) for m in
               jax.tree.leaves(_adam_state(engine.state.opt_state).mu))
    grads = jax.grad(model.loss)(master, jax.tree.map(jnp.asarray, batch))
    engine.train_batch(batch)
    mu = _adam_state(engine.state.opt_state).mu
    for k in master:
        np.testing.assert_allclose(np.asarray(mu[k]), 0.1 * np.asarray(grads[k]),
                                   rtol=0, atol=1e-6)
