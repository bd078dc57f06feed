"""Overlapped host-offload optimizer pipeline (runtime/zero/overlap.py):

- bit-exact parity with the synchronous cpu tier across steps and after
  checkpoint round trips;
- structural overlap evidence by COUNTERS/ORDERING, not wall-clock: D2H
  submits precede train_batch's return, the join lands at the next step,
  and bucket 0's H2D upload is dispatched before bucket 1's host update
  completes (single ordered worker);
- crash mid-pipeline (testing/faults.py site ``offload_bucket_update``):
  the error surfaces at the next join, the pipeline poisons (no further
  training, no checkpoint of torn state), and restore + resume reproduces
  the synchronous trajectory bit-exactly — no step is ever half-applied.
"""

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer, tiny
from shuffle_exchange_tpu.parallel import reset_topology
from shuffle_exchange_tpu.runtime.zero.overlap import make_buckets
from shuffle_exchange_tpu.testing import faults
from shuffle_exchange_tpu.testing.faults import InjectedFault


def _model():
    return Transformer(tiny(vocab=128, d=64, layers=2, heads=4, seq=32))


def _config(grad_clip=0.0, **offload):
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1, "offload_optimizer": offload},
        "steps_per_print": 10**9,
    }
    if grad_clip:
        cfg["gradient_clipping"] = grad_clip
    return cfg


def _overlap(grad_clip=0.0):
    # overlap_bucket_mb=0: one leaf per bucket (16 buckets for the tiny
    # model) so bucket pipelining is observable
    return _config(grad_clip=grad_clip, device="cpu", offload_overlap=True,
                   overlap_bucket_mb=0)


def _batch(seed=0):
    return {"input_ids": np.random.default_rng(seed).integers(
        0, 128, size=(8, 32)).astype(np.int32)}


def test_make_buckets():
    leaves = [np.zeros(n, np.float32) for n in (10, 10, 1000, 10)]
    assert make_buckets(leaves, 0) == [[0], [1], [2], [3]]
    # 10+10 fp32 = 80 B fit one 100-byte bucket; the 4000 B leaf spills
    assert make_buckets(leaves, 100) == [[0, 1], [2], [3]]
    assert make_buckets(leaves, 10**9) == [[0, 1, 2, 3]]


@pytest.mark.parametrize("grad_clip", [0.0, 0.5])
def test_overlap_matches_sync_bit_exact(grad_clip, devices8):
    """Same seeds, same steps: losses and final weights must be IDENTICAL
    between the synchronous and overlapped paths (same per-leaf fused
    kernel, same leaf order, same clip accumulation order)."""
    import jax

    reset_topology()
    e_sync, *_ = sxt.initialize(model=_model(),
                                config=_config(grad_clip, device="cpu"))
    reset_topology()
    e_ov, *_ = sxt.initialize(model=_model(), config=_overlap(grad_clip))
    assert e_ov._host_pipeline is not None
    assert len(e_ov._host_pipeline.buckets) >= 2
    for s in range(4):
        l_sync = float(e_sync.train_batch(_batch(s)))
        l_ov = float(e_ov.train_batch(_batch(s)))
        assert l_sync == l_ov, f"step {s}: {l_sync} != {l_ov}"
    w_sync = jax.device_get(e_sync.module_weights())
    w_ov = jax.device_get(e_ov.module_weights())
    for a, b in zip(jax.tree_util.tree_leaves(w_sync),
                    jax.tree_util.tree_leaves(w_ov)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overlap_ordering_counters(devices8):
    """Overlap is asserted structurally: (1) train_batch returns with the
    host update still in flight (delayed parameter application), (2) every
    D2H submit precedes the step's return, (3) the join lands at the NEXT
    step, (4) bucket 0's H2D dispatch precedes bucket 1's host-Adam
    completion (ordered worker pipelining). No wall-clock involved."""
    reset_topology()
    eng, *_ = sxt.initialize(model=_model(), config=_overlap())
    pipe = eng._host_pipeline
    eng.train_batch(_batch(0))
    # (1) submitted but not joined when train_batch returns
    assert pipe.pending
    eng.train_batch(_batch(1))     # joins step 0, submits step 1
    assert pipe.pending
    # event ordering for step 0
    step_ret = pipe.event_seq("step_return")
    join = pipe.event_seq("join")
    assert step_ret is not None and join is not None
    d2h_all = [s for s, t, _ in pipe.events if t == "d2h_submit"]
    assert d2h_all
    # (2) all of step-0's submits (first n_leaves events) precede step_return
    n_leaves = len(eng._host_opt.params)
    assert max(d2h_all[:n_leaves]) < step_ret
    # (3) the join happened only after the step returned
    assert join > step_ret
    # (4) pipelined buckets: upload of bucket 0 before update of bucket 1
    h2d0 = pipe.event_seq("h2d_dispatch", index=0)
    adam1 = pipe.event_seq("adam_done", index=1)
    assert h2d0 is not None and adam1 is not None and h2d0 < adam1
    # counters reach the monitor at the join
    eng.module_weights()           # final join
    mm = eng.monitor.memory_monitor
    assert mm.latest("offload/overlap_steps") >= 1
    for label in ("offload/d2h_wait_s", "offload/host_adam_s",
                  "offload/h2d_dispatch_s"):
        assert mm.latest(label) is not None


def test_overlap_composes_with_bf16_and_the_kept_flash_residuals(devices8):
    """The cpu tier + ``offload_overlap`` under a bf16 step whose layers are
    checkpointed with ``save_flash_lse``: the host optimizer and the
    pipeline engage, two steps give finite losses and the joined step
    reaches the monitor. The three options meet in no other test."""
    model = Transformer(tiny(vocab=128, d=64, layers=2, heads=4, seq=32,
                             position="rope", remat=True,
                             remat_policy="save_flash_lse"))
    cfg = dict(_config(device="cpu", offload_overlap=True),
               bf16={"enabled": True})
    reset_topology()
    eng, *_ = sxt.initialize(model=model, config=cfg)
    assert eng._host_opt is not None, "host-resident optimizer not engaged"
    assert eng._host_pipeline is not None, "overlap pipeline not engaged"
    losses = [float(eng.train_batch(_batch(seed))) for seed in (0, 1)]
    assert np.all(np.isfinite(losses)), losses
    eng.module_weights()    # joins the in-flight overlapped step
    assert eng.monitor.memory_monitor.latest("offload/overlap_steps") >= 1


def test_overlap_checkpoint_roundtrip(tmp_path, devices8):
    """save -> train -> load -> retrain reproduces the trajectory (the save
    joins the in-flight step first — never a half-applied checkpoint)."""
    reset_topology()
    eng, *_ = sxt.initialize(model=_model(), config=_overlap())
    for s in range(2):
        eng.train_batch(_batch(s))
    eng.save_checkpoint(str(tmp_path))
    after = [float(eng.train_batch(_batch(10 + s))) for s in range(2)]

    reset_topology()
    eng2, *_ = sxt.initialize(model=_model(), config=_overlap())
    eng2.load_checkpoint(str(tmp_path))
    replay = [float(eng2.train_batch(_batch(10 + s))) for s in range(2)]
    assert replay == after


def test_crash_mid_pipeline_never_half_applies(tmp_path, devices8):
    """Fault at bucket 1 of the host update: the crash surfaces at the next
    join, checkpointing torn state is impossible, training refuses to
    continue, and restore + resume is bit-exact with the synchronous
    trajectory from the same checkpoint."""
    try:
        reset_topology()
        e_sync, *_ = sxt.initialize(model=_model(),
                                    config=_config(device="cpu"))
        for s in range(2):
            e_sync.train_batch(_batch(s))
        e_sync.save_checkpoint(str(tmp_path / "sync"))
        ref = [float(e_sync.train_batch(_batch(10 + s))) for s in range(3)]

        reset_topology()
        e_ov, *_ = sxt.initialize(model=_model(), config=_overlap())
        for s in range(2):
            e_ov.train_batch(_batch(s))
        e_ov.save_checkpoint(str(tmp_path / "ov"))
        faults.arm("offload_bucket_update", index=1)
        e_ov.train_batch(_batch(10))    # worker crashes at bucket 1
        # the torn step cannot be checkpointed
        with pytest.raises(InjectedFault):
            e_ov.save_checkpoint(str(tmp_path / "ov"))
        # the pipeline is poisoned: no silent continuation on torn state
        with pytest.raises(RuntimeError, match="poisoned"):
            e_ov.train_batch(_batch(11))
        # recovery: restore the last committed checkpoint and resume
        e_ov.load_checkpoint(str(tmp_path / "ov"))
        resumed = [float(e_ov.train_batch(_batch(10 + s))) for s in range(3)]
        assert resumed == ref
    finally:
        faults.clear()


def test_pinned_pool_buffers():
    from shuffle_exchange_tpu.ops.native.aio import PinnedBufferPool

    pool = PinnedBufferPool()
    a = pool.empty((16, 3), np.uint16)
    assert a.shape == (16, 3) and a.dtype == np.uint16
    a[:] = 7
    assert (a == 7).all()
    if pool.native:
        assert a.ctypes.data % PinnedBufferPool.ALIGNMENT == 0
    b = pool.empty((0,), np.float32)
    assert b.size == 0
