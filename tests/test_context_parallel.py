"""Ring-attention context parallelism (ISSUE 15): the ``context_parallel``
config section maps onto the mesh "seq" axis and forces the model's
attention onto the ring path — KV rotating around the ring by ``ppermute``
with online-softmax accumulation, exact-softmax numerics, per-chip
attention memory O(seq/CP).

Ring-attention NUMERICS (forward/GQA/kernel-hop/backward parity) are
covered by tests/test_sequence.py; this file covers the CP plumbing:
config validation, engine routing, CP-vs-replicated trajectory and grad
parity, the ``save_flash_lse`` x ring composition (backward enters the
hop kernels from SAVED lse), and the memory-scaling shape claim.
"""

import numpy as np
import pytest

import jax

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.config import ConfigError, SXConfig
from shuffle_exchange_tpu.models import Transformer, tiny
from shuffle_exchange_tpu.parallel import reset_topology

VOCAB, SEQ, BATCH = 128, 64, 8


def _mcfg(**kw):
    return tiny(vocab=VOCAB, d=64, layers=2, heads=4, seq=SEQ,
                n_kv_heads=2, activation="swiglu", norm="rmsnorm",
                position="rope", **kw)


def _train_cfg(**over):
    cfg = {"train_batch_size": BATCH,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "steps_per_print": 10**9}
    cfg.update(over)
    return cfg


def _batch(seed=0):
    return {"input_ids": np.random.default_rng(seed).integers(
        0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)}


# ---------------------------------------------------------------------------
# Config contracts
# ---------------------------------------------------------------------------


class TestConfig:
    def test_cp_and_ulysses_both_claim_seq_rejected(self):
        """ring CP and Ulysses SP are alternative attention shapes over
        the same mesh axis — exactly one may own it."""
        with pytest.raises(ConfigError, match="both\\s+claim the mesh 'seq'"):
            SXConfig.load({"train_batch_size": 8,
                           "context_parallel": {"degree": 2},
                           "sequence_parallel_size": 2}, world_size=4)

    def test_cp_degree_merges_onto_seq_axis(self):
        cfg = SXConfig.load({"train_batch_size": 8,
                             "context_parallel": {"degree": 2},
                             "mesh": {"data": -1}}, world_size=4)
        assert cfg.mesh.seq == 2

    def test_cp_conflicting_mesh_seq_rejected(self):
        with pytest.raises(ConfigError):
            SXConfig.load({"train_batch_size": 8,
                           "context_parallel": {"degree": 2},
                           "mesh": {"seq": 4, "data": -1}}, world_size=8)

    def test_use_kernel_validated(self):
        with pytest.raises(ConfigError, match="use_kernel"):
            SXConfig.load({"train_batch_size": 8,
                           "context_parallel": {"degree": 2,
                                                "use_kernel": "cuda"}},
                          world_size=2)


# ---------------------------------------------------------------------------
# Engine routing + parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def replicated_run(devices8):
    """The CP=1 reference: loss trajectory + staged full grads on the
    plain data-parallel path (module-scoped — every CP degree compares
    against this one run)."""
    reset_topology()
    eng, *_ = sxt.initialize(model=Transformer(_mcfg()),
                             config=_train_cfg(), seed=0)
    eng.forward(_batch())
    eng.backward()
    grads = {n: np.asarray(eng.get_full_grad(n))
             for n in ("embed", "layers.wq", "layers.wo", "layers.w_down")}
    eng.step()
    losses = [float(eng.train_batch(_batch())) for _ in range(2)]
    reset_topology()
    return grads, losses


class TestParity:
    def test_cp_routes_model_onto_ring(self, devices8):
        reset_topology()
        model = Transformer(_mcfg())
        assert model.config.sp_attention == "ulysses"   # zoo default
        eng, *_ = sxt.initialize(
            model=model,
            config=_train_cfg(context_parallel={"degree": 2, "kv_chunk": 32,
                                                "use_kernel": "xla"},
                              mesh={"seq": 2, "data": -1}), seed=0)
        assert model.config.sp_attention == "ring"
        assert model.config.cp_kv_chunk == 32
        assert model.config.cp_use_kernel == "xla"
        reset_topology()

    @pytest.mark.parametrize("cp", [2, 4])
    def test_cp_loss_and_grad_parity(self, devices8, replicated_run, cp):
        """CP=2 and CP=4 track the replicated reference: same first-step
        grads (<= 2e-4 — exact softmax, different reduction order) and the
        same short loss trajectory."""
        ref_grads, ref_losses = replicated_run
        reset_topology()
        eng, *_ = sxt.initialize(
            model=Transformer(_mcfg()),
            config=_train_cfg(context_parallel={"degree": cp},
                              mesh={"seq": cp, "data": -1}), seed=0)
        eng.forward(_batch())
        eng.backward()
        for name, want in ref_grads.items():
            got = np.asarray(eng.get_full_grad(name))
            assert np.max(np.abs(got - want)) <= 2e-4, name
        eng.step()
        losses = [float(eng.train_batch(_batch())) for _ in range(2)]
        reset_topology()
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)


# ---------------------------------------------------------------------------
# save_flash_lse x ring: backward enters hop kernels from SAVED lse
# ---------------------------------------------------------------------------


def test_ring_save_flash_lse_skips_forward_recompute(monkeypatch, devices8):
    """With ``hop_remat=False`` under an enclosing ``save_flash_lse``
    checkpoint, each hop's (out, lse) pair is saved and the forward
    kernel is DCE'd out of the backward recompute — fewer pallas calls
    than the default per-hop checkpoint, which re-runs forward attention
    inside every hop's backward."""
    import functools as ft

    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.config.config import MeshConfig
    from shuffle_exchange_tpu.models.transformer import _remat_policy
    from shuffle_exchange_tpu.parallel.mesh import MeshTopology, shard_map
    from shuffle_exchange_tpu.parallel.sequence import ring_attention

    monkeypatch.setenv("SXT_LSE_INTERPRET", "1")
    topo = MeshTopology.build(MeshConfig(data=1, seq=2), n_devices=2)
    B, T, H, D = 1, 256, 2, 64   # kernel-eligible hop shape (Tq 128/hop)
    q = np.random.default_rng(0).standard_normal(
        (B, T, H, D)).astype(np.float32)
    spec = P(None, "seq", None, None)

    def counts(hop_remat):
        def attn(q, k, v):
            return ring_attention(q, k, v, axis_name="seq", causal=True,
                                  use_kernel=True, interpret=True,
                                  hop_remat=hop_remat)

        fn = shard_map(attn, mesh=topo.mesh, in_specs=(spec,) * 3,
                       out_specs=spec, check_vma=False)
        if not hop_remat:
            fn = jax.checkpoint(fn, policy=_remat_policy("save_flash_lse"))

        return str(jax.make_jaxpr(jax.grad(
            lambda x: fn(x, x, x).sum()))(q)).count("pallas_call")

    saved = counts(hop_remat=False)
    default = counts(hop_remat=True)
    # default: every hop's backward re-runs its forward kernel; saved-lse:
    # the backward enters dq/dkv from the saved (out, lse) — strictly
    # fewer pallas calls, with the fwd kernel absent from the bwd segment
    assert saved < default, (saved, default)


def _jaxpr_peak_var_bytes(jaxpr) -> int:
    """Largest single array (bytes) among the variables of the jaxpr's
    shard_map body, subjaxprs included: there the shapes are one chip's,
    where the outer jaxpr's operands keep their global [B, T, ...] shapes
    at every CP degree."""
    def walk(jx):
        yield jx
        for sub in jax.core.subjaxprs(jx):
            yield from walk(sub)

    regions = [eqn.params["jaxpr"] for jx in walk(jaxpr.jaxpr)
               for eqn in jx.eqns if eqn.primitive.name == "shard_map"]
    assert regions, "no shard_map region in the jaxpr"
    avals = (getattr(var, "aval", None)
             for jx in walk(getattr(regions[0], "jaxpr", regions[0]))
             for eqn in jx.eqns for var in (*eqn.outvars, *eqn.invars))
    return max(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals
               if hasattr(a, "shape") and hasattr(a, "dtype"))


def test_ring_attention_peak_memory_scales_inverse_with_cp(devices8):
    """The per-chip attention working set is O(seq/CP): the largest
    intermediate in the local ring region halves as the degree doubles
    (score tiles never materialize past the hop chunk)."""
    from jax.sharding import PartitionSpec as P

    from shuffle_exchange_tpu.config.config import MeshConfig
    from shuffle_exchange_tpu.parallel.mesh import MeshTopology, shard_map
    from shuffle_exchange_tpu.parallel.sequence import ring_attention

    B, T, H, D = 1, 512, 2, 16
    q = np.zeros((B, T, H, D), np.float32)
    spec = P(None, "seq", None, None)
    peak = {}
    for cp in (1, 2, 4, 8):
        reset_topology()
        topo = MeshTopology.build(MeshConfig(data=1, seq=cp),
                                  n_devices=max(1, cp))
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                           causal=True, use_kernel=False,
                                           kv_chunk=64),
            mesh=topo.mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)
        peak[cp] = _jaxpr_peak_var_bytes(jax.make_jaxpr(fn)(q, q, q))
    reset_topology()
    for lo, hi in ((2, 1), (4, 2), (8, 4)):
        assert peak[lo] <= peak[hi] / 2 * 1.25, peak
    assert peak[8] <= peak[1] / 4, peak
