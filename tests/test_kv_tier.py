"""Tiered paged KV (ISSUE 15): cold blocks spill host-ward byte-exactly,
fetch back into fresh pool slots with no re-prefill, admission stays
atomic-on-reject at every tier transition, and the chaos fault sites
(``kv_spill``/``kv_fetch``) leave pool + allocator + host tier
byte-identically clean on a mid-operation crash.
"""

import dataclasses

import numpy as np
import pytest

import jax

from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                            InferenceConfig,
                                            InferenceEngineV2)
from shuffle_exchange_tpu.inference.kv_tier import HostKVTier
from shuffle_exchange_tpu.models import Transformer, tiny
from shuffle_exchange_tpu.testing import faults
from shuffle_exchange_tpu.testing.faults import InjectedFault


@pytest.fixture(scope="module")
def model_and_params():
    # same fixture shape as test_disagg — the compile
    # cache reuses the prefill/decode programs across these files
    cfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
               activation="swiglu", norm="rmsnorm", position="rope",
               n_kv_heads=2, tie_embeddings=False)
    model = Transformer(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _icfg(num_kv_blocks=40, kv_cache_dtype="bf16", **tier):
    tier.setdefault("enabled", True)
    return InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8,
        num_kv_blocks=num_kv_blocks, kv_cache_dtype=kv_cache_dtype,
        kv_tier=tier,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})


def _planes_at(eng, uid):
    """Host copy of uid's pool planes in descriptor-position order (the
    byte-identity oracle: block IDS may change across spill/fetch, the
    BYTES at each position may not)."""
    desc = eng._seqs[uid]
    idx = np.asarray(desc.blocks, np.int32)
    out = [np.asarray(eng.cache.k[:, idx]), np.asarray(eng.cache.v[:, idx])]
    if eng.cache.quantized:
        out += [np.asarray(eng.cache.k_scale[:, idx]),
                np.asarray(eng.cache.v_scale[:, idx])]
    return out


# ---------------------------------------------------------------------------
# HostKVTier: pure-host store (no jax)
# ---------------------------------------------------------------------------


class TestHostTier:
    def _planes(self, rng, nb):
        return [rng.standard_normal((2, nb, 2, 8, 4)).astype(np.float32),
                rng.standard_normal((2, nb, 2, 8, 4)).astype(np.float32)]

    def test_roundtrip_and_drop(self):
        tier = HostKVTier()
        rng = np.random.default_rng(0)
        planes = self._planes(rng, 3)
        tier.store(7, [0, 2, 5], planes)
        idx, got = tier.load(7)
        assert idx == [0, 2, 5]
        for w, g in zip(planes, got):
            np.testing.assert_array_equal(w, g)
        assert tier.spilled(7) == [0, 2, 5] and tier.uids() == [7]
        assert tier.spilled_blocks == 3 and tier.host_bytes > 0
        tier.drop(7)
        assert tier.spilled(7) == [] and tier.spilled_blocks == 0
        assert tier.host_bytes == 0
        tier.drop(7)   # unknown uid is a no-op
        with pytest.raises(KeyError):
            tier.load(7)

    def test_merge_spill_disjoint_positions(self):
        """A second spill of the same uid merges position-sorted;
        overlapping positions are a caller bug and refuse loudly."""
        tier = HostKVTier()
        rng = np.random.default_rng(1)
        a = self._planes(rng, 2)
        b = self._planes(rng, 2)
        tier.store(1, [4, 1], [p[:, [0, 1]] for p in a])
        tier.store(1, [3, 0], [p[:, [0, 1]] for p in b])
        idx, got = tier.load(1)
        assert idx == [0, 1, 3, 4]
        # position 4 came from a[0], 1 from a[1], 3 from b[0], 0 from b[1]
        for g, pa, pb in zip(got, a, b):
            np.testing.assert_array_equal(g[:, 0], pb[:, 1])
            np.testing.assert_array_equal(g[:, 1], pa[:, 1])
            np.testing.assert_array_equal(g[:, 2], pb[:, 0])
            np.testing.assert_array_equal(g[:, 3], pa[:, 0])
        assert tier.spilled_blocks == 4
        with pytest.raises(ValueError, match="re-spills"):
            tier.store(1, [3], [p[:, :1] for p in a])

    def test_prefetch_hit_miss_accounting(self):
        tier = HostKVTier(prefetch_depth=1)
        rng = np.random.default_rng(2)
        tier.store(1, [0], self._planes(rng, 1))
        tier.store(2, [0], self._planes(rng, 1))
        assert tier.prefetch(1) and tier.prefetch(1)   # idempotent
        assert tier.prefetches == 1
        _, staged = tier.load(1)
        assert tier.prefetch_hits == 1 and tier.prefetch_misses == 0
        _, cold = tier.load(2)
        assert tier.prefetch_misses == 1
        assert tier.hit_rate == 0.5
        assert not tier.prefetch(99)   # nothing spilled for that uid
        # depth bound: staging 2 evicts 1's staging
        tier.prefetch(1)
        tier.prefetch(2)
        assert list(tier._staged) == [2]

    def test_prefetch_failure_recycles_slot(self, monkeypatch):
        """A failed prefetch (IO error in the read/copy) is best-effort:
        it returns False instead of raising into the scheduler tick, and
        the slot reservation recycles so the uid can be staged again."""
        tier = HostKVTier(prefetch_depth=2)
        rng = np.random.default_rng(4)
        tier.store(1, [0], self._planes(rng, 1))
        real = tier._read_planes
        calls = {"n": 0}

        def flaky(e):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("injected read failure")
            return real(e)

        monkeypatch.setattr(tier, "_read_planes", flaky)
        assert tier.prefetch(1) is False
        assert tier._slots == {} and tier._staged == {}
        # the retry succeeds: the reservation was recycled, not leaked
        assert tier.prefetch(1) is True
        assert tier.prefetches == 1
        _, got = tier.load(1)
        assert tier.prefetch_hits == 1

    def test_spill_dir_file_tier(self, tmp_path):
        """With ``spill_dir`` the bytes ride the AsyncIOEngine file path
        and come back byte-identical; drop removes the file."""
        import os

        tier = HostKVTier(spill_dir=str(tmp_path))
        rng = np.random.default_rng(3)
        planes = self._planes(rng, 2)
        tier.store(5, [0, 1], planes)
        files = os.listdir(tmp_path)
        assert len(files) == 1
        _, got = tier.load(5)
        for w, g in zip(planes, got):
            np.testing.assert_array_equal(
                w.view(np.uint8), np.asarray(g).view(np.uint8))
        tier.drop(5)
        assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# Engine spill/fetch: byte identity, residency gate, atomicity
# ---------------------------------------------------------------------------


class TestEngineSpillFetch:
    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_spill_fetch_byte_exact(self, model_and_params, kv_dtype):
        """Spill + fetch restores every descriptor position's pool bytes
        (data AND scale planes — never re-quantized), into fresh blocks."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(kv_cache_dtype=kv_dtype))
        rng = np.random.default_rng(0)
        eng.put([3], [rng.integers(1, 90, size=21).tolist()])
        want = _planes_at(eng, 3)
        blocks0 = list(eng._seqs[3].blocks)
        free0 = eng.free_blocks
        n = eng.spill_sequence(3)
        assert n == len(blocks0) and eng.free_blocks == free0 + n
        assert not eng.is_resident(3)
        assert eng.tier.spilled(3) == list(range(n))
        got_n = eng.fetch_spilled(3)
        assert got_n == n and eng.is_resident(3)
        assert eng.free_blocks == free0
        assert eng.tier.spilled(3) == []   # tier entry dropped on commit
        got = _planes_at(eng, 3)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w.view(np.uint8), g.view(np.uint8))
        # the restored sequence decodes (fresh blocks are live KV)
        toks = eng.decode_loop([3], [5], 3)
        assert len(toks[0]) == 3

    def test_hot_tail_stays_resident(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params,
                                _icfg(hot_block_fraction=0.5))
        rng = np.random.default_rng(1)
        eng.put([1], [rng.integers(1, 90, size=30).tolist()])   # 4 blocks
        n = eng.spill_sequence(1)
        desc = eng._seqs[1]
        assert n == 2 and sorted(desc.spilled) == [0, 1]
        assert desc.blocks[2] >= 0 and desc.blocks[3] >= 0
        assert eng.spillable_blocks() == 0   # the rest is the hot tail

    def test_shared_prefix_blocks_not_spillable(self, model_and_params):
        """Refcount>1 blocks (prefix-cache shared) stay resident — another
        sequence may dispatch against them this tick."""
        model, params = model_and_params
        icfg = dataclasses.replace(_icfg(), prefix_caching=True)
        eng = InferenceEngineV2(model, params, icfg)
        rng = np.random.default_rng(2)
        prefix = rng.integers(1, 90, size=16).tolist()   # 2 full blocks
        eng.put([1], [prefix + [91]])
        eng.put([2], [prefix + [92]])   # shares the 2 prefix blocks
        n = eng.spill_sequence(1)
        desc = eng._seqs[1]
        assert 0 not in desc.spilled and 1 not in desc.spilled
        assert n == len(desc.blocks) - 2

    def test_dispatch_requires_residency(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        rng = np.random.default_rng(3)
        eng.put([1], [rng.integers(1, 90, size=12).tolist()])
        eng.spill_sequence(1)
        with pytest.raises(RuntimeError, match="fetch_spilled"):
            eng.decode_loop([1], [5], 2)
        with pytest.raises(RuntimeError, match="fetch_spilled"):
            eng.put([1], [[7]])
        with pytest.raises(RuntimeError, match="fetch_spilled"):
            eng.rewind(1, 1)
        with pytest.raises(RuntimeError, match="fetch_spilled"):
            eng.fork(1, 9)
        eng.fetch_spilled(1)
        eng.decode_loop([1], [5], 2)   # resident again — dispatch works

    def test_fetch_reject_is_atomic(self, model_and_params):
        """A fetch the free pool cannot fund refuses with engine AND tier
        exactly as before — then succeeds verbatim once blocks free up."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=12))
        rng = np.random.default_rng(4)
        eng.put([1], [rng.integers(1, 90, size=28).tolist()])   # 4 blocks
        eng.spill_sequence(1)
        eng.put([2], [rng.integers(1, 90, size=60).tolist()])   # hog the pool
        free0, stats0 = eng.free_blocks, eng.tier.stats()
        spilled0 = set(eng._seqs[1].spilled)
        with pytest.raises(RuntimeError, match="cannot fetch"):
            eng.fetch_spilled(1)
        assert eng.free_blocks == free0
        assert set(eng._seqs[1].spilled) == spilled0
        assert eng.tier.stats()["spilled_blocks"] == stats0["spilled_blocks"]
        eng.flush([2])
        assert eng.fetch_spilled(1) == len(spilled0)

    def test_flush_spilled_sequence_drops_tier_entry(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        rng = np.random.default_rng(5)
        eng.put([1], [rng.integers(1, 90, size=21).tolist()])
        free0 = eng.free_blocks + len(eng._seqs[1].blocks)
        eng.spill_sequence(1, keep_hot=1)   # mixed: spilled + resident
        eng.flush([1])
        assert eng.free_blocks == free0
        assert eng.tier.spilled(1) == [] and eng.tier.uids() == []

    def test_admission_refusal_names_reclaimable(self, model_and_params):
        """Tier-aware pressure accounting: a refused admission names the
        spillable (reclaimable-not-free) blocks next to the free count."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=10))
        rng = np.random.default_rng(6)
        eng.put([1], [rng.integers(1, 90, size=40).tolist()])   # 5 blocks
        ok, _, why = eng._admission_detail([2], [40])
        assert not ok and "reclaimable via kv_tier spill" in why
        assert eng.spillable_blocks() == 5
        assert eng.spillable_blocks(exclude=[1]) == 0


# ---------------------------------------------------------------------------
# Chaos: the kv_spill / kv_fetch fault sites
# ---------------------------------------------------------------------------


class TestChaos:
    def test_crash_mid_spill_leaves_everything_clean(self, model_and_params):
        """A replica dying mid-spill (after the host gather, before the
        tier store + allocator free) leaves pool, allocator, and host
        tier byte-identically unchanged — the sequence is still fully
        resident and a retried spill succeeds."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        rng = np.random.default_rng(7)
        eng.put([1], [rng.integers(1, 90, size=21).tolist()])
        want = _planes_at(eng, 1)
        blocks0 = list(eng._seqs[1].blocks)
        free0 = eng.free_blocks
        faults.arm("kv_spill")
        with pytest.raises(InjectedFault):
            eng.spill_sequence(1)
        assert eng._seqs[1].blocks == blocks0 and not eng._seqs[1].spilled
        assert eng.free_blocks == free0 and eng.is_resident(1)
        assert eng.tier.uids() == [] and eng.tier.stats()["spills"] == 0
        for w, g in zip(want, _planes_at(eng, 1)):
            np.testing.assert_array_equal(w.view(np.uint8), g.view(np.uint8))
        n = eng.spill_sequence(1)   # retry succeeds verbatim
        assert n == len(blocks0)

    def test_crash_mid_fetch_rolls_back_fresh_blocks(self, model_and_params):
        """A fetch killed after allocation frees the fresh blocks again;
        the tier entry survives untouched (NON-destructive load) and a
        retried fetch restores the exact bytes."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        rng = np.random.default_rng(8)
        eng.put([1], [rng.integers(1, 90, size=21).tolist()])
        want = _planes_at(eng, 1)
        eng.spill_sequence(1)
        free0 = eng.free_blocks
        spilled0 = set(eng._seqs[1].spilled)
        faults.arm("kv_fetch")
        with pytest.raises(InjectedFault):
            eng.fetch_spilled(1)
        assert eng.free_blocks == free0
        assert set(eng._seqs[1].spilled) == spilled0
        assert eng.tier.spilled(1) == sorted(spilled0)
        assert eng.fetch_spilled(1) == len(spilled0)
        for w, g in zip(want, _planes_at(eng, 1)):
            np.testing.assert_array_equal(w.view(np.uint8), g.view(np.uint8))

    def test_export_of_spilled_sequence_composes(self, model_and_params):
        """Failover KV-migration of a PARKED sequence: export_kv_blocks
        assembles the payload from both tiers (resident gather + host
        bytes) — byte-identical to a fully-resident export, with no fetch
        and no re-prefill — and imports into a second engine that decodes
        token-identically."""
        from shuffle_exchange_tpu.serving import KVTransferChannel

        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, 90, size=21).tolist()
        eng.put([1], [prompt])
        resident = eng.export_kv_blocks(1)
        eng.spill_sequence(1, keep_hot=1)    # park: cold prefix host-ward
        fetches0 = eng.tier.stats()["fetches"]
        parked = eng.export_kv_blocks(1)
        assert eng.tier.stats()["fetches"] == fetches0   # export != fetch
        for w, g in [(resident.k, parked.k), (resident.v, parked.v)]:
            np.testing.assert_array_equal(
                np.asarray(w).view(np.uint8), np.asarray(g).view(np.uint8))
        assert parked.tokens == resident.tokens
        # the payload lands on a survivor and continues decoding
        dst = InferenceEngineV2(model, params, _icfg())
        KVTransferChannel().transfer(eng, dst, 1, flush_src=False)
        ref = InferenceEngineV2(model, params, _icfg())
        ref.put([1], [prompt])
        first = int(np.argmax(ref._seqs[1].last_logits))
        assert (list(map(int, dst.decode_loop([1], [first], 4)[0]))
                == list(map(int, ref.decode_loop([1], [first], 4)[0])))


# ---------------------------------------------------------------------------
# Scheduler: park-instead-of-preempt
# ---------------------------------------------------------------------------


class TestSchedulerParking:
    def test_park_replaces_preempt_token_identical(self, model_and_params):
        """A pool sized below the trace's aggregate KV completes with
        parks (no preemptions) and exact token parity vs an
        unconstrained-pool reference."""
        model, params = model_and_params
        rng = np.random.default_rng(10)
        prompts = [rng.integers(1, 90, size=15).tolist() for _ in range(6)]

        ref_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        want = ContinuousBatchingScheduler(ref_eng).serve(
            prompts, max_new_tokens=8)

        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=12))
        sched = ContinuousBatchingScheduler(eng)
        got = sched.serve(prompts, max_new_tokens=8)
        assert got == want
        st = sched.stats()
        assert st["preemptions"] == 0
        assert st["kv_tier"]["parks"] > 0
        assert st["kv_tier"]["parks"] == st["kv_tier"]["unparks"]
        assert st["kv_tier"]["spilled_blocks"] == 0   # all fetched back
        assert st["kv_tier"]["fetches"] >= st["kv_tier"]["parks"]

    def test_tier_counters_ride_health_and_stats(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=12))
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(11)
        sched.serve([rng.integers(1, 90, size=15).tolist()
                     for _ in range(6)], max_new_tokens=8)
        h = sched.load()
        assert {"parked", "spillable_blocks"} <= set(h)
        assert 0.0 <= h["kv_pressure"] <= 1.0
        kt = sched.stats()["kv_tier"]
        assert {"spills", "fetches", "hit_rate", "prefetch_misses",
                "parks", "unparks"} <= set(kt)
        assert sched.knobs()["spill_enabled"] is True

    @pytest.mark.slow   # 4s e2e serve; nightly via ci_full (tier-1 budget)
    def test_hot_fraction_serve_token_parity(self, model_and_params):
        """hot_block_fraction > 0 (tail blocks of parked sequences stay
        resident) keeps the park/unpark loop token-exact."""
        model, params = model_and_params
        rng = np.random.default_rng(14)
        prompts = [rng.integers(1, 90, size=15).tolist() for _ in range(6)]
        ref_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        want = ContinuousBatchingScheduler(ref_eng).serve(
            prompts, max_new_tokens=8)
        eng = InferenceEngineV2(model, params, _icfg(
            num_kv_blocks=12, hot_block_fraction=0.5))
        sched = ContinuousBatchingScheduler(eng)
        got = sched.serve(prompts, max_new_tokens=8)
        assert got == want
        assert sched.stats()["kv_tier"]["parks"] > 0

    @pytest.mark.slow   # 4s e2e serve; nightly via ci_full (tier-1 budget)
    def test_park_probes_older_actives_when_youngest_unspillable(
            self, model_and_params):
        """When the youngest active has nothing spillable (here: a short
        sequence kept fully resident by hot_block_fraction), the park
        scan must probe OLDER actives before falling back to preemption
        — preempt only when nothing on the replica can spill."""
        model, params = model_and_params
        rng = np.random.default_rng(16)
        pa = rng.integers(1, 90, size=50).tolist()   # 7 blocks, spills 1
        pb = rng.integers(1, 90, size=24).tolist()   # 4 blocks, all hot
        ref_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        want = ContinuousBatchingScheduler(ref_eng).serve(
            [pa, pb], max_new_tokens=8)
        eng = InferenceEngineV2(model, params, _icfg(
            num_kv_blocks=12, hot_block_fraction=0.8))
        sched = ContinuousBatchingScheduler(eng)
        got = sched.serve([pa, pb], max_new_tokens=8)
        assert got == want
        st = sched.stats()
        assert st["preemptions"] == 0, (
            "youngest-unspillable pressure must park an older active, "
            "not preempt")
        assert st["kv_tier"]["parks"] > 0

    @pytest.mark.slow   # 4s e2e serve; nightly via ci_full (tier-1 budget)
    def test_force_unpark_reclaims_hot_tails_before_stall(
            self, model_and_params):
        """When everything is parked and the head's fetch cannot be
        funded, the force-unpark must spill the OTHER parked sequences'
        resident (hot-tail) blocks before raising 'serving stalled' — a
        pool that could still serve must serve. The armed state needs
        parks at different pressure moments (the pool oversubscribes
        across time), built here with the scheduler's own park/fetch
        primitives."""
        model, params = model_and_params
        rng = np.random.default_rng(15)
        pa = rng.integers(1, 90, size=50).tolist()   # 7 blocks at seen 56
        pb = rng.integers(1, 90, size=24).tolist()   # 4 blocks, never grows
        ref_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        want = ContinuousBatchingScheduler(ref_eng).serve(
            [pa, pb], max_new_tokens=8)

        eng = InferenceEngineV2(
            model, params,
            _icfg(num_kv_blocks=12, hot_block_fraction=0.75))
        sched = ContinuousBatchingScheduler(eng)
        a = sched.submit(pa, max_new_tokens=8)
        b = sched.submit(pb, max_new_tokens=8)
        # drive A to a block boundary (seen 56 = 7 full blocks; the +1
        # decode-write surcharge arms the unpark need), B co-resident
        for _ in range(30):
            if a in eng._seqs and eng._seqs[a].seen_tokens >= 56:
                break
            sched.tick()
        assert eng._seqs[a].seen_tokens == 56
        # park both at 0.75 hot fraction (A keeps 6 resident, spills 1;
        # B keeps 3, spills 1), then refetch B's spilled block: B sits
        # parked fully resident — the hot-tail shape a grown-then-parked
        # sequence leaves — and the free pool is below A's unpark need
        assert sched._park(sched.requests[a])
        assert sched._park(sched.requests[b])
        eng.fetch_spilled(b)
        need = len(eng._seqs[a].spilled) + 1   # spilled fetch + boundary
        assert need > eng.free_blocks, "stall corner not armed"
        spills_before = eng.tier.spills
        assert sched.tick()   # pre-fix: RuntimeError('serving stalled')
        assert eng.tier.spills > spills_before   # B's hot tail reclaimed
        while sched.tick():
            pass
        got = {u: sched.requests[u].generated for u in (a, b)}
        assert got == want

    @pytest.mark.slow   # 4s e2e serve; nightly via ci_full (tier-1 budget)
    def test_parked_head_not_starved_by_younger_arrivals(
            self, model_and_params):
        """Seniority under pressure: while a parked sequence waits for its
        unpark window, younger queue arrivals must NOT be admitted — they
        would absorb every freed block chunk-by-chunk and the parked head
        (the oldest request on the replica) could starve against the
        all-at-once unpark gate. Tokens stay exact for everyone."""
        model, params = model_and_params
        rng = np.random.default_rng(13)
        prompts = [rng.integers(1, 90, size=15).tolist() for _ in range(8)]

        ref_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        want = ContinuousBatchingScheduler(ref_eng).serve(
            prompts, max_new_tokens=8)

        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=12))
        sched = ContinuousBatchingScheduler(eng)
        first = [sched.submit(p, max_new_tokens=8) for p in prompts[:4]]
        while not sched.parked and sched.tick():
            pass
        assert sched.parked, "probe never parked — shrink the pool"
        younger = {sched.submit(p, max_new_tokens=8) for p in prompts[4:]}
        while True:
            active_before = {r.uid for r in sched.active}
            alive = sched.tick()
            gained = {r.uid for r in sched.active} - active_before
            if sched.parked:
                # the tick ended with a sequence still parked, so the
                # queue lane must not have admitted past it
                assert not (younger & gained), (
                    f"younger arrivals {younger & gained} overtook the "
                    f"parked head {sched.parked[0].uid}")
            if not alive:
                break
        got = {u: sched.requests[u].generated
               for u in first + sorted(younger)}
        assert got == want
        assert sched.stats()["kv_tier"]["parks"] > 0

    @pytest.mark.slow   # 4s e2e serve; nightly via ci_full (tier-1 budget)
    def test_drain_exports_parked_requests(self, model_and_params):
        """Elastic drain with parked requests: the export drops both the
        resident blocks and the host-tier entries, and the replayed
        requests finish elsewhere token-identically (zero lost)."""
        model, params = model_and_params
        rng = np.random.default_rng(12)
        prompts = [rng.integers(1, 90, size=15).tolist() for _ in range(6)]
        ref_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        want = ContinuousBatchingScheduler(ref_eng).serve(
            prompts, max_new_tokens=8)

        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=12))
        sched = ContinuousBatchingScheduler(eng)
        uids = [sched.submit(p, max_new_tokens=8) for p in prompts]
        for _ in range(6):
            sched.tick()
        # force at least one park before draining
        if not sched.parked:
            for _ in range(10):
                sched.tick()
                if sched.parked:
                    break
        exported = sched.export_requests()
        assert eng.tier.uids() == [] and not sched.parked
        assert eng.free_blocks == eng.allocator.num_blocks - 1
        dst_eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=40))
        dst = ContinuousBatchingScheduler(dst_eng)
        for r in exported:
            dst.inject(r)
        while dst.tick():
            pass
        got = {u: dst.requests[u].generated for u in uids}
        assert got == want
