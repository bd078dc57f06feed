"""Speculative decoding inside the one-dispatch serving step (ISSUE 8).

Contracts pinned here:
  (a) EXACT-token parity with the sequential put()+decode_loop reference
      across k in {1, 2, 4}, for both drafters, including under
      KV-pressure preemption -> requeue (greedy, bf16/f32 KV);
  (b) one dispatch per tick survives speculation (compile-count assert)
      and the warmed server never recompiles (shape-bin ladder, verify
      widths on the k ladder);
  (c) steps-per-emitted-token < 0.67 at k=4 with the self-speculation
      drafter on a repetitive-suffix workload (the ISSUE acceptance bar);
  (d) rejected drafts roll paged-KV state back — written-token history,
      block refcounts, prefix-cache commit chain — atomically, with the
      committed/ref-shared rewind refusing to corrupt shared blocks
      (targeted error + COW fallback, PR 6 allocator-test discipline);
  (e) prefix_caching x speculative x kv_cache_dtype compose.
"""

import dataclasses

import numpy as np
import pytest

import jax

from shuffle_exchange_tpu.config import ConfigError
from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                            DraftModelDrafter,
                                            InferenceConfig,
                                            InferenceEngineV2, NGramDrafter,
                                            ServingConfig, SpeculativeConfig,
                                            make_drafter)
from shuffle_exchange_tpu.models import Transformer, tiny


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
               activation="swiglu", norm="rmsnorm", position="rope",
               n_kv_heads=2, tie_embeddings=False)
    model = Transformer(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _icfg(num_kv_blocks=64, k=4, spec=True, **kw):
    serving = {"token_budget": 64, "max_running": 4, "chunk_min": 4,
               "speculative": {"enabled": spec, "k": k}}
    serving.update(kw.pop("serving", {}))
    return InferenceConfig(dtype="float32", max_seq_len=128, kv_block_size=8,
                           num_kv_blocks=num_kv_blocks, serving=serving, **kw)


def _reference(model, params, prompt, n_new, **kw):
    eng = InferenceEngineV2(model, params, InferenceConfig(
        dtype="float32", max_seq_len=128, kv_block_size=8, num_kv_blocks=64,
        **kw))
    lg = eng.put([0], [prompt])
    first = int(np.argmax(lg[0]))
    if n_new == 1:
        return [first]
    toks = eng.decode_loop([0], [first], n_new - 1)
    return [first] + [int(t) for t in toks[0]]


def _repetitive_prompts(rng, n=3, period=4, lo=20, hi=28):
    cyc = rng.integers(1, 90, size=period).tolist()
    return [(cyc * 12)[:int(rng.integers(lo, hi))] for _ in range(n)]


def _cycle_walker(model, params, cyc):
    """``params`` rebuilt so that greedy decoding from any token of ``cyc``
    emits the next one, for ever: the layers add nothing to the residual
    (zero out-projection and down-projection), each cycle token embeds to
    its own unit vector, and the unembedding of its successor is that
    vector. The final norm only rescales, so the successor's logit wins."""
    import jax.numpy as jnp

    layers = dict(params["layers"])
    for name in ("wo", "w_down"):
        layers[name] = jnp.zeros_like(layers[name])
    embed = jnp.zeros_like(params["embed"])
    unembed = jnp.zeros_like(params["unembed"])
    for i, tok in enumerate(cyc):
        embed = embed.at[tok, i].set(1.0)
        unembed = unembed.at[i, cyc[(i + 1) % len(cyc)]].set(1.0)
    return {**params, "layers": layers, "embed": embed, "unembed": unembed}


class TestParity:
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_ngram_speculative_matches_sequential_reference(
            self, model_and_params, k):
        """Self-speculation serving emits byte-identical token streams to
        the sequential reference at every k — on prompts WITH repetitive
        structure (drafts fire, some reject) and without (drafts rarely
        fire)."""
        model, params = model_and_params
        rng = np.random.default_rng(k)
        prompts = _repetitive_prompts(rng, n=2) + [
            rng.integers(1, 90, size=int(n)).tolist() for n in (11, 7)]
        want = [_reference(model, params, p, 16) for p in prompts]
        eng = InferenceEngineV2(model, params, _icfg(k=k))
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=16)
        assert [out[u] for u in out] == want
        assert eng.free_blocks == eng.allocator.num_blocks - 1
        st = sched.stats()["speculative"]
        assert st["proposed"] == st["accepted"] + st["rejected"]

    @pytest.mark.slow
    def test_draft_model_matches_reference_full_and_zero_acceptance(
            self, model_and_params):
        """Draft-model speculation is exact at BOTH extremes: a draft
        model identical to the target accepts everything; a mismatched
        draft model rejects everything and the corrections still
        reproduce the reference chain token for token."""
        model, params = model_and_params
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 90, size=int(n)).tolist()
                   for n in (15, 9)]
        want = [_reference(model, params, p, 12) for p in prompts]
        icfg = _icfg()

        eng = InferenceEngineV2(model, params, icfg)
        same = DraftModelDrafter.for_target(model, params, icfg)
        sched = ContinuousBatchingScheduler(eng, drafter=same)
        out = sched.serve(prompts, max_new_tokens=12)
        st = sched.stats()["speculative"]
        assert [out[u] for u in out] == want
        assert st["acceptance_rate"] == 1.0 and st["rollbacks"] == 0
        assert st["drafter"] == "DraftModelDrafter"
        # draft engine cleaned up alongside the target
        assert same.engine.free_blocks == same.engine.allocator.num_blocks - 1

        other = model.init(jax.random.PRNGKey(9))
        eng2 = InferenceEngineV2(model, params, icfg)
        sched2 = ContinuousBatchingScheduler(
            eng2, drafter=DraftModelDrafter.for_target(model, other, icfg))
        out2 = sched2.serve(prompts, max_new_tokens=12)
        st2 = sched2.stats()["speculative"]
        assert [out2[u] for u in out2] == want
        assert st2["accepted"] == 0 and st2["rollbacks"] > 0
        assert eng2.spec_rolled_tokens == st2["rejected"]
        assert eng2.free_blocks == eng2.allocator.num_blocks - 1

    def test_rollback_under_preemption_requeue(self, model_and_params):
        """A pool sized to force preemption mid-speculation: the preempted
        request replays token-identically (its generated continuation is
        all verifier-approved greedy tokens), rejected-draft rewinds and
        preemption-flushes compose, and nothing leaks."""
        model, params = model_and_params
        rng = np.random.default_rng(7)
        prompts = [(rng.integers(1, 90, size=4).tolist() * 8)[:20],
                   (rng.integers(1, 90, size=4).tolist() * 8)[:18]]
        want = [_reference(model, params, p, 12) for p in prompts]
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=7))
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=12)
        assert sched.preemptions > 0, "pool was sized to force preemption"
        assert [out[u] for u in out] == want
        assert eng.free_blocks == eng.allocator.num_blocks - 1

    def test_kv_pressure_demotes_verify_rows_before_preempting(
            self, model_and_params):
        """Draft widths are optional work: when the pool can hold every
        running sequence's +1 token but not the +1+k verify widths, the
        scheduler demotes verify rows to plain decode instead of
        preempting (a preempt flushes KV and replays the whole prefill).
        Pool arithmetic: 2 prompts of 8 (1 block each) + 8 new tokens
        (2 blocks each at finish) fit 5 usable blocks; the transient +5
        verify ask near block boundaries does not."""

        class ConstantDrafter:
            def propose(self, uid, history, k):
                return [1] * k

            def forget(self, uid):
                pass

        model, params = model_and_params
        rng = np.random.default_rng(47)
        prompts = [rng.integers(1, 90, size=8).tolist() for _ in range(2)]
        want = [_reference(model, params, p, 8) for p in prompts]
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=6))
        sched = ContinuousBatchingScheduler(eng, drafter=ConstantDrafter())
        out = sched.serve(prompts, max_new_tokens=8)
        assert sched.preemptions == 0, (
            "verify-width pressure must demote, not preempt")
        assert sched.stats()["speculative"]["proposed"] > 0, (
            "pool was sized to allow SOME verify rows")
        assert [out[u] for u in out] == want
        assert eng.free_blocks == eng.allocator.num_blocks - 1

    def test_draft_model_proposals_are_batched_per_tick(
            self, model_and_params):
        """propose_many: one tick's draft work for N running sequences is
        one sync put() plus one decode_loop on the draft engine — not one
        dispatch pair per sequence."""
        model, params = model_and_params
        icfg = _icfg()
        eng = InferenceEngineV2(model, params, icfg)
        dr = DraftModelDrafter.for_target(model, params, icfg)
        sched = ContinuousBatchingScheduler(eng, drafter=dr)
        rng = np.random.default_rng(53)
        uids = [sched.submit(rng.integers(1, 90, size=int(n)).tolist(),
                             max_new_tokens=12) for n in (6, 9, 7)]
        while not all(sched.requests[u].state == "running" for u in uids):
            sched.tick()
        d0, p0 = dr.engine.dispatch_count, sched.spec_proposed
        sched.tick()
        assert sched.spec_proposed > p0, "tick carried no draft rows"
        assert dr.engine.dispatch_count - d0 <= 3, (
            "draft dispatches must not scale with the running set")
        sched.drain()
        assert [sched.requests[u].generated for u in uids] == [
            _reference(model, params, sched.requests[u].prompt, 12)
            for u in uids]

    def test_streaming_order_with_multi_token_ticks(self, model_and_params):
        model, params = model_and_params
        rng = np.random.default_rng(8)
        streamed = []
        eng = InferenceEngineV2(model, params, _icfg())
        sched = ContinuousBatchingScheduler(
            eng, on_token=lambda uid, tok: streamed.append((uid, tok)))
        out = sched.serve(_repetitive_prompts(rng, n=2), max_new_tokens=10)
        for uid, toks in out.items():
            assert [t for u, t in streamed if u == uid] == toks


class TestOneDispatchAndCompiles:
    def test_one_dispatch_per_tick_with_speculation(self, model_and_params):
        """The tentpole contract survives speculation: decode rows, verify
        rows AND prefill chunks of a tick are ONE compiled dispatch (the
        same-model draft drafter proposes every tick, so verify rows are
        guaranteed; its own dispatches hit the DRAFT engine only)."""
        model, params = model_and_params
        icfg = _icfg()
        eng = InferenceEngineV2(model, params, icfg)
        sched = ContinuousBatchingScheduler(
            eng, drafter=DraftModelDrafter.for_target(model, params, icfg))
        rng = np.random.default_rng(1)
        for n in (10, 18, 7):
            sched.submit(rng.integers(1, 90, size=int(n)).tolist(),
                         max_new_tokens=10)
        d0 = eng.dispatch_count
        while sched.tick():
            pass
        assert eng.dispatch_count - d0 == sched.ticks
        assert any(k[0] == "spec" for k in eng.program_shapes), (
            "no tick carried a verify row")
        assert sched.stats()["speculative"]["accepted"] > 0

    def test_warmed_server_zero_recompile_and_ladder_shapes(
            self, model_and_params):
        """A varied speculative workload compiles a bounded program set —
        verify widths off the k ladder, everything else powers of two /
        chunk bins — and an identical second workload on the warmed
        engine compiles NOTHING new."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        sv = eng.config.serving

        def workload():
            sched = ContinuousBatchingScheduler(eng)
            rq = np.random.default_rng(11)
            prompts = _repetitive_prompts(rq, n=4) + [
                rq.integers(1, 90, size=int(n)).tolist()
                for n in rq.integers(3, 30, size=4)]
            news = [int(n) for n in rq.integers(4, 14, size=len(prompts))]
            sched.serve(list(zip(prompts, news)))
            return sched

        sched = workload()
        shapes = eng.program_shapes
        assert sched.ticks > 0 and any(k[0] == "spec" for k in shapes)

        def pow2(n):
            return n & (n - 1) == 0

        for key in shapes:
            if key[0] != "spec":
                continue
            _, bd, wd, bp, c, wp, bs_, cs, ws = key
            for n in (bd, wd, bp, wp, bs_, ws):
                assert n == 0 or pow2(n), key
            assert c == 0 or c == sv.bin_chunk(c), key
            # verify width = k-ladder bin + 1 (the pending token)
            assert cs >= 2 and cs - 1 == sv.speculative.bin_k(cs - 1), key
        assert len(shapes) <= 24, sorted(shapes)
        workload()
        assert eng.program_shapes == shapes

    def test_steps_per_emitted_token_bar(self, model_and_params):
        """The ISSUE acceptance bar: k=4 self-speculation on a
        repetitive-suffix workload measures < 0.67 decode steps per
        emitted token per sequence (>= 1.5x fewer steps than k=0).

        Grounded on a CONSTRUCTED target, not a random model's argmax: the
        weights below make greedy decoding walk a fixed 4-token cycle, so
        every n-gram draft is the verifier's own next token and must be
        accepted. (The bar used to ride the seeded random weights' luck:
        their greedy output happened to be repetitive enough under jax
        0.4.37 and missed `base / 1.5` by 0.008 once jax 0.5 flipped
        `jax_threefry_partitionable` and `PRNGKey(0)` drew other weights.)"""
        model, params = model_and_params
        cyc = [11, 47, 5, 80]
        params = _cycle_walker(model, params, cyc)
        rng = np.random.default_rng(5)
        prompts = [(cyc * 12)[:int(rng.integers(20, 28))] for _ in range(3)]
        eng = InferenceEngineV2(model, params, _icfg(k=4))
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=40)
        for p, toks in zip(prompts, out.values()):
            at = cyc.index(p[-1])
            assert toks == [cyc[(at + 1 + i) % 4] for i in range(40)]
        st = sched.stats()["speculative"]
        assert st["proposed"] > 0 and st["acceptance_rate"] == 1.0, st
        assert st["steps_per_emitted_token"] < 0.67, st
        # the k=0 baseline on the same trace sits near 1.0
        eng0 = InferenceEngineV2(model, params, _icfg(spec=False))
        s0 = ContinuousBatchingScheduler(eng0)
        s0.serve(prompts, max_new_tokens=40)
        base = s0.stats()["speculative"]["steps_per_emitted_token"]
        assert base > 0.9
        assert st["steps_per_emitted_token"] < base / 1.5


class TestCounters:
    def test_speculative_counter_group_through_monitor(self,
                                                       model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(17)
        sched.serve(_repetitive_prompts(rng, n=2), max_new_tokens=12)
        mm = sched.memory_monitor
        st = sched.stats()["speculative"]
        assert mm.latest("speculative/proposed") == st["proposed"] > 0
        assert mm.latest("speculative/accepted") == st["accepted"]
        assert mm.latest("speculative/rejected") == st["rejected"]
        assert mm.latest("speculative/rollbacks") == st["rollbacks"]
        rate = mm.latest("speculative/acceptance_rate")
        assert rate == pytest.approx(st["acceptance_rate"])
        assert st["proposed"] == st["accepted"] + st["rejected"]

    def test_no_speculative_events_when_disabled(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(spec=False))
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(18)
        sched.serve([rng.integers(1, 90, size=9).tolist()],
                    max_new_tokens=4)
        assert sched.memory_monitor.latest("speculative/proposed") is None
        assert sched.stats()["speculative"]["enabled"] is False


class TestComposeMatrix:
    # the quantized corners run in the nightly ci_full.sh pass (slow):
    # tier-1 keeps the bf16 exact-parity column, which is the contract the
    # acceptance criteria bind on; int8/fp8 add the determinism check
    @pytest.mark.parametrize("kv_dtype", [
        "bf16",
        pytest.param("int8", marks=pytest.mark.slow),
        pytest.param("fp8", marks=pytest.mark.slow),
    ])
    @pytest.mark.parametrize("prefix_caching", [False, True])
    def test_prefix_cache_x_speculative_x_kv_dtype(self, model_and_params,
                                                   prefix_caching, kv_dtype):
        """The compose matrix: speculative serving under every
        kv_cache_dtype with and without prefix caching. bf16 KV keeps the
        exact-parity contract; quantized KV keeps DETERMINISM (two
        identical runs emit identical tokens — the documented
        approximate-vs-sequential contract from PR 6) plus clean pools
        and consistent counters."""
        model, params = model_and_params
        rng = np.random.default_rng(19)
        shared = rng.integers(1, 90, size=16).tolist()
        warm = shared + rng.integers(1, 90, size=5).tolist()
        prompts = [shared + (rng.integers(1, 90, size=3).tolist() * 4)
                   for _ in range(2)]

        def run():
            eng = InferenceEngineV2(model, params, _icfg(
                prefix_caching=prefix_caching, kv_cache_dtype=kv_dtype))
            sched = ContinuousBatchingScheduler(eng)
            # warm request first, alone, so its shared-prefix blocks are
            # committed before the batch arrives (concurrent admissions
            # in one tick can't hit each other's uncommitted blocks)
            sched.serve([warm], max_new_tokens=4)
            out = sched.serve(prompts, max_new_tokens=10)
            return eng, sched, [out[u] for u in out]

        eng, sched, got = run()
        st = sched.stats()
        assert all(len(t) == 10 for t in got)
        assert st["speculative"]["proposed"] > 0
        if prefix_caching:
            assert st["prefix_cache"]["hit_tokens"] > 0
        if kv_dtype == "bf16":
            want = [_reference(model, params, p, 10,
                               kv_cache_dtype=kv_dtype) for p in prompts]
            assert got == want
        else:
            _, _, again = run()
            assert got == again
        assert eng.free_blocks == eng.allocator.num_blocks - 1


class TestDisaggCompose:
    @pytest.mark.slow
    def test_speculative_step_on_imported_sequence(self, model_and_params):
        """Disagg front passthrough (PR 7): a sequence whose KV arrived
        over the prefill->decode wire is an ordinary descriptor — the
        decode side's speculative config applies to it unchanged, and a
        verify row on it reproduces the reference chain exactly."""
        model, params = model_and_params
        rng = np.random.default_rng(43)
        prompt = rng.integers(1, 90, size=14).tolist()
        want = _reference(model, params, prompt, 5)
        pre = InferenceEngineV2(model, params, _icfg(spec=False))
        dec = InferenceEngineV2(model, params, _icfg())   # speculative cfg
        pre.put([0], [prompt])
        payload = pre.export_kv_blocks(0)
        resv = dec.begin_import(0, payload.seen_tokens)
        dec.commit_import(resv, payload)
        t0 = int(np.argmax(dec._seqs[0].last_logits))
        assert t0 == want[0]
        # draft the true continuation -> full accept plus the bonus token
        _, _, sres = dec.step([], [], [],
                              speculative=[(0, [t0] + want[1:4])])
        [(a, emitted)] = sres
        assert a == 3 and emitted == want[1:5]


class TestRewind:
    """Satellite 2: paged-KV rewind vs the prefix-cache commit chain —
    refuse/COW on committed ref-shared blocks, atomic on failure
    (mirrors PR 6's allocator double-free discipline)."""

    def _committed_pair(self, model_and_params):
        """uid 0 prefilled with a 16-token prompt (2 committed blocks),
        uid 1 admitted sharing both committed blocks live."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(prefix_caching=True))
        rng = np.random.default_rng(23)
        prompt = rng.integers(1, 90, size=16).tolist()
        eng.put([0], [prompt])
        eng.put([1], [prompt + [5]])
        assert eng._seqs[1].blocks[:2] == eng._seqs[0].blocks[:2]
        assert eng.allocator.ref_count(eng._seqs[0].blocks[1]) == 2
        return eng, prompt

    def test_rewind_into_shared_committed_block_takes_cow(
            self, model_and_params):
        eng, prompt = self._committed_pair(model_and_params)
        shared = eng._seqs[0].blocks[1]
        cow0 = eng.cow_copies
        eng.rewind(0, 12)   # into committed block 1, shared with uid 1
        assert eng.cow_copies == cow0 + 1
        assert eng._seqs[0].blocks[1] != shared
        assert eng.allocator.ref_count(shared) == 1     # uid 1 keeps it
        assert eng._seqs[0].seen_tokens == 12
        assert eng._seqs[0].committed == 1
        assert eng._seqs[0].tokens == prompt[:12]
        # uid 1 is untouched and still decodes
        d1 = eng._seqs[1]
        assert d1.seen_tokens == 17 and d1.tokens[:16] == prompt
        eng.put([1], [[7]])   # still serveable

    def test_rewind_cow_refused_when_pool_dry_is_atomic(
            self, model_and_params):
        """The targeted error: a rewind that needs a COW clone with zero
        free blocks refuses BEFORE mutating anything."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(
            num_kv_blocks=4, prefix_caching=True))
        rng = np.random.default_rng(29)
        prompt = rng.integers(1, 90, size=16).tolist()
        eng.put([0], [prompt])                  # 2 blocks (+1 scratch)
        eng.put([1], [prompt + [5]])            # shares 2, allocates 1
        assert eng.free_blocks == 0
        d0 = eng._seqs[0]
        seen0, blocks0 = d0.seen_tokens, list(d0.blocks)
        committed0, key0 = d0.committed, d0.last_key
        with pytest.raises(RuntimeError, match=r"block \d+ is a committed "
                                               r"prefix block shared by 2"):
            eng.rewind(0, 12)
        assert d0.seen_tokens == seen0 and d0.blocks == blocks0
        assert d0.committed == committed0 and d0.last_key == key0
        assert eng.free_blocks == 0
        # freeing the sharer funds the clone and the rewind succeeds
        eng.flush([1])
        eng.rewind(0, 12)
        assert d0.seen_tokens == 12

    def test_rewind_exclusive_committed_block_unregisters(
            self, model_and_params):
        """Rewinding into a committed block we hold exclusively drops its
        content registration — a later admission must MISS (the bytes are
        about to change under the key)."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(prefix_caching=True))
        rng = np.random.default_rng(31)
        prompt = rng.integers(1, 90, size=16).tolist()
        eng.put([0], [prompt])
        hit, _, _ = eng.prefix_peek(prompt + [5])
        assert hit == 16
        eng.rewind(0, 12)
        hit, _, _ = eng.prefix_peek(prompt + [5])
        assert hit == 8, "invalidated block 1 must not be addressable"
        assert eng._seqs[0].committed == 1

    def test_rewind_frees_surplus_blocks_and_parks_valid_content(
            self, model_and_params):
        """Whole committed blocks PAST the rewind boundary return to the
        allocator with their registration intact (the bytes still match
        the key), so a re-proposed chain can hit them parked."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(prefix_caching=True))
        rng = np.random.default_rng(37)
        prompt = rng.integers(1, 90, size=24).tolist()
        eng.put([0], [prompt])
        free0 = eng.free_blocks
        eng.rewind(0, 8)     # drop blocks 1 and 2 whole
        assert eng.free_blocks == free0 + 2
        _, live, parked = eng.prefix_peek(prompt + [5])
        assert live == 1 and parked == 2

    def test_unregister_shared_block_raises(self):
        from shuffle_exchange_tpu.inference import BlockedAllocator

        alloc = BlockedAllocator(4)
        [b] = alloc.allocate(1)
        alloc.register(b"k1", b)
        alloc.retain([b])
        with pytest.raises(ValueError, match="refcount 2"):
            alloc.unregister(b)
        alloc.free([b])
        alloc.unregister(b)    # refcount 1 now: legal

    def test_rewind_validation(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        with pytest.raises(ValueError, match="unknown uid 42"):
            eng.rewind(42, 1)
        eng.put([0], [[3, 4, 5]])
        with pytest.raises(ValueError, match=r"in \[1, seen_tokens=3\]"):
            eng.rewind(0, 0)
        with pytest.raises(ValueError, match=r"in \[1, seen_tokens=3\]"):
            eng.rewind(0, 7)
        eng.rewind(0, 3)   # no-op


class TestEngineStepAPI:
    def test_spec_row_validation(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        eng.put([1], [[5, 6, 7]])
        with pytest.raises(ValueError, match="speculative uid 9 unknown"):
            eng.step([], [], [], speculative=[(9, [1, 2])])
        with pytest.raises(ValueError, match="belongs in decode_uids"):
            eng.step([], [], [], speculative=[(1, [4])])
        with pytest.raises(ValueError, match="never two at once"):
            eng.step([1], [9], [], speculative=[(1, [4, 5])])

    def test_spec_step_returns_three_tuple_and_rolls_back(
            self, model_and_params):
        """Direct step(speculative=...) API: the 3-tuple result, the
        greedy acceptance semantics, and the KV rewind are visible at the
        engine level (what the scheduler builds on)."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        lg = eng.put([0], [[5, 6, 7, 8]])
        t0 = int(np.argmax(lg[0]))
        # drafts the verifier cannot have produced (the verifier's token
        # after t0 equals the plain-decode reference, and a draft equal to
        # it would be accepted — pick the other candidate): expect the
        # correction to equal the reference decode token and the rejected
        # slots rolled back
        ref = InferenceEngineV2(model, params, _icfg())
        ref.put([9], [[5, 6, 7, 8]])
        want = int(np.argmax(ref.put([9], [[t0]])[0]))
        bad = 1 if want != 1 else 2
        _, _, sres = eng.step([], [], [], speculative=[(0, [t0, bad, bad])])
        [(a, emitted)] = sres
        assert a == 0 and emitted == [want]
        assert eng._seqs[0].seen_tokens == 5      # prompt 4 + t0 only
        assert eng._seqs[0].tokens == [5, 6, 7, 8, t0]
        assert eng.spec_rollbacks == 1


class TestDrafters:
    def test_ngram_drafter_matches_most_recent_occurrence(self):
        d = NGramDrafter(ngram=2)
        h = [1, 2, 9, 9, 1, 2, 7, 7, 1, 2]
        # trailing [1, 2]: most recent earlier occurrence at index 4 -> [7, 7]
        assert d.propose(0, h, 4) == [7, 7, 1, 2]
        assert d.propose(0, h, 1) == [7]
        assert d.propose(0, [1, 2, 3], 4) == []          # no earlier match
        assert d.propose(0, [1, 2], 4) == []             # history too short
        assert d.propose(0, h, 0) == []

    def test_draft_model_drafter_tracks_rejections(self, model_and_params):
        """The draft engine mirrors the target's ACCEPTED history: after a
        rejection the next propose() rewinds the draft cache past the
        stale suffix and keeps proposing from the corrected history."""
        model, params = model_and_params
        icfg = _icfg()
        d = DraftModelDrafter.for_target(model, params, icfg)
        hist = [3, 4, 5, 6]
        out1 = d.propose(0, hist, 3)
        assert len(out1) == 3
        # pretend the verifier rejected everything and corrected to 42
        hist2 = hist + [42]
        out2 = d.propose(0, hist2, 3)
        assert len(out2) == 3
        assert d.engine._seqs[0].tokens[:5] == hist2
        d.forget(0)
        assert d.engine.free_blocks == d.engine.allocator.num_blocks - 1

    def test_make_drafter_from_config(self, model_and_params):
        model, params = model_and_params
        ng = make_drafter(SpeculativeConfig(enabled=True, k=4, ngram=3))
        assert isinstance(ng, NGramDrafter) and ng.ngram == 3
        with pytest.raises(ConfigError, match="draft_model"):
            make_drafter(SpeculativeConfig(enabled=True, drafter="model"))
        dm = make_drafter(SpeculativeConfig(enabled=True, drafter="model"),
                          like=_icfg(), draft=(model, params))
        assert isinstance(dm, DraftModelDrafter)
        assert dm.engine.config.max_seq_len == 128
        # the draft engine itself must not recurse into speculation
        assert not dm.engine.config.serving.speculative.enabled


class TestEligibilityGate:
    """Satellite 1: k>1 speculative width gates fused-decode routing
    explicitly instead of silently mis-routing verify rows."""

    def test_eligibility_records_verify_gate(self, model_and_params):
        from shuffle_exchange_tpu.models.transformer import (
            decode_fusion_eligibility)

        mcfg = model_and_params[0].config
        elig = decode_fusion_eligibility(mcfg)
        assert elig["verify"] is None
        elig4 = decode_fusion_eligibility(mcfg, speculative_k=4)
        assert "5 tokens wide" in elig4["verify"]
        assert "paged-extend" in elig4["verify"]
        # the plain-decode entries are untouched by the spec width
        assert elig4["qkv"] == elig["qkv"] and elig4["mlp"] == elig["mlp"]

    def test_resolver_warns_once_on_speculative_pallas(self, monkeypatch):
        from shuffle_exchange_tpu.ops.dispatch import resolve_decode_kernel
        from shuffle_exchange_tpu.utils import logging as sxt_logging

        warned = []
        monkeypatch.setattr(sxt_logging, "warning_once", warned.append)
        assert resolve_decode_kernel("xla", speculative_k=4) == "xla"
        assert not warned, "the XLA path needs no routing warning"
        assert resolve_decode_kernel("pallas", speculative_k=4) == "pallas"
        assert len(warned) == 1
        assert "verify rows" in warned[0] and "5 tokens" in warned[0]
        assert resolve_decode_kernel("pallas") == "pallas"
        assert len(warned) == 1, "k=0 must not warn"

    def test_engine_resolves_with_speculation_configured(
            self, model_and_params):
        """An engine built with speculation on still resolves its decode
        kernel (xla on CPU) and serves — the gate is advisory routing,
        not a construction error."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        assert eng._decode_kernel in ("xla", "pallas")


class TestRouterPassthrough:
    @pytest.mark.slow
    def test_router_passes_speculative_config_per_replica(
            self, model_and_params):
        """The fleet front (PR 7) passes serving.speculative through per
        replica unchanged: each replica's scheduler speculates from its
        engine's own config, routed serving stays token-identical to the
        k=0 single engine, and the fleet stats()/FleetMonitor aggregate
        the speculative counter group."""
        from shuffle_exchange_tpu.serving import ReplicaRouter

        model, params = model_and_params
        rng = np.random.default_rng(41)
        prompts = _repetitive_prompts(rng, n=4)
        want = [_reference(model, params, p, 10) for p in prompts]
        router = ReplicaRouter([
            InferenceEngineV2(model, params, _icfg()),
            InferenceEngineV2(model, params, _icfg())])
        for rep in router.replicas:
            assert rep.scheduler.spec.enabled and rep.scheduler.spec.k == 4
            assert isinstance(rep.scheduler.drafter, NGramDrafter)
        out = router.serve(prompts, max_new_tokens=10)
        assert [out[u] for u in sorted(out)] == want
        st = router.stats()["speculative"]
        assert st["enabled"] and st["proposed"] > 0
        assert st["proposed"] == st["accepted"] + st["rejected"]
        agg = router.publish()
        assert agg["speculative"]["proposed"] == st["proposed"]


class TestConfig:
    def test_speculative_config_validation(self):
        with pytest.raises(ConfigError, match="k must be an int >= 1"):
            SpeculativeConfig(k=0)
        with pytest.raises(ConfigError, match='"ngram" or "model"'):
            SpeculativeConfig(drafter="oracle")
        with pytest.raises(ConfigError, match="ngram must be an int >= 1"):
            SpeculativeConfig(ngram=0)
        with pytest.raises(ConfigError, match="cover k=8"):
            SpeculativeConfig(k=8, k_bins=[1, 2, 4])
        sc = SpeculativeConfig(k=4)
        assert sc.bins() == (1, 2, 4)
        assert sc.bin_k(3) == 4 and sc.bin_k(1) == 1 and sc.bin_k(9) == 16

    def test_token_budget_must_cover_speculative_width(self):
        with pytest.raises(ConfigError, match="max_running \\* "
                                              "\\(speculative.k \\+ 1\\)"):
            ServingConfig(token_budget=16, max_running=4,
                          speculative={"enabled": True, "k": 4})
        ServingConfig(token_budget=20, max_running=4, chunk_min=4,
                      speculative={"enabled": True, "k": 4})

    def test_from_dict_rejects_unknown_speculative_keys(self):
        with pytest.raises(ConfigError,
                           match="unknown serving.speculative config keys"):
            InferenceConfig.from_dict(
                {"serving": {"speculative": {"kk": 2}}})
        cfg = InferenceConfig.from_dict(
            {"serving": {"token_budget": 64, "max_running": 4,
                         "speculative": {"enabled": True, "k": 2,
                                         "drafter": "ngram", "ngram": 3}}})
        sp = cfg.serving.speculative
        assert sp.enabled and sp.k == 2 and sp.ngram == 3
