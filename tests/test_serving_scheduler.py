"""Continuous-batching serving scheduler (ISSUE 5): Dynamic-SplitFuse
ticks must be (a) exact-token-identical to the sequential put()+decode_loop
reference, (b) ONE dispatch per tick, (c) compile-bounded by the shape-bin
ladder, (d) starvation-free for running decodes, and (e) correct through
KV-exhaustion preemption/requeue.
"""

import numpy as np
import pytest

import jax

from shuffle_exchange_tpu.config import ConfigError
from shuffle_exchange_tpu.inference import (ContinuousBatchingScheduler,
                                            InferenceConfig,
                                            InferenceEngineV2, ServingConfig)
from shuffle_exchange_tpu.models import Transformer, tiny


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
               activation="swiglu", norm="rmsnorm", position="rope",
               n_kv_heads=2, tie_embeddings=False)
    model = Transformer(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _icfg(num_kv_blocks=40, **serving):
    serving = {"token_budget": 16, "max_running": 4, "chunk_min": 4,
               **serving}
    return InferenceConfig(dtype="float32", max_seq_len=64, kv_block_size=8,
                           num_kv_blocks=num_kv_blocks, serving=serving)


def _reference(model, params, prompt, n_new):
    """The sequential serving reference: one put() prefill, then the fused
    decode_loop — the engine-parity oracle the scheduler must reproduce."""
    eng = InferenceEngineV2(model, params, _icfg())
    lg = eng.put([0], [prompt])
    first = int(np.argmax(lg[0]))
    if n_new == 1:
        return [first]
    toks = eng.decode_loop([0], [first], n_new - 1)
    return [first] + [int(t) for t in toks[0]]


class TestParity:
    def test_scheduled_serving_matches_sequential_reference(self, model_and_params):
        """Mixed prefill+decode ticks produce EXACTLY the tokens the
        sequential put()+decode_loop path does, for every request, under
        concurrent admission."""
        model, params = model_and_params
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 90, size=int(n)).tolist()
                   for n in (12, 5, 22, 9)]
        want = [_reference(model, params, p, 8) for p in prompts]
        eng = InferenceEngineV2(model, params, _icfg())
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=8)
        assert [out[u] for u in out] == want
        # every admitted sequence was flushed on finish: pool fully free
        assert eng.free_blocks == eng.allocator.num_blocks - 1

    def test_one_dispatch_per_tick(self, model_and_params):
        """The whole mixed batch of a tick — decodes AND prefill chunks —
        is ONE compiled dispatch (the tentpole contract)."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(1)
        for n in (10, 18, 7):
            sched.submit(rng.integers(1, 90, size=n).tolist(), max_new_tokens=6)
        d0 = eng.dispatch_count
        while sched.tick():
            pass
        assert eng.dispatch_count - d0 == sched.ticks
        # and ticks actually mixed phases at least once
        assert any(k[0] == "mixed" for k in eng.program_shapes)

    def test_preemption_requeue_identical_output(self, model_and_params):
        """6 usable blocks x 8 slots < the two requests' total KV: the
        youngest sequence is preempted, requeued, replayed — and every
        request's tokens still match the uninterrupted reference."""
        model, params = model_and_params
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 90, size=20).tolist(),
                   rng.integers(1, 90, size=18).tolist()]
        want = [_reference(model, params, p, 12) for p in prompts]
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=7))
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=12)
        assert sched.preemptions > 0, "pool was sized to force preemption"
        assert [out[u] for u in out] == want
        assert sched.memory_monitor.latest("serving/preemptions") == sched.preemptions

    def test_streaming_tokens_arrive_in_order(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        streamed = []
        sched = ContinuousBatchingScheduler(
            eng, on_token=lambda uid, tok: streamed.append((uid, tok)))
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 90, size=6).tolist(),
                   rng.integers(1, 90, size=11).tolist()]
        out = sched.serve(prompts, max_new_tokens=5)
        for uid, toks in out.items():
            assert [t for u, t in streamed if u == uid] == toks


class TestScheduling:
    def test_compile_count_bounded_by_shape_bin_ladder(self, model_and_params):
        """A long, varied workload compiles a bounded program set (shapes
        only from the bin ladder), and a SECOND identical workload on the
        warmed engine compiles nothing new — the production property that
        a warmed server never recompiles."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        cfg = eng.config.serving
        rng = np.random.default_rng(3)

        def workload():
            sched = ContinuousBatchingScheduler(eng)
            rq = np.random.default_rng(7)
            prompts = [rq.integers(1, 90, size=int(n)).tolist()
                       for n in rq.integers(3, 30, size=10)]
            news = [int(n) for n in rq.integers(2, 9, size=10)]
            sched.serve(list(zip(prompts, news)))
            return sched

        sched = workload()
        shapes = eng.program_shapes
        assert sched.ticks > len(shapes), (sched.ticks, shapes)
        # every shape comes off the ladder: powers of two for batch/width,
        # serving chunk bins for C
        def pow2(n):
            return n & (n - 1) == 0
        for key in shapes:
            if key[0] == "mixed":
                _, bd, wd, bp, c, wp = key
                assert all(map(pow2, (bd, wd, bp, wp))), key
                assert c == cfg.bin_chunk(c), key
            elif key[0] == "decode":
                assert all(map(pow2, key[1:])), key
            elif key[0] == "extend":
                _, bp, c, wp = key
                assert pow2(bp) and pow2(wp) and c == cfg.bin_chunk(c), key
        assert len(shapes) <= 20, sorted(shapes)
        # warmed server: the same trace again adds zero program shapes
        workload()
        assert eng.program_shapes == shapes

    def test_long_prefill_cannot_stall_running_decodes(self, model_and_params):
        """Starvation bound: while a long prompt chews through chunked
        prefill, every running sequence still advances one token per tick,
        and no chunk exceeds budget - running."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(
            num_kv_blocks=40, token_budget=8, max_running=4, chunk_min=2))
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(4)
        a = sched.submit(rng.integers(1, 90, size=5).tolist(), max_new_tokens=30)
        b = sched.submit(rng.integers(1, 90, size=6).tolist(), max_new_tokens=30)
        while not all(sched.requests[u].state == "running" for u in (a, b)):
            sched.tick()
        long_uid = sched.submit(rng.integers(1, 90, size=40).tolist(),
                                max_new_tokens=2)
        long_req = sched.requests[long_uid]
        prefill_ticks = 0
        while long_req.state in ("queued", "prefill"):
            ga, gb = (len(sched.requests[u].generated) for u in (a, b))
            done_before = long_req.prefill_done
            sched.tick()
            prefill_ticks += 1
            # running decodes advanced this tick despite the long prefill
            assert len(sched.requests[a].generated) == ga + 1
            assert len(sched.requests[b].generated) == gb + 1
            # the chunk obeyed the budget with both decodes packed
            assert long_req.prefill_done - done_before <= 8 - 2
        assert prefill_ticks >= 40 // 6, "prompt should take several chunks"
        sched.drain()
        assert sched.requests[long_uid].state == "finished"

    def test_serving_counters_through_memory_monitor(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(5)
        sched.serve([rng.integers(1, 90, size=9).tolist() for _ in range(3)],
                    max_new_tokens=4)
        mm = sched.memory_monitor
        assert len(mm.values("serving/ttft_s")) == 3
        assert len(mm.values("serving/tpot_s")) == 3 * 3   # max_new-1 per req
        assert mm.values("serving/budget_fill")
        assert all(0 < f <= 1 for f in mm.values("serving/budget_fill"))
        assert mm.latest("serving/queue_depth") == 0
        st = sched.stats()
        assert st["requests"] == 3 and st["generated_tokens"] == 12
        assert st["ttft_p50_s"] > 0 and st["tpot_p50_s"] > 0

    def test_arrival_trace_defers_submission(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 90, size=5).tolist() for _ in range(3)]
        out = sched.serve(prompts, max_new_tokens=3,
                          arrivals=[0.0, 0.0, 0.05])
        assert len(out) == 3
        assert all(len(t) == 3 for t in out.values())
        # the late arrival was submitted measurably after the first two
        subs = sorted(r.submitted_at for r in sched.requests.values())
        assert subs[2] - subs[0] >= 0.04

    def test_poisson_trace_at_twice_capacity_keeps_parity_and_goodput(
            self, model_and_params):
        """A seeded Poisson trace offered at 2x the capacity an all-at-once
        pass measured: every request's tokens are the sequential
        reference's and ``stats()`` reports the goodput group."""
        from shuffle_exchange_tpu.autotuning import PoissonTrace

        model, params = model_and_params
        trace = PoissonTrace.generate(11, vocab=90, n_requests=6,
                                      prompt_lo=4, prompt_hi=20, max_new=5)
        prompts = trace.prompt_lists()
        want = [_reference(model, params, p, trace.max_new) for p in prompts]
        eng = InferenceEngineV2(model, params, _icfg())
        first = ContinuousBatchingScheduler(eng)
        first.serve(prompts, max_new_tokens=trace.max_new)
        capacity = first.stats()["sustained_tokens_per_sec"]
        assert capacity > 0
        trace = trace.with_load(capacity, 2.0)
        sched = ContinuousBatchingScheduler(eng)
        out = sched.serve(prompts, max_new_tokens=trace.max_new,
                          arrivals=trace.arrival_list())
        assert [out[u] for u in out] == want
        st = sched.stats()
        assert st["requests"] == 6 and st["generated_tokens"] == 30
        assert st["sustained_tokens_per_sec"] > 0
        assert st["ttft_p95_s"] >= st["ttft_p50_s"] > 0 and st["tpot_p50_s"] > 0
        fill = sched.memory_monitor.values("serving/budget_fill")
        assert fill and all(0 < f <= 1 for f in fill)


class TestAdmissionErrors:
    def test_put_kv_exhaustion_names_numbers(self, model_and_params):
        """ISSUE 5 satellite: put() admission failures name needed vs free
        KV blocks and the offending uid, like decode_loop's do."""
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=5))
        with pytest.raises(RuntimeError,
                           match=r"needs \d+ KV blocks, \d+ free.*uid 7"):
            eng.put([7], [list(range(1, 50))])

    def test_put_seq_len_overrun_names_uid(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        with pytest.raises(RuntimeError, match=r"uid 3 would overrun "
                                               r"max_seq_len: 0 seen \+ 70"):
            eng.put([3], [list(range(70))])

    def test_step_rejects_dual_role_uid(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg())
        eng.put([1], [[5, 6, 7]])
        with pytest.raises(ValueError,
                           match="either decoding, prefilling or verifying"):
            eng.step([1], [9], [(1, [4, 4])])
        with pytest.raises(ValueError, match="decode uid 42 unknown"):
            eng.step([42], [1], [])

    def test_step_leaves_state_untouched_on_rejection(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=6))
        eng.put([1], [[5, 6, 7]])
        free0, seen0 = eng.free_blocks, eng._seqs[1].seen_tokens
        with pytest.raises(RuntimeError, match="KV blocks"):
            eng.step([1], [9], [(2, list(range(1, 40)))])
        assert eng.free_blocks == free0
        assert eng._seqs[1].seen_tokens == seen0
        assert 2 not in eng._seqs

    def test_submit_validation_names_limits(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngineV2(model, params, _icfg(num_kv_blocks=4))
        sched = ContinuousBatchingScheduler(eng)
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            sched.submit(list(range(60)), max_new_tokens=10)
        with pytest.raises(ValueError, match="KV blocks but the pool has"):
            sched.submit(list(range(30)), max_new_tokens=10)


class TestServingConfig:
    def test_ladder_and_validation(self):
        sv = ServingConfig(token_budget=64, chunk_min=8)
        assert sv.bins() == (8, 16, 32, 64)
        assert sv.bin_chunk(1) == 8 and sv.bin_chunk(20) == 32
        assert sv.bin_chunk(65) == 128   # direct step() callers stay binned
        with pytest.raises(ConfigError, match="max_running"):
            ServingConfig(token_budget=4, max_running=8)
        with pytest.raises(ConfigError, match="chunk_min"):
            ServingConfig(token_budget=4, max_running=2, chunk_min=8)

    def test_from_dict_rejects_unknown_serving_keys(self):
        with pytest.raises(ConfigError, match="unknown serving config keys"):
            InferenceConfig.from_dict({"serving": {"token_bugdet": 64}})
        cfg = InferenceConfig.from_dict(
            {"serving": {"token_budget": 128, "chunk_bins": [32, 64]}})
        assert cfg.serving.token_budget == 128
        assert cfg.serving.bins() == (32, 64)
