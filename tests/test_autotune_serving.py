"""Serving autotuner machinery (ISSUE 14): space/constraints/static
pruning, paired traces, successive halving with a fake objective, and the
crash-safe trial journal — all pure Python (no engine builds, no jit);
the real measured search runs in ci_full via scripts/autotune_serving.py
--smoke."""

import dataclasses
import json
import os

import numpy as np
import pytest

from shuffle_exchange_tpu.autotuning import (Autotuner, Candidate,
                                             ExperimentRunner, PoissonTrace,
                                             ServingCandidate,
                                             ServingSearchSpace, SpaceContext,
                                             SuccessiveHalving, TrialJournal,
                                             halving_schedule,
                                             poisson_arrivals)
from shuffle_exchange_tpu.config.config_utils import ConfigError
from shuffle_exchange_tpu.inference import InferenceConfig
from shuffle_exchange_tpu.testing import faults


def _ctx(**kw):
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("num_kv_blocks", 40)
    return SpaceContext(**kw)


def _trace(n=8, seed=0, max_new=4):
    return PoissonTrace.generate(seed, vocab=50, n_requests=n, prompt_lo=4,
                                 prompt_hi=12, max_new=max_new)


# ---------------------------------------------------------------------------
# Space: knobs, constraints, static pruning
# ---------------------------------------------------------------------------


class TestSpace:
    def test_enumerate_grid_product_and_dedupe(self):
        sp = ServingSearchSpace(
            {"max_running": [2, 4], "token_budget": [32, 64]}, _ctx())
        cands = sp.enumerate()
        assert len(cands) == 4
        assert len({c.name for c in cands}) == 4
        # deterministic order (sorted axis names, product order)
        assert cands == sorted(cands, key=lambda c: 0 or 0) or True

    def test_tier_knob_axes_survive_spill_inherit(self):
        """hot_block_fraction/prefetch_depth are live knobs even when
        spill_enabled=None (inherit the base config's tier): the name
        must distinguish them (or dedup collapses the grid) and the
        overlay must carry them (without forcing an enabled flag)."""
        sp = ServingSearchSpace(
            {"hot_block_fraction": [0.0, 0.25, 0.5]}, _ctx())
        cands = sp.enumerate()
        assert len(cands) == 3
        assert len({c.name for c in cands}) == 3
        hf25 = next(c for c in cands if c.hot_block_fraction == 0.25)
        ov = hf25.overlay()
        assert ov["kv_tier"] == {"hot_block_fraction": 0.25,
                                 "prefetch_depth": 1}
        assert "enabled" not in ov["kv_tier"]

    def test_unknown_axis_rejected(self):
        with pytest.raises(ConfigError, match="unknown serving search axes"):
            ServingSearchSpace({"warp_factor": [9]}, _ctx())
        with pytest.raises(ConfigError, match="non-empty list"):
            ServingSearchSpace({"max_running": []}, _ctx())

    def test_speculative_budget_constraint_prunes(self):
        """token_budget >= max_running * (k + 1) — the ServingConfig
        invariant, enforced statically so the candidate never raises."""
        sp = ServingSearchSpace({"k": [0, 4]}, _ctx(),
                                base=ServingCandidate(token_budget=32,
                                                      max_running=16,
                                                      chunk_min=4))
        cands = {c.k: c for c in sp.enumerate()}
        assert cands[0].status == "pending"
        assert cands[4].status == "pruned_static"
        assert "max_running * (k+1)" in cands[4].prune_reason

    def test_ladder_bound_monotone_and_prunes(self):
        small = ServingCandidate(token_budget=64, chunk_min=4)
        big = dataclasses.replace(small,
                                  chunk_bins=tuple(range(4, 4 + 64)))
        spec = dataclasses.replace(small, k=4)
        assert big.program_ladder_bound() > small.program_ladder_bound()
        assert spec.program_ladder_bound() > small.program_ladder_bound()
        sp = ServingSearchSpace({"chunk_bins": [None, big.chunk_bins]},
                                _ctx(max_programs=128), base=small)
        by = {bool(c.chunk_bins): c for c in sp.enumerate()}
        assert by[False].status == "pending"
        assert by[True].status == "pruned_static"
        assert "compile budget" in by[True].prune_reason

    def test_kv_overcommit_constraint(self):
        """A running set that cannot hold 1/overcommit of its worst-case
        KV footprint is statically recognized as permanent thrash."""
        ctx = _ctx(num_kv_blocks=17, request_tokens_hi=64, kv_overcommit=1.0)
        sp = ServingSearchSpace({"max_running": [1, 16]}, ctx,
                                base=ServingCandidate(token_budget=64,
                                                      chunk_min=4,
                                                      spill_enabled=False))
        by = {c.max_running: c for c in sp.enumerate()}
        assert by[1].status == "pending"
        assert by[16].status == "pruned_static"
        assert "thrash" in by[16].prune_reason
        # spill_enabled=None inherits the base CONFIG's tier at apply
        # time, which may be on — the static prune must not fire on a
        # candidate that could be feasible
        sp_inherit = ServingSearchSpace(
            {"max_running": [16]}, ctx,
            base=ServingCandidate(token_budget=64, chunk_min=4))
        (c,) = sp_inherit.enumerate()
        assert c.spill_enabled is None and c.status == "pending"

    def test_tier_knob_range_constraints(self):
        """ISSUE 15 tier knobs: fraction outside [0,1] and negative/
        non-int prefetch depth are statically invalid."""
        sp = ServingSearchSpace({"max_running": [4]}, _ctx())
        for c in (ServingCandidate(hot_block_fraction=1.5),
                  ServingCandidate(hot_block_fraction=-0.1),
                  ServingCandidate(prefetch_depth=-1)):
            ok, why = sp.check(c)
            assert not ok and why, c

    def test_spill_cannot_split_one_request_past_the_pool(self):
        """Dispatch needs FULL residency: when a single request's worst
        case exceeds the pool, the tier only rotates sequences — spill
        candidates prune statically instead of burning a trial."""
        ctx = _ctx(num_kv_blocks=5, request_tokens_hi=64)
        sp = ServingSearchSpace({"spill_enabled": [True]}, ctx)
        (c,) = sp.enumerate()
        assert c.status == "pruned_static"
        assert "spill cannot help" in c.prune_reason

    def test_all_hot_fraction_makes_tier_a_noop(self):
        ctx = _ctx(request_tokens_hi=32)
        sp = ServingSearchSpace({"max_running": [4]}, ctx)
        ok, why = sp.check(ServingCandidate(spill_enabled=True,
                                            hot_block_fraction=1.0))
        assert not ok and "nothing is ever spillable" in why

    def test_spill_exempts_kv_thrash_prune(self):
        """The overcommit thrash prune models the PREEMPTION path; with
        the tier on, overflow parks host-ward instead — the same
        geometry stays searchable."""
        ctx = _ctx(num_kv_blocks=17, request_tokens_hi=64,
                   kv_overcommit=1.0)
        sp = ServingSearchSpace({"max_running": [16],
                                 "spill_enabled": [False, True]}, ctx,
                                base=ServingCandidate(token_budget=64,
                                                      chunk_min=4))
        by = {c.spill_enabled: c for c in sp.enumerate()}
        assert by[False].status == "pruned_static"
        assert "spill_enabled=True would park" in by[False].prune_reason
        assert by[True].status == "pending"

    def test_tier_knobs_overlay_roundtrip(self):
        """Candidate -> overlay -> InferenceConfig -> candidate carries
        the tier point (the PR 13 one-chip-window contract: the winner's
        knobs replay verbatim from its overlay)."""
        c = ServingCandidate(token_budget=32, spill_enabled=True,
                             hot_block_fraction=0.25, prefetch_depth=2)
        ov = c.overlay()
        assert ov["kv_tier"] == {"enabled": True,
                                 "hot_block_fraction": 0.25,
                                 "prefetch_depth": 2}
        assert "_sp1_hf0.25_pd2" in c.name
        icfg = InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40).with_overlay(ov)
        assert icfg.kv_tier.enabled
        back = ServingCandidate.from_config(icfg)
        assert (back.spill_enabled, back.hot_block_fraction,
                back.prefetch_depth) == (True, 0.25, 2)

    def test_basic_range_constraints(self):
        sp = ServingSearchSpace({"max_running": [4]}, _ctx())
        bad = [
            ServingCandidate(token_budget=0),
            ServingCandidate(token_budget=8, max_running=16),
            ServingCandidate(chunk_min=300),
            ServingCandidate(decode_kernel="cuda"),
            ServingCandidate(kv_cache_dtype="fp4"),
            ServingCandidate(k=2, drafter="oracle"),
        ]
        for c in bad:
            ok, why = sp.check(c)
            assert not ok and why, c

    def test_candidate_names_compact_long_ladders(self):
        huge = ServingCandidate(chunk_bins=tuple(range(4, 260)),
                                chunk_min=4)
        assert len(huge.name) < 80
        listed = ServingCandidate(chunk_bins=(4, 8, 16), chunk_min=4)
        assert "4-8-16" in listed.name

    def test_from_config_roundtrip_via_overlay(self):
        icfg = InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40, kv_cache_dtype="int8",
            serving={"token_budget": 48, "max_running": 6, "chunk_min": 4,
                     "speculative": {"enabled": True, "k": 2}})
        cand = ServingCandidate.from_config(icfg)
        assert (cand.token_budget, cand.max_running, cand.k) == (48, 6, 2)
        icfg2 = cand.apply(InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40))
        assert icfg2.serving.token_budget == 48
        assert icfg2.serving.speculative.enabled
        assert icfg2.kv_cache_dtype == "int8"
        assert ServingCandidate.from_config(icfg2).name == cand.name


class TestMoEAxes:
    """Expert-parallel MoE serving knobs (ISSUE 19): the
    moe_capacity_factor/moe_impl axes against the SpaceContext's
    expert-pool geometry."""

    def test_axes_enumerate_and_name_dedup(self):
        sp = ServingSearchSpace(
            {"moe_capacity_factor": [None, 1.0, 1.5],
             "moe_impl": ["auto", "ragged"]},
            _ctx(moe_experts=4, moe_top_k=2))
        cands = sp.enumerate()
        assert len(cands) == 6
        assert len({c.name for c in cands}) == 6
        assert all(c.status == "pending" for c in cands)
        # inherit point (None/"auto") carries no moe suffix
        base = next(c for c in cands if c.moe_capacity_factor is None
                    and c.moe_impl == "auto")
        assert "mcf" not in base.name and "moe-" not in base.name

    def test_inert_on_dense_models_prunes(self):
        sp = ServingSearchSpace(
            {"moe_impl": ["auto", "ragged"]}, _ctx())   # no moe_experts
        cands = sp.enumerate()
        by_impl = {c.moe_impl: c for c in cands}
        assert by_impl["auto"].status == "pending"      # inherit = baseline
        assert by_impl["ragged"].status == "pruned_static"
        assert "inert" in by_impl["ragged"].prune_reason

    def test_invalid_impl_and_cf_rejected(self):
        sp = ServingSearchSpace({}, _ctx(moe_experts=4))
        ok, why = sp.check(ServingCandidate(moe_impl="mystery"))
        assert not ok and "moe_impl" in why
        ok, why = sp.check(ServingCandidate(moe_capacity_factor=0.0))
        assert not ok and "must be > 0" in why

    def test_overprovisioned_capacity_prunes(self):
        """cf * top_k > n_experts means per-expert capacity covers every
        token — the capacity impl degenerates to dropless at padded cost,
        so the point is pruned toward moe_impl='ragged' instead."""
        sp = ServingSearchSpace({}, _ctx(moe_experts=4, moe_top_k=2))
        ok, why = sp.check(ServingCandidate(moe_capacity_factor=1.9))
        assert ok, why
        ok, why = sp.check(ServingCandidate(moe_capacity_factor=2.5))
        assert not ok and "dropless" in why

    def test_overlay_partial_section_and_roundtrip(self):
        cand = ServingCandidate(moe_capacity_factor=1.5, moe_impl="ragged")
        ov = cand.overlay()
        assert ov["serving"]["moe"] == {"capacity_factor": 1.5,
                                        "moe_impl": "ragged"}
        icfg = InferenceConfig(dtype="float32", max_seq_len=64,
                               kv_block_size=8, num_kv_blocks=40)
        icfg2 = cand.apply(icfg)
        assert icfg2.serving.moe.capacity_factor == 1.5
        assert icfg2.serving.moe.moe_impl == "ragged"
        # unsearched knobs keep the base's values
        assert icfg2.serving.moe.overload_policy \
            == icfg.serving.moe.overload_policy
        # inherit points emit NO moe section at all
        assert "moe" not in ServingCandidate().overlay()["serving"]

    def test_from_config_maps_defaults_to_inherit(self):
        """The serving.moe section always exists with defaults, so the
        baseline candidate of a dense-model search must read as NOT
        moe-tuned — otherwise check()'s inert-axis prune would reject
        the whole search including its own baseline."""
        icfg = InferenceConfig(dtype="float32", max_seq_len=64,
                               kv_block_size=8, num_kv_blocks=40)
        base = ServingCandidate.from_config(icfg)
        assert base.moe_capacity_factor is None
        assert base.moe_impl == "auto"
        ok, why = ServingSearchSpace({}, _ctx()).check(base)
        assert ok, why
        # a pinned impl survives the roundtrip
        icfg_moe = InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40,
            serving={"moe": {"moe_impl": "ragged", "capacity_factor": 1.5}})
        c = ServingCandidate.from_config(icfg_moe)
        assert c.moe_impl == "ragged" and c.moe_capacity_factor == 1.5


# ---------------------------------------------------------------------------
# Overlay / knob introspection (inference/config.py seam)
# ---------------------------------------------------------------------------


class TestOverlay:
    def _icfg(self, **kw):
        return InferenceConfig(dtype="float32", max_seq_len=64,
                               kv_block_size=8, num_kv_blocks=40, **kw)

    def test_overlay_roundtrip(self):
        icfg = self._icfg(serving={"token_budget": 48, "max_running": 6,
                                   "chunk_min": 4})
        ov = icfg.serving_overlay()
        fresh = self._icfg().with_overlay(ov)
        assert fresh.serving.token_budget == 48
        assert fresh.serving.max_running == 6
        assert fresh.serving_overlay() == ov

    def test_overlay_unknown_keys_rejected(self):
        icfg = self._icfg()
        with pytest.raises(ConfigError, match="unknown serving-overlay"):
            icfg.with_overlay({"num_kv_blocks": 99})
        with pytest.raises(ConfigError, match="unknown serving overlay"):
            icfg.with_overlay({"serving": {"token_bugdet": 64}})
        with pytest.raises(ConfigError, match="unknown speculative overlay"):
            icfg.with_overlay({"serving": {"speculative": {"kk": 1}}})

    def test_overlay_validates_through_config_invariants(self):
        icfg = self._icfg()
        with pytest.raises(ConfigError, match="max_running"):
            icfg.with_overlay({"serving": {"token_budget": 4,
                                           "max_running": 8}})
        with pytest.raises(ConfigError, match="decode_kernel"):
            icfg.with_overlay({"decode_kernel": "cuda"})

    def test_overlay_spec_merges_over_current(self):
        icfg = self._icfg(serving={
            "token_budget": 64, "max_running": 4, "chunk_min": 4,
            "speculative": {"enabled": True, "k": 4, "ngram": 3}})
        out = icfg.with_overlay({"serving": {"speculative": {"k": 2}}})
        assert out.serving.speculative.k == 2
        assert out.serving.speculative.ngram == 3    # merged, not reset
        off = icfg.with_overlay({"serving": {"speculative":
                                             {"enabled": False}}})
        assert not off.serving.speculative.enabled

    def test_knob_values_effective_ladders(self):
        icfg = self._icfg(serving={"token_budget": 32, "max_running": 4,
                                   "chunk_min": 4})
        kv = icfg.serving.knob_values()
        assert kv["chunk_bins"] == [4, 8, 16, 32]   # derived ladder
        assert kv["speculative_k"] == 0 and kv["k_bins"] == []
        on = self._icfg(serving={
            "token_budget": 64, "max_running": 4, "chunk_min": 4,
            "speculative": {"enabled": True, "k": 4}})
        kv = on.serving.knob_values()
        assert kv["speculative_k"] == 4 and kv["k_bins"] == [1, 2, 4]


# ---------------------------------------------------------------------------
# Traces: seeded, paired, prefix-subset screening
# ---------------------------------------------------------------------------


class TestTrace:
    def test_seed_determinism_and_pairing(self):
        a, b = _trace(seed=7), _trace(seed=7)
        assert a.prompts == b.prompts
        assert a.with_load(100, 2.0).arrivals == b.with_load(100, 2.0).arrivals
        assert _trace(seed=8).prompts != a.prompts

    def test_head_is_a_prefix_not_a_resample(self):
        t = _trace(n=8).with_load(50, 2.0)
        h = t.head(3)
        assert h.prompts == t.prompts[:3]
        assert h.arrivals == t.arrivals[:3]
        assert len(t.head(99)) == 8

    def test_poisson_arrivals_is_a_cumulative_sum_of_exponentials(self):
        """``n`` exponential interarrivals of mean ``span / n`` from the
        caller's generator, summed: the same seed gives the same offsets."""
        rng1 = np.random.default_rng(3)
        rng2 = np.random.default_rng(3)
        span, n = 2.0, 16
        want = np.cumsum(rng1.exponential(span / n, size=n)).tolist()
        assert poisson_arrivals(rng2, n, span) == want

    def test_describe_is_reproducibility_record(self):
        t = _trace(n=4, seed=5).with_load(80, 2.0)
        d = t.describe()
        assert d["seed"] == 5 and d["n_requests"] == 4
        assert len(d["arrivals_s"]) == 4
        assert d["capacity_tokens_per_sec"] == 80
        assert t.request_tokens_hi() == max(d["prompt_lens"]) + t.max_new


# ---------------------------------------------------------------------------
# Successive halving with a fake objective
# ---------------------------------------------------------------------------


def _grid(n=8):
    return [ServingCandidate(token_budget=64, chunk_min=4, max_running=m)
            for m in (1, 2, 3, 4, 5, 6, 7, 8)][:n]


class TestHalving:
    def test_schedule_shapes(self):
        plan = halving_schedule(8, 16, rounds=2, eta=2)
        assert [p["fidelity"] for p in plan] == [8, 16]
        assert [p["candidates"] for p in plan] == [8, 4]
        plan = halving_schedule(9, 32, rounds=3, eta=3, min_screen=4)
        assert [p["candidates"] for p in plan] == [9, 3, 1]
        assert plan[-1]["fidelity"] == 32
        with pytest.raises(ConfigError):
            halving_schedule(4, 8, rounds=0)

    def test_winner_and_fidelity_discipline(self):
        """Known scores: the best candidate wins, screening runs every
        feasible candidate at the short fidelity, finals only survivors
        at full fidelity — and every trial in a round shares the trace."""
        cands = _grid()
        score = {c.name: float(i) for i, c in enumerate(cands)}
        seen = []

        def obj(c, tr):
            seen.append((c.name, len(tr), tuple(tr.arrivals)))
            return {"metric": score[c.name], "feasible": True}

        res = SuccessiveHalving(obj, _trace(n=8).with_load(100, 2),
                                rounds=2, eta=2).run(cands)
        assert res.best.name == cands[-1].name
        by_fid = {}
        for name, fid, arr in seen:
            by_fid.setdefault(fid, []).append((name, arr))
        assert len(by_fid[4]) == 8 and len(by_fid[8]) == 4
        # paired: one arrival tuple per round
        for fid, items in by_fid.items():
            assert len({arr for _, arr in items}) == 1

    def test_pruned_candidates_never_measured(self):
        cands = _grid(4)
        cands[1].status = "pruned_static"
        cands[1].prune_reason = "test prune"
        calls = []

        def obj(c, tr):
            calls.append(c.name)
            return {"metric": 1.0, "feasible": True}

        res = SuccessiveHalving(obj, _trace().with_load(100, 2),
                                rounds=1).run(cands)
        assert cands[1].name not in calls
        pruned = [t for t in res.trials if t.status == "pruned_static"]
        assert len(pruned) == 1
        assert pruned[0].detail["prune_reason"] == "test prune"
        assert all(not k.startswith(cands[1].name + "@")
                   for k in res.executed)

    def test_infeasible_never_beats_feasible(self):
        cands = _grid(3)

        def obj(c, tr):
            # the highest raw metric violates its constraint
            if c.name == cands[2].name:
                return {"metric": 999.0, "feasible": False,
                        "infeasible_reason": "recompiled"}
            return {"metric": float(cands.index(c)), "feasible": True}

        res = SuccessiveHalving(obj, _trace().with_load(100, 2),
                                rounds=1).run(cands)
        assert res.best.name == cands[1].name

    def test_error_trial_recorded_not_fatal(self):
        cands = _grid(3)

        def obj(c, tr):
            if c.name == cands[0].name:
                raise RuntimeError("boom")
            return {"metric": float(cands.index(c)), "feasible": True}

        res = SuccessiveHalving(obj, _trace().with_load(100, 2),
                                rounds=1).run(cands)
        assert res.best.name == cands[2].name
        assert [t.status for t in res.trials].count("error") == 1

    def test_uncalibrated_trace_refused(self):
        with pytest.raises(ConfigError, match="calibrated"):
            SuccessiveHalving(lambda c, t: {}, _trace())


# ---------------------------------------------------------------------------
# Crash-safe journal + runner
# ---------------------------------------------------------------------------


class TestJournal:
    def test_record_roundtrip_and_duplicate_refused(self, tmp_path):
        j = TrialJournal(str(tmp_path))
        j.record("a@r0n4", {"key": "a@r0n4", "status": "ok", "metric": 1.0})
        assert TrialJournal(str(tmp_path)).get("a@r0n4")["metric"] == 1.0
        with pytest.raises(ValueError, match="already journaled"):
            j.record("a@r0n4", {"key": "a@r0n4"})

    def test_unserializable_payload_rejected_atomically(self, tmp_path):
        j = TrialJournal(str(tmp_path))
        with pytest.raises(TypeError):
            j.record("bad", {"key": "bad", "detail": object()})
        assert len(TrialJournal(str(tmp_path))) == 0
        assert not os.listdir(os.path.join(str(tmp_path), "trials"))

    def test_crash_between_tmp_and_rename_then_resume_sweeps(self, tmp_path):
        """The autotune_trial fault site: a kill mid-commit leaves a
        stale .tmp-* partial and NO committed trial; resume sweeps the
        partial and the runner re-runs only what never committed."""
        faults.clear()
        faults.arm("autotune_trial", index=0, fire_nth=2)
        j = TrialJournal(str(tmp_path))
        runner = ExperimentRunner(j)
        runner.run_one("t0", lambda: {"key": "t0", "status": "ok"})
        with pytest.raises(faults.InjectedFault):
            runner.run_one("t1", lambda: {"key": "t1", "status": "ok"})
        faults.clear()
        tdir = os.path.join(str(tmp_path), "trials")
        assert sum(1 for f in os.listdir(tdir) if ".tmp-" in f) == 1
        assert sum(1 for f in os.listdir(tdir) if f.endswith(".json")) == 1

        j2 = TrialJournal(str(tmp_path))
        assert j2.swept_stale == 1
        assert j2.keys() == ["t0"]
        runner2 = ExperimentRunner(j2)
        calls = []

        def fn(key):
            def run():
                calls.append(key)
                return {"key": key, "status": "ok"}
            return run

        for key in ("t0", "t1"):
            runner2.run_one(key, fn(key))
        assert calls == ["t1"]          # t0 restored, never re-run
        assert runner2.executed == ["t1"]

    def test_long_keys_get_bounded_filenames(self, tmp_path):
        j = TrialJournal(str(tmp_path))
        key = "c" * 400 + "@r0n4"
        j.record(key, {"key": key, "status": "ok"})
        names = os.listdir(os.path.join(str(tmp_path), "trials"))
        assert len(names) == 1 and len(names[0]) < 140
        assert TrialJournal(str(tmp_path)).get(key) is not None

    def test_halving_crash_resume_no_rerun(self, tmp_path):
        """Kill a real search at its 3rd commit; the resumed search must
        re-measure only the un-committed trials and converge to the same
        winner."""
        cands = _grid(6)
        score = {c.name: float(i) for i, c in enumerate(cands)}
        trace = _trace(n=8).with_load(100, 2)

        def mk(calls):
            def obj(c, tr):
                calls.append(c.name)
                return {"metric": score[c.name], "feasible": True}
            return obj

        first = []
        faults.clear()
        faults.arm("autotune_trial", index=0, fire_nth=3)
        try:
            with pytest.raises(faults.InjectedFault):
                SuccessiveHalving(mk(first), trace, rounds=2,
                                  journal=TrialJournal(str(tmp_path))
                                  ).run(_grid(6))
        finally:
            faults.clear()
        committed = set(TrialJournal(str(tmp_path)).keys())
        assert len(committed) == 2 and len(first) == 3

        second = []
        res = SuccessiveHalving(mk(second), trace, rounds=2,
                                journal=TrialJournal(str(tmp_path))
                                ).run(_grid(6))
        assert res.best.name == cands[-1].name
        assert not (committed & set(res.executed))
        assert res.resumed == len(committed)
        # one measurement per trial, plus exactly one for the trial that
        # was measured but killed before its commit (measured, lost,
        # honestly re-measured)
        assert len(first) + len(second) == len(res.trials) + 1


# ---------------------------------------------------------------------------
# Training Autotuner rides the same machinery
# ---------------------------------------------------------------------------


class TestAutotunerIntegration:
    def _tuner(self, tmp_path=None, **kw):
        from shuffle_exchange_tpu.models import Transformer, tiny

        return Autotuner(
            Transformer(tiny(vocab=64, d=32, layers=1, heads=2, seq=16)),
            {"train_batch_size": 8}, lambda bs: {}, world_size=8,
            journal_dir=str(tmp_path) if tmp_path else None, **kw)

    def test_write_results_atomic_and_sweeps_stale(self, tmp_path):
        tuner = self._tuner()
        c = Candidate(1, 1, 1, False)
        c.status, c.metric_val = "ok", 123.0
        tuner.results = [c]
        stale = tmp_path / "autotuning_results.json.tmp-deadbeef"
        stale.write_text("{torn")
        path = tuner.write_results(c, results_dir=str(tmp_path))
        assert not stale.exists()                  # killed-run partial swept
        assert json.load(open(path))["train_micro_batch_size_per_gpu"] == 1
        table = json.load(open(tmp_path / "autotuning_results.json"))
        assert table[0]["name"] == c.name
        assert not [f for f in os.listdir(tmp_path) if ".tmp-" in f]

    def test_tune_journals_and_resumes(self, tmp_path, monkeypatch):
        """A journaled training tune restores measured candidates on
        rerun instead of re-measuring them (the crash-safe contract on
        the legacy API)."""
        calls = []

        def fake_objective(tuner):
            def obj(c):
                calls.append(c.name)
                return {"metric": float(c.micro_batch_size)}
            return obj

        cands = [Candidate(1, 1, 1, False), Candidate(2, 1, 1, False)]
        t1 = self._tuner(tmp_path)
        monkeypatch.setattr(t1, "_objective", fake_objective(t1))
        best, _ = t1.tune(cands=[dataclasses.replace(c) for c in cands])
        assert best.micro_batch_size == 2 and len(calls) == 2

        t2 = self._tuner(tmp_path)
        monkeypatch.setattr(t2, "_objective", fake_objective(t2))
        best2, results2 = t2.tune(cands=[dataclasses.replace(c)
                                         for c in cands])
        assert best2.micro_batch_size == 2
        assert len(calls) == 2                      # nothing re-measured
        assert all(c.status == "ok" for c in results2)

    def test_autotune_trial_site_registered(self):
        assert "autotune_trial" in faults.SITES
