"""``bench.py`` off the chip and its rows at toy size.

The rows are toy-size pins of ``bench.py``'s row functions (ROADMAP S0
replaces the file; until then a row that no longer runs is a broken
artifact). ``bench.py`` itself measures a TPU or fails: a CPU run must exit
non-zero, print no valid number and leave the published baseline alone.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_main_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    before = open(os.path.join(REPO, "BASELINE.json")).read()
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)
    assert proc.returncode != 0, proc.stdout[-500:]
    row = json.loads([l for l in proc.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert row["valid"] is False and row["value"] == 0, row
    assert "needs a TPU" in row["errors"]["calibration"], row
    # nothing was measured, so nothing was published
    assert open(os.path.join(REPO, "BASELINE.json")).read() == before


def test_bench_parent_stays_off_jax():
    """The parent only starts children (a chip has one owner): with the
    children stubbed out, main() must finish without JAX ever imported."""
    code = (
        "import sys; sys.argv = ['bench.py']; import bench\n"
        "bench._child = lambda *a, **k: (None, 'stub')\n"
        "rc = bench.main()\n"
        "assert rc == 1, rc\n"
        "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1500:]


def test_bench_refuses_unknown_chips_and_missing_memory_stats():
    sys.path.insert(0, REPO)
    import bench

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_limit": 17_000_000_000}

    assert bench.chip_peak_flops(Dev()) == 197e12
    assert bench.chip_hbm_bandwidth(Dev()) == 819e9
    assert bench.hbm_bytes(Dev()) == 17_000_000_000
    Dev.device_kind = "TPU v99"
    with pytest.raises(RuntimeError, match="no published peaks"):
        bench.chip_peak_flops(Dev())
    Dev.memory_stats = lambda self: None
    with pytest.raises(RuntimeError, match="memory_stats"):
        bench.hbm_bytes(Dev())


def test_host_offload_ladder_entry_runs_at_toy_size():
    """The config-2 host-offload ladder entry (bench.py
    host_offload_ladder_entry) at toy size: same config SHAPE — cpu offload
    tier + offload_overlap + save_flash_lse remat — trains on CPU, so the
    published bench config cannot rot."""
    import sys

    sys.path.insert(0, REPO)
    import numpy as np

    from bench import host_offload_ladder_entry
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.parallel import reset_topology

    name, mcfg, ds, bs, seq = host_offload_ladder_entry(toy=True)
    assert mcfg.remat and mcfg.remat_policy == "save_flash_lse"
    off = ds["zero_optimization"]["offload_optimizer"]
    assert off["device"] == "cpu" and off["offload_overlap"] is True

    reset_topology()
    engine, *_ = sxt.initialize(model=Transformer(mcfg), config=ds)
    assert engine._host_opt is not None, "host-resident optimizer not engaged"
    assert engine._host_pipeline is not None, "overlap pipeline not engaged"
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, mcfg.vocab_size,
                                       size=(bs, seq)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(2)]
    assert all(np.isfinite(l) for l in losses)
    engine.module_weights()    # joins the in-flight overlapped step
    assert engine.monitor.memory_monitor.latest("offload/overlap_steps") >= 1

    # the full-size entry agrees with the published claims: ~1.5-2B params,
    # host-offload + overlap + save_flash_lse, north-star head geometry
    from bench import _param_count

    name_f, mcfg_f, ds_f, _, _ = host_offload_ladder_entry()
    n = _param_count(mcfg_f)
    assert 1.5e9 <= n <= 2.0e9, n
    assert mcfg_f.head_dim == 128 and mcfg_f.n_heads // mcfg_f.kv_heads == 4
    assert ds_f["zero_optimization"]["offload_optimizer"]["offload_overlap"]


def test_serving_goodput_row_runs_at_toy_size():
    """The config-5 serving-goodput row (bench.serving_goodput_row) at toy
    size: same two-pass shape — capacity pass, then a Poisson trace offered
    at 2x capacity through the continuous-batching scheduler — runs on CPU,
    so the published bench row cannot rot on the driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_goodput_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = serving_goodput_row(model, params, icfg, mcfg.vocab_size,
                              n_requests=6, prompt_lo=4, prompt_hi=20,
                              max_new=5, load=2.0)
    assert row["sustained_tokens_per_sec"] > 0
    assert row["capacity_tokens_per_sec"] > 0
    assert row["ttft_p50_s"] > 0 and row["tpot_p50_s"] > 0
    assert row["ttft_p95_s"] >= row["ttft_p50_s"]
    assert 0 < row["budget_fill_mean"] <= 1
    assert row["n_requests"] == 6 and row["chunk_bins"] == [4, 8, 16]
    assert row["compiled_programs"] >= 1
    # random prompts share nothing and the config has prefix_caching off
    assert row["prefix_hit_rate"] is None


def test_serving_fleet_row_runs_at_toy_size():
    """The config-5 serving-fleet row (bench.serving_fleet_row) at toy
    size: the same Poisson trace served by a 1-replica and a 2-replica
    router fleet — goodput + TTFT tails both ways, token parity across
    fleet widths — runs on CPU, so the published row cannot rot on the
    driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_fleet_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = serving_fleet_row(model, params, icfg, mcfg.vocab_size,
                            n_requests=6, prompt_lo=4, prompt_hi=20,
                            max_new=5, load=2.0)
    assert row["capacity_tokens_per_sec"] > 0
    assert row["sustained_tokens_per_sec_1r"] > 0
    assert row["sustained_tokens_per_sec_2r"] > 0
    assert row["fleet_speedup_x"] > 0
    assert row["replicas_used"] == [1, 2]
    assert row["ttft_p95_s_1r"] >= row["ttft_p50_s_1r"] > 0
    assert row["ttft_p95_s_2r"] >= row["ttft_p50_s_2r"] > 0
    assert row["tpot_p50_s_1r"] > 0 and row["tpot_p50_s_2r"] > 0
    # identical weights + greedy decoding: routing is token-identical
    assert row["token_mismatches_vs_1r"] == 0


def test_serving_failover_row_runs_at_toy_size():
    """The config-5 serving-failover row (bench.serving_failover_row) at
    toy size: the same Poisson trace served clean and with one mid-trace
    unclean replica kill — goodput retention, recovered-request count,
    TTFT p95 delta, token parity — runs on CPU, so the published row
    cannot rot on the driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_failover_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4},
        router={"retry_backoff_s": 0.001})
    row = serving_failover_row(model, params, icfg, mcfg.vocab_size,
                               n_requests=4, prompt_lo=4, prompt_hi=16,
                               max_new=4, kill_after_ticks=2, load=2.0)
    assert row["deaths"] == 1
    assert row["recovered_requests"] >= 1
    assert row["quarantined"] == 0
    # greedy drain-replay: an unclean death never costs output fidelity
    assert row["token_mismatches_vs_clean"] == 0
    assert row["sustained_tokens_per_sec_clean"] > 0
    assert row["sustained_tokens_per_sec_failover"] > 0
    assert row["goodput_retention"] > 0
    assert row["ttft_p95_s_failover"] >= row["ttft_p50_s_failover"] > 0


@pytest.mark.slow   # ~15s: 4 fleet passes (warm/cap/barrier/async) + converge; nightly via ci_full
def test_serving_async_publish_row_runs_at_toy_size():
    """The config-5 async-weight-sync row (bench.serving_async_publish_row)
    at toy size: the same Poisson trace with mid-trace publishes, barrier
    two-phase vs async shuffle-exchange gossip — per-publish stall,
    goodput retention, honest version census, bounded staleness,
    converge() — runs on CPU, so the published row cannot rot on the
    driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_async_publish_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = serving_async_publish_row(model, params, icfg, mcfg.vocab_size,
                                    n_requests=4, prompt_lo=4, prompt_hi=16,
                                    max_new=4, publish_every_ticks=2,
                                    n_publishes=3, staleness_window=2,
                                    load=2.0)
    assert row["publishes"] == 3
    # same-bytes publishes: version churn never costs output fidelity
    assert row["token_mismatches_vs_barrier"] == 0
    # the acceptance pins: no stamp outside the window, and converge()
    # lands every live replica on one version
    assert row["staleness_window_held"]
    assert row["fleet_converged"]
    assert row["converged_version"] > 3
    assert sum(row["version_census"].values()) == 4
    assert row["publish_bytes"] > 0
    assert row["publish_stall_p50_s_barrier"] > 0
    assert row["publish_stall_p50_s_async"] > 0
    assert row["sustained_tokens_per_sec_barrier"] > 0
    assert row["sustained_tokens_per_sec_async"] > 0
    assert row["goodput_retention"] > 0
    assert row["failed_exchanges"] == 0


def test_prefix_cache_row_runs_at_toy_size():
    """The config-5 prefix-cache row (bench.prefix_cache_row) at toy size:
    the shared-system-prompt trace served with and without prefix_caching
    must report a real hit-rate, identical tokens both ways, and the TTFT
    comparison — on CPU, so the published row cannot rot on the driver
    box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import prefix_cache_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=64,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = prefix_cache_row(model, params, icfg, mcfg.vocab_size,
                           n_requests=4, sys_prompt_len=16, suffix_lo=4,
                           suffix_hi=12, max_new=5, load=2.0)
    # every admission past the first reuses the 2-block system prompt
    # (counters are engine-cumulative over warm + capacity + trace passes,
    # so 3 x 16 from the first pass is the floor)
    assert row["prefix_hit_rate"] > 0
    assert row["prefix_hit_tokens"] >= 3 * 16
    assert row["ttft_p50_s_no_cache"] > 0 and row["ttft_p50_s_cached"] > 0
    assert row["sustained_tokens_per_sec_cached"] > 0
    assert row["cow_copies"] == 0
    # bf16 KV mode: cached and uncached serves are exactly token-equal
    assert row["token_mismatches_vs_no_cache"] == 0


@pytest.mark.slow   # 15s: bench-row pin; nightly via ci_full (ISSUE 13 tier-1 budget)
def test_serving_speculative_row_runs_at_toy_size():
    """The config-5 speculative row (bench.serving_speculative_row) at toy
    size: the same repetitive-suffix Poisson trace at k=0 vs k=4 with the
    n-gram self-drafter and a draft model — steps-per-token, acceptance
    rate, TTFT/TPOT tails, and exact token parity across every variant —
    runs on CPU, so the published row cannot rot on the driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_speculative_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=128, kv_block_size=8, num_kv_blocks=64,
        serving={"token_budget": 24, "max_running": 4, "chunk_min": 4})
    row = serving_speculative_row(model, params, icfg, mcfg.vocab_size,
                                  n_requests=4, period=4, prompt_lo=16,
                                  prompt_hi=24, max_new=16, k=4, load=2.0)
    base, ng, dm = row["baseline_k0"], row["ngram_k4"], row["draft_model_k4"]
    assert base["acceptance_rate"] is None and base["proposed"] == 0
    assert ng["proposed"] > 0 and 0 <= ng["acceptance_rate"] <= 1
    # the same-weights draft model is the acceptance ceiling: everything
    # it proposes verifies, and steps/token collapses toward 1/(k+1)
    assert dm["acceptance_rate"] == 1.0 and dm["rollbacks"] == 0
    assert dm["steps_per_emitted_token"] < base["steps_per_emitted_token"]
    assert row["speedup_steps_draft_x"] > 1.5
    for v in (base, ng, dm):
        assert v["ttft_p50_s"] > 0 and v["tpot_p95_s"] >= 0
        assert v["sustained_tokens_per_sec"] > 0
    # greedy acceptance: every variant emits the k=0 tokens exactly
    assert row["token_mismatches_ngram_vs_k0"] == 0
    assert row["token_mismatches_draft_vs_k0"] == 0


@pytest.mark.slow   # ~60s: real bounded search; nightly via ci_full (tier-1 budget)
def test_serving_autotune_row_runs_at_toy_size():
    """The config-5 serving-autotune row (bench.serving_autotune_row) at
    toy size: a 2-round successive-halving search over the max_running
    ladder (plus the statically-pruned insane-chunk-ladder candidates)
    against one paired Poisson trace — winner config, trials run, and the
    tuned-vs-default goodput delta all present, the static-prune and
    winner-zero-recompile contracts green — on CPU, so the published row
    cannot rot on the driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_autotune_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    # a deliberately mid-range default (max_running=2): the space above
    # it holds configs that pack fatter ticks, so the search has a real
    # delta to find — the same shape scripts/autotune_serving.py --smoke
    # drills in ci_full
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=96,
        serving={"token_budget": 64, "max_running": 2, "chunk_min": 4})
    row = serving_autotune_row(model, params, icfg, mcfg.vocab_size,
                               n_requests=12, prompt_lo=4, prompt_hi=20,
                               max_new=6, load=2.0, rounds=2)
    # winner config present and loadable as an overlay
    assert row["winner"]
    overlay = row["winner_overlay"]
    icfg.with_overlay(overlay)                      # validates
    assert overlay["serving"]["max_running"] >= 1
    # trials run + goodput delta fields (the published headline)
    assert row["trials_measured"] >= 4
    assert row["pruned_static"] >= 1
    assert row["pruned_never_measured"] is True
    assert row["goodput_default_tokens_per_sec"] > 0
    assert row["goodput_tuned_tokens_per_sec"] > 0
    assert "goodput_delta_pct" in row
    # the winner and the baseline measured with warmed, zero-recompile
    # passes (an unwarmable candidate may legitimately appear infeasible
    # in the ranked list — never as the winner)
    assert row["winner_zero_recompile"] is True
    assert row["default_zero_recompile"] is True
    # the knob ranking the BASELINE.md retune plan reads
    assert "max_running" in row["knob_effects"]
    assert row["trace"]["seed"] == 0 and len(row["trace"]["arrivals_s"]) == 12
    # tuned beats default on the paired trace (the ISSUE 14 acceptance
    # bar; the deliberately small default leaves a wide margin)
    assert (row["goodput_tuned_tokens_per_sec"]
            > row["goodput_default_tokens_per_sec"])


def test_rlhf_rollout_row_runs_at_toy_size():
    """The config-5 RLHF row (bench.rlhf_rollout_row) at toy size: three
    train -> publish -> generate flips on a warmed 2-replica fleet with
    shared-prompt rollouts — flip latency, rollout goodput, prefix-cache
    hit rate, and the zero-recompile / replay / version-convergence
    contract flags — runs on CPU, so the published row cannot rot on the
    driver box."""
    import sys

    sys.path.insert(0, REPO)
    from bench import rlhf_rollout_row
    from shuffle_exchange_tpu.models import tiny

    mcfg = tiny(vocab=64, d=32, layers=2, heads=2, seq=64)
    row = rlhf_rollout_row(mcfg, n_rollouts=8, shared_len=16, suffix_lo=4,
                           suffix_hi=8, max_new=6, flips=2, kv_block=8,
                           toy=True)
    assert row["flips"] == 2
    assert row["flip_s_median"] > 0 and row["gather_s_total"] > 0
    assert row["rollout_tokens_per_sec"] > 0
    # shared system prompt -> the second+ rollouts hit committed blocks
    assert row["prefix_cache_hit_rate"] is not None
    assert row["prefix_cache_hit_rate"] > 0
    # the contract flags the TPU row will publish alongside the timings
    assert row["zero_recompile_across_flips"] is True
    assert row["kv_pools_intact"] is True
    assert row["weight_versions_converged"] is True
    assert row["replays_bit_exact"] == 2
    assert row["weight_version"] == row["train_steps"] - 1


@pytest.mark.slow   # ~50s: warm+measure pairs x 3 variants; nightly via ci_full
def test_serving_sampling_row_runs_at_toy_size():
    """The config-5 one-dispatch-sampling row (bench.serving_sampling_row)
    at toy size: the same Poisson trace greedy vs sampled (temp=0.8 /
    top_p=0.9) vs sampled-with-EOS-stop at identical arrivals — seeded
    replay verified inside the row, EOS early-stop returning real budget,
    and the generalized speculative accept at temperature > 0 with
    spec-on/off parity — runs on CPU, so the published row cannot rot on
    the driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_sampling_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = serving_sampling_row(model, params, icfg, mcfg.vocab_size,
                               n_requests=6, prompt_lo=6, prompt_hi=16,
                               max_new=10, load=2.0, seed=0)
    # the EOS id really is a token the sampled run emits, so the stop
    # condition fires (early_stop_fraction > 0) and returns real budget
    assert row["early_stop_fraction"] > 0
    assert row["dead_tokens_saved"] > 0
    assert row["early_stop_freed_blocks"] > 0
    assert row["sampled_eos"]["emitted_tokens"] < \
        row["sampled_no_stop"]["emitted_tokens"]
    # the seeded Gumbel chain: a fresh scheduler re-serving the trace
    # under the same seeds emitted bit-identical tokens
    assert row["seeded_replay_verified"] is True
    # the generalized accept rule at temperature > 0: the target-as-draft
    # side trace accepts real drafts, resamples on rejects, and spec
    # on/off emit identical seeded chains
    assert row["spec_acceptance_at_temp"] is not None
    assert row["spec_acceptance_at_temp"] > 0
    assert row["spec_resamples"] > 0
    assert row["spec_token_parity_at_temp"] is True
    for v in ("greedy", "sampled_no_stop", "sampled_eos"):
        assert row[v]["sustained_tokens_per_sec"] > 0
        assert row[v]["ttft_p50_s"] > 0
    assert row["sampling_overhead_x"] > 0
    assert row["goodput_eos_vs_no_stop_x"] > 0
    assert row["trace"]["seed"] == 0 and len(row["trace"]["arrivals_s"]) == 6
    # the CPU pin asserts structure + determinism contracts; the goodput
    # HEADLINE (EOS early-stop vs stop-disabled at identical arrivals)
    # is the driver-box row's to publish — toy wall-clock noise can swamp
    # the dead-token signal


@pytest.mark.slow   # ~60s: 4-pass tier row (ref/cap/baseline/spill); nightly via ci_full
def test_serving_longctx_row_runs_at_toy_size():
    """The config-5 long-context tier row (bench.serving_longctx_row) at
    toy size: the same Poisson trace on constrained pools, spill-on vs the
    refuse-admission baseline vs an unconstrained-pool parity oracle —
    parks must fully replace preemptions and bf16 token parity is asserted
    inside the row itself."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_longctx_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=128, kv_block_size=8, num_kv_blocks=96,
        serving={"token_budget": 32, "max_running": 4, "chunk_min": 4})
    row = serving_longctx_row(model, params, icfg, mcfg.vocab_size,
                              n_requests=8, prompt_blocks=6, grow_blocks=2,
                              load=2.0)
    assert row["token_mismatches_spill_on"] == 0
    assert row["token_mismatches_baseline"] == 0
    assert row["preemptions_spill_on"] == 0     # parks replace preempts
    assert row["parks"] > 0 and row["parks"] == row["unparks"]
    assert row["spills"] >= row["parks"] and row["fetches"] >= row["parks"]
    assert row["aggregate_kv_blocks"] > row["pool_blocks_constrained"]
    assert row["sustained_tokens_per_sec_spill_on"] > 0
    assert row["goodput_vs_baseline"] > 0
    assert row["ttft_p95_s_spill_on"] > 0 and row["tpot_p95_s_spill_on"] > 0
    # the CPU pin asserts structure + parity; the goodput DOMINANCE claim
    # is the driver-box row's to publish (BASELINE.md pending note) — at
    # toy scale wall-clock noise can swamp the re-prefill waste signal


@pytest.mark.slow   # ~40s: 1/3/6-adapter sweep + solo parity replays; nightly via ci_full
def test_serving_multi_tenant_row_runs_at_toy_size():
    """The config-5 multi-tenant LoRA row (bench.serving_multi_tenant_row)
    at toy size: the same Poisson trace striped across 1 vs 3 vs 6
    adapters on a 2-slot pool — the oversubscribed entries must page (LRU
    evictions), park rather than preempt, and keep mixed-vs-solo token
    parity (asserted inside the row), so the published bench row cannot
    rot on the CPU driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_multi_tenant_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = serving_multi_tenant_row(model, params, icfg, mcfg.vocab_size,
                                   n_requests=6, adapter_counts=(1, 3, 6),
                                   pool_slots=2, rank=4, prompt_lo=4,
                                   prompt_hi=20, max_new=5, load=2.0,
                                   parity_samples=2)
    assert row["token_mismatches_mixed_vs_solo"] == 0
    assert [e["n_adapters"] for e in row["entries"]] == [1, 3, 6]
    e1, e3, e6 = row["entries"]
    # resident single tenant: everything hits, nothing pages
    assert e1["pool_hit_rate"] == 1.0 and e1["evictions"] == 0
    # oversubscribed entries page through the 2-slot pool
    assert e6["evictions"] > 0 and e6["pool_hit_rate"] < 1.0
    # adapter pressure parks, never preempts (asserted in-row too)
    assert all(e["preemptions"] == 0 for e in row["entries"])
    assert all(e["parks"] == e["unparks"] for e in row["entries"])
    assert all(e["sustained_tokens_per_sec"] > 0 for e in row["entries"])
    assert e1["goodput_retention"] == 1.0
    # adapter identity is data: the in-row fresh-adapter probe served a
    # never-seen adapter id on the warmed engine without compiling
    assert row["fresh_adapter_new_programs"] == 0


@pytest.mark.slow   # ~60s: dense + MoE twin passes + oracle replays; nightly via ci_full
def test_serving_moe_row_runs_at_toy_size():
    """The config-5 expert-parallel MoE row (bench.serving_moe_row) at toy
    size: the same Poisson trace on the dense baseline vs the MoE twin at
    matched total params, with batched-vs-sequential token parity and
    park-don't-preempt asserted inside the row — so the published bench
    row cannot rot on the CPU driver box."""
    import sys

    sys.path.insert(0, REPO)
    import jax

    from bench import serving_moe_row
    from shuffle_exchange_tpu.inference import InferenceConfig
    from shuffle_exchange_tpu.models import Transformer, tiny

    mcfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    icfg = InferenceConfig(
        dtype="float32", max_seq_len=64, kv_block_size=8, num_kv_blocks=40,
        serving={"token_budget": 16, "max_running": 4, "chunk_min": 4})
    row = serving_moe_row(model, params, icfg, mcfg.vocab_size,
                          n_requests=6, n_experts=4, prompt_lo=4,
                          prompt_hi=20, max_new=5, load=2.0,
                          parity_samples=2)
    assert row["token_mismatches_vs_oracle"] == 0
    assert row["moe_impl"] == "ragged"
    dense, moe = row["entries"]["dense"], row["entries"]["moe"]
    assert dense["sustained_tokens_per_sec"] > 0
    assert moe["sustained_tokens_per_sec"] > 0
    assert row["goodput_vs_dense"] > 0
    # expert pressure parks, never preempts; ragged routing never drops
    assert dense["preemptions"] == 0 and moe["preemptions"] == 0
    assert moe["dropped"] == 0
    assert moe["dispatched"] > 0 and moe["expert_load_max"] >= 1
    assert moe["n_experts"] == 4 and moe["top_k"] == 2
    assert 0 < moe["expert_load_balance"] <= 1.0


@pytest.mark.slow   # ~90s: per-degree sxt.initialize + train steps; nightly via ci_full
def test_ring_scaling_row_runs_at_toy_size():
    """The config-2 ring-attention scaling entry (bench.ring_scaling_row)
    at toy size on the virtual mesh: loss parity across CP degrees and the
    O(seq/CP) per-chip attention-memory shape claim."""
    import sys

    sys.path.insert(0, REPO)
    from bench import ring_scaling_row

    row = ring_scaling_row(cp_degrees=(1, 2, 4), d=64, heads=4, layers=2,
                           seq=128, vocab=128, batch=4, steps=1)
    assert row["degrees"] == [1, 2, 4]
    by = {e["cp"]: e for e in row["entries"]}
    assert all(e["tokens_per_sec"] > 0 for e in row["entries"])
    # exact softmax: the ring changes layout, not math
    assert row["loss_parity"] <= 2e-2
    # per-chip attention working set shrinks with the ring degree
    assert by[2]["attention_peak_bytes_per_chip"] <= \
        by[1]["attention_peak_bytes_per_chip"]
    assert by[4]["attention_peak_bytes_per_chip"] < \
        by[1]["attention_peak_bytes_per_chip"]
    assert by[4]["attention_mem_vs_cp1"] <= 0.5
