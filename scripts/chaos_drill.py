#!/usr/bin/env python
"""Chaos drill CLI (ISSUE 12): kill/hang/revive serving replicas under a
live Poisson trace and assert the fault-tolerance bars — zero lost
requests, token parity with the clean run, ACTIVE-only recovery, bounded
TTFT degradation, and (with a hang kill) KV migration with zero re-prefill
tokens.

Runs on the CPU driver box (virtual mesh not required — replicas are
in-process engine+scheduler pairs). Wired into scripts/ci_full.sh; the
same harness rides dryrun config 14 (__graft_entry__.dryrun_multichip)
and, at toy size, tests/test_failover.py.

Usage:
    python scripts/chaos_drill.py                  # default crash+hang drill
    python scripts/chaos_drill.py --kills 3:crash:0 6:hang:1 --requests 12
    python scripts/chaos_drill.py --process        # ISSUE 17: REAL worker
        # processes behind the RPC boundary, killed with real SIGKILL /
        # SIGSTOP (kinds: kill|stop); same bars, kernel-visible failures
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# this drill is a CPU correctness gate (same recipe as tests/conftest.py);
# its --process workers inherit the platform stated here
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_backend_optimization_level" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_backend_optimization_level=0"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kills", nargs="*", default=None,
                    help="after_request:kind:replica triples, e.g. "
                         "4:crash:0 8:hang:1 (kind in crash|hang|"
                         "tick_exception)")
    ap.add_argument("--cooperative", action="store_true",
                    help="drive ticks inline instead of threaded replicas "
                         "(crash/tick_exception kills only)")
    ap.add_argument("--process", action="store_true",
                    help="ISSUE 17: spawn REAL worker processes behind the "
                         "RPC boundary and kill them with real SIGKILL/"
                         "SIGSTOP (kill kinds: kill|stop)")
    ap.add_argument("--async-publish", action="store_true",
                    help="ISSUE 20: async shuffle-exchange weight-sync "
                         "drill — mid-trace publishes over gossip edges, "
                         "one replica killed mid-gossip; zero lost "
                         "requests, token parity, bounded staleness, and "
                         "survivors converge() to one version")
    ap.add_argument("--adapters", type=int, default=0, metavar="N",
                    help="ISSUE 18: stripe requests across N LoRA "
                         "adapters on a 2-slot pool (threads mode) — "
                         "failover must re-place onto adapter-resident "
                         "survivors and replay token-identically")
    ap.add_argument("--no-revive", action="store_true")
    ap.add_argument("--ttft-bound-x", type=float, default=None,
                    help="assert chaos TTFT p95 <= bound * clean p95")
    ap.add_argument("--json", action="store_true", help="machine-readable "
                    "report on stdout")
    args = ap.parse_args()

    import jax

    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    from shuffle_exchange_tpu.inference import (InferenceConfig,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.models import Transformer, tiny
    from shuffle_exchange_tpu.serving import run_chaos_drill

    if args.process:
        return _process_drill(args)
    if args.async_publish:
        return _async_publish_drill(args)

    cfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
               activation="swiglu", norm="rmsnorm", position="rope",
               n_kv_heads=2, tie_embeddings=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))

    adapter_names = [f"drill-tenant-{i}" for i in range(args.adapters)]

    def _adapter_factors(i):
        import numpy as np

        from shuffle_exchange_tpu.inference.adapters import target_dims

        frng = np.random.default_rng(7000 + i)
        out = {}
        for t in ("wq", "wv"):
            din, dout = target_dims(cfg, t)
            out[t] = (0.5 * frng.standard_normal(
                          (cfg.n_layers, din, 4)).astype("float32"),
                      0.5 * frng.standard_normal(
                          (cfg.n_layers, 4, dout)).astype("float32"))
        return out

    def mk():
        eng = InferenceEngineV2(model, params, InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40,
            adapters=({"enabled": True, "slots": 2, "max_rank": 4,
                       "targets": ("wq", "wv")} if args.adapters
                      else {"enabled": False}),
            serving={"token_budget": 16, "max_running": 4, "chunk_min": 4},
            # detection thresholds sized for a 1-core CPU box where a
            # NORMAL warm tick takes a few hundred ms but a COLD one can
            # sit in a multi-second compile: the injected hang parks
            # forever, so the generous threshold only delays detection
            router={"heartbeat_interval_s": 0.25, "suspect_after_misses": 8,
                    "dead_after_misses": 40, "tick_timeout_s": 10.0,
                    "health_check_interval_s": 0.05,
                    "poison_death_threshold": 3}))
        # register in the FACTORY (content-keyed, deterministic versions)
        # so revived replacement replicas know every tenant too
        for i, name in enumerate(adapter_names):
            eng.adapters.register(name, _adapter_factors(i), alpha=8.0)
        return eng

    adapter_ids = ([adapter_names[i % args.adapters] if i % 4 else None
                    for i in range(args.requests)]
                   if args.adapters else None)

    if args.kills:
        kills = []
        for spec in args.kills:
            after, kind, rid = spec.split(":")
            kills.append((int(after), kind, int(rid)))
    else:
        kills = [(args.requests // 3, "crash", 0)]
        if not args.cooperative and args.replicas > 1:
            kills.append((2 * args.requests // 3, "hang", 1))

    report = run_chaos_drill(
        mk, n_replicas=args.replicas, n_requests=args.requests,
        max_new=args.max_new, vocab=90, seed=args.seed, kills=kills,
        threaded=not args.cooperative, revive=not args.no_revive,
        ttft_p95_bound_x=args.ttft_bound_x,
        require_migration=any(k[1] == "hang" for k in kills),
        timeout_s=600.0, arm_wait_s=60.0, adapter_ids=adapter_ids)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        fo = report["failover"]
        print(f"chaos drill: {report['finished']}/{report['n_requests']} "
              f"finished, {report['lost']} lost, "
              f"{report['token_mismatches']} token mismatches, "
              f"{fo['deaths']} deaths -> {fo['recovered_requests']} "
              f"recovered ({fo['migrated_sequences']} KV-migrated, "
              f"{fo['reprefill_tokens']} re-prefill tokens), "
              f"shed {report['shed']}, active_only={report['active_only']}, "
              f"ttft_p95 {report['ttft_p95_s_clean']} -> "
              f"{report['ttft_p95_s_chaos']}")
        if report["adapters_enabled"] and report["adapters"]:
            ad = report["adapters"]
            print(f"chaos drill adapters: {args.adapters} tenants on "
                  f"2-slot pools, hits {ad.get('hits')}, "
                  f"misses {ad.get('misses')}, parks {ad.get('parks')}, "
                  f"token parity held through failover")
    print("chaos drill: ok")
    return 0


def _async_publish_drill(args) -> int:
    """ISSUE 20 acceptance drill: the fleet on the async shuffle-exchange
    sync (Gossip edges, bounded staleness) with publishes landing
    MID-TRACE and one replica killed mid-gossip. Publishes carry the same
    bytes as the boot weights so token parity with the clean single-run
    oracle is exact regardless of which version served each token. Bars:
    zero lost requests, token parity, every finished request's stamped
    ``weight_version`` inside the staleness window, the corpse out of the
    gossip schedule (survivor staleness drains to 0), and ``converge()``
    landing every live replica on one full-average version."""
    import numpy as np

    import jax

    from shuffle_exchange_tpu.inference import (InferenceConfig,
                                                InferenceEngineV2)
    from shuffle_exchange_tpu.models import Transformer, tiny
    from shuffle_exchange_tpu.serving import ReplicaRouter

    window = 3
    cfg = tiny(vocab=97, d=32, layers=2, heads=4, seq=128,
               activation="swiglu", norm="rmsnorm", position="rope",
               n_kv_heads=2, tie_embeddings=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def mk():
        return InferenceEngineV2(model, params, InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40,
            serving={"token_budget": 16, "max_running": 4, "chunk_min": 4},
            router={"sync": {"enabled": True, "method": "Gossip",
                             "gossip_prob": 1.0,
                             "staleness_window": window}}))

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, 90, size=int(n)).tolist()
               for n in rng.integers(4, 17, size=args.requests)]

    # clean single-engine oracle (greedy): v1..vN publishes repeat the
    # boot bytes, so EVERY version's decode matches this reference
    oracle = []
    for p in prompts:
        eng = InferenceEngineV2(model, params, InferenceConfig(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40,
            serving={"token_budget": 16, "max_running": 4, "chunk_min": 4}))
        lg = eng.put([0], [p])
        first = int(np.argmax(lg[0]))
        rest = eng.decode_loop([0], [first], args.max_new - 1)
        oracle.append([first] + [int(t) for t in rest[0]])

    router = ReplicaRouter([mk() for _ in range(args.replicas)])
    uids = [router.submit(p, max_new_tokens=args.max_new) for p in prompts]
    victim = args.replicas - 1
    kill_tick = max(2, args.requests // 3)
    publishes, ticks, version = max(2, args.requests // 4), 0, 0
    killed = False
    while router.tick():
        ticks += 1
        if version < publishes and ticks % 2 == 0:
            version += 1
            router.publish_weights(params, version=version)
        router.sync_step()
        if not killed and ticks == kill_tick:
            # the mid-gossip kill: a publish is in flight somewhere on
            # the edge schedule when the victim dies uncleanly
            router.fail_over(victim, reason="drill: mid-gossip kill")
            killed = True
    while version < publishes:       # short trace: spend the budget
        version += 1
        router.publish_weights(params, version=version)
        router.sync_step()

    finished = sum(router.requests[u].state == "finished" for u in uids)
    lost = args.requests - finished
    mismatches = sum(router.requests[u].generated != want
                     for u, want in zip(uids, oracle))
    newest = router._async_sync.newest_version
    stamps = [router.requests[u].weight_version for u in uids]
    window_ok = all(wv is not None and 0 <= newest - wv <= window
                    for wv in stamps)
    router.sync_step()               # corpse out of the schedule: drains
    st = router._async_sync.staleness()
    cv = router.converge()
    live = [r for r in router.replicas if r.active]
    converged = bool(live) and all(r.engine.weight_version == cv
                                   for r in live)
    report = {
        "n_requests": args.requests, "finished": finished, "lost": lost,
        "token_mismatches": mismatches, "publishes": publishes,
        "killed_replica": victim, "kill_tick": kill_tick,
        "newest_version": newest, "staleness_window": window,
        "staleness_window_held": window_ok,
        "survivor_staleness_max": st["staleness_max"],
        "forced_catchups": st["forced_catchups"],
        "edge_exchanges": st["edge_exchanges"],
        "converged_version": cv, "fleet_converged": converged,
        "sync": router.stats()["sync"],
    }
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(f"async-publish drill: {finished}/{args.requests} finished, "
              f"{lost} lost, {mismatches} token mismatches, "
              f"{publishes} publishes over gossip edges, replica {victim} "
              f"killed at tick {kill_tick}, window<= {window} held="
              f"{window_ok}, survivor staleness {st['staleness_max']}, "
              f"converged v{cv} on {len(live)} survivors={converged}")
    ok = (lost == 0 and mismatches == 0 and window_ok and killed
          and st["staleness_max"] == 0 and converged)
    if not ok:
        print("chaos drill: FAILED", file=sys.stderr)
        return 1
    print("chaos drill: ok")
    return 0


def _process_drill(args) -> int:
    """ISSUE 17 acceptance drill: 2+ real worker processes, >= 1 real
    SIGKILL and >= 1 real SIGSTOP mid-trace, zero lost + token parity +
    ACTIVE-only. The spec is the deterministic engine recipe every
    worker rebuilds (same init seed => byte-identical weights), with RPC
    timeouts sized so a frozen worker costs seconds, not minutes."""
    from shuffle_exchange_tpu.serving import run_process_chaos_drill

    spec = {
        "model": dict(vocab=97, d=32, layers=2, heads=4, seq=128,
                      activation="swiglu", norm="rmsnorm", position="rope",
                      n_kv_heads=2, tie_embeddings=False),
        "init_seed": 0,
        "inference": dict(
            dtype="float32", max_seq_len=64, kv_block_size=8,
            num_kv_blocks=40,
            serving={"token_budget": 16, "max_running": 4, "chunk_min": 4},
            router={"heartbeat_interval_s": 0.25, "suspect_after_misses": 4,
                    "dead_after_misses": 16, "tick_timeout_s": 10.0,
                    "health_check_interval_s": 0.05,
                    "poison_death_threshold": 3, "fleet_mode": "process",
                    "rpc_call_timeout_s": 2.0, "rpc_ping_timeout_s": 1.0}),
    }
    n_replicas = max(2, args.replicas if args.replicas != 3 else 2)
    if args.kills:
        kills = []
        for spec_s in args.kills:
            after, kind, rid = spec_s.split(":")
            kills.append((int(after), kind, int(rid)))
    else:
        kills = [(max(1, args.requests // 3), "kill", 0),
                 (max(2, 2 * args.requests // 3), "stop", 1)]
    report = run_process_chaos_drill(
        spec, n_replicas=n_replicas, n_requests=args.requests,
        max_new=args.max_new, seed=args.seed, kills=kills,
        revive=not args.no_revive, timeout_s=600.0)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        fo = report["failover"]
        print(f"process chaos drill: {report['finished']}/"
              f"{report['n_requests']} finished, {report['lost']} lost, "
              f"{report['token_mismatches']} token mismatches, "
              f"kills={[(k['kind'], k['replica']) for k in report['kills']]}"
              f", {fo['deaths']} deaths -> {fo['recovered_requests']} "
              f"recovered ({fo['reprefill_tokens']} re-prefill tokens), "
              f"active_only={report['active_only']}")
    print("chaos drill: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
