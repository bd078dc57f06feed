"""Serving cells: ``ContinuousBatchingScheduler(InferenceEngineV2(...))``
under open-loop load at a rate fixed in the traffic file. Starts from
``chip_smoke.phase_server`` (PR 23): seeded bf16 weights born on the device,
greedy decoding, served tokens held to a plain forward by logits.

The loop is ``scheduler.serve``'s own (submit what is due, tick, sleep when
idle), copied so that the benchmark knows each request's DUE time: time to
first token counts from ``t0 + arrival``, not from the submission, which a
stalled tick delays.

Set-up walks the engine's whole program ladder (``Server.warm_ladder``): the
engine compiles one program per (decode rows, decode table width, prefill
rows, chunk length, prefill table width), each binned to a power of two, and
has no call that precompiles them, so the benchmark drives ``engine.step``
once through every combination the cell's configuration and traffic can
reach. A new process pays about two seconds of tracing for each even when
the persistent cache holds the executable, so a cell's configuration has to
keep that ladder short (``PERF.md``, section 6: why no serving cell does yet).
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from chipbench import arith, harness, reference, traffic_gen


def bf16_params(model, seed: int, dtype):
    """Seeded random weights born in the serving dtype on the device, in one
    jitted call: the float32 tree ``model.init`` describes would not fit
    beside the pool at these widths, so the cast is fused into the
    initialiser."""
    import jax

    return jax.jit(lambda k: jax.tree.map(
        lambda p: p.astype(dtype), model.init(k)))(jax.random.PRNGKey(seed))


class Server:
    """The system under test and the benchmark's view of it."""

    def __init__(self, ctx: dict):
        import jax.numpy as jnp

        from shuffle_exchange_tpu.inference import (
            ContinuousBatchingScheduler, InferenceConfig, InferenceEngineV2)
        from shuffle_exchange_tpu.models import Transformer

        cell = ctx["cell"]
        rehearsal = ctx.get("rehearsal") or {}
        settings = cell["config"]["chipbench"]
        self.mcfg = harness.model_config(cell, rehearsal)
        inference = {**settings["inference"], **rehearsal.get("inference", {})}
        self.icfg = InferenceConfig(**inference)
        model = Transformer(self.mcfg)
        self.params = bf16_params(model, harness.seed32(ctx["seed"]),
                                  getattr(jnp, self.icfg.dtype))
        self.engine = InferenceEngineV2(model, self.params, self.icfg)
        self.clock = time.perf_counter      # the scheduler's clock and ours
        self.sched = ContinuousBatchingScheduler(self.engine, clock=self.clock)
        self.calls = []          # one row per engine dispatch, traced runs

    def instrument(self, spans) -> None:
        """Spans from outside: around ``tick()`` and around the engine call
        inside it (to token readback: ``step`` returns host arrays)."""
        spans.wrap(self.sched, "tick", "tick")
        sched, calls = self.sched, self.calls
        for attr in ("step", "step_sampled"):
            inner = getattr(self.engine, attr)

            def timed(decode_uids, decode_tokens, prefills=(), *a,
                      _inner=inner, **k):
                live = sum(len(sched.requests[u].prompt)
                           + len(sched.requests[u].generated)
                           for u in decode_uids)
                with spans.span("engine_step"):
                    out = _inner(decode_uids, decode_tokens, prefills, *a, **k)
                calls.append((spans.rows[-1][1], len(decode_uids), live,
                              sum(len(c) for _, c in prefills)))
                return out

            setattr(self.engine, attr, timed)

    def offer(self, trace: dict, seconds: float, *, drain_s: float = 0.0,
              on_time=None) -> dict:
        """Offer ``trace`` open loop and tick until every request is done or
        ``seconds + drain_s`` have passed."""
        sched = self.sched
        clock = self.clock
        arrivals = trace["arrivals"]
        pending = deque(range(len(arrivals)))
        uids, late = [], []
        t0 = clock()
        while pending or sched.active or sched.queue:
            now = clock() - t0
            if on_time is not None:
                on_time(now)
            if now >= seconds + drain_s:
                break
            while pending and now >= arrivals[pending[0]]:
                i = pending.popleft()
                uids.append(sched.submit(trace["prompts"][i],
                                         max_new_tokens=trace["max_new"][i]))
                late.append(sched.requests[uids[-1]].submitted_at
                            - (t0 + arrivals[i]))
            if not sched.tick() and pending:
                wait = arrivals[pending[0]] - (clock() - t0)
                if wait > 0:
                    time.sleep(wait)
        return {"t0": t0, "uids": uids, "late_s": late}

    def ladder(self, traffic: dict):
        """Every program key the cell can reach: ("decode", Bd, Wd),
        ("mixed", Bd, Wd, Bp, C, Wp), ("extend", Bp, C, Wp). Row counts and
        table widths are powers of two, chunk lengths the serving ladder;
        the traffic's clips bound the table widths, ``max_prefills`` (traffic
        file) the prefill rows of one tick."""
        icfg, bs = self.icfg, self.icfg.kv_block_size

        def pow2(lo, hi):
            out, b = [], 1
            while b < hi:
                if b >= lo:
                    out.append(b)
                b *= 2
            return out + [b]

        longest = min(icfg.max_seq_len,
                      traffic["prompt"]["max"] + traffic["answer"]["max"])
        widths = [min(w, icfg.max_seq_len // bs)
                  for w in pow2(1, -(-longest // bs))]
        widths = sorted(set(widths))
        rows = pow2(1, icfg.serving.max_running)
        prefills = pow2(1, int(traffic.get("max_prefills", 4)))
        chunks = [c for c in icfg.serving.bins()]
        lead = [(c, w) for c in chunks for w in widths
                if c <= w * bs]                 # a chunk fits its own table
        keys = [("decode", b, w) for b in rows for w in widths]
        keys += [("extend", p, c, w) for p in prefills for c, w in lead]
        keys += [("mixed", b, wd, p, c, w) for b in rows for wd in widths
                 for p in prefills for c, w in lead]
        return keys

    def warm_ladder(self, traffic: dict) -> int:
        """Drive ``engine.step`` once through every key of ``ladder``, on
        sequences of the benchmark's own (uids from 10**6), then flush them.
        A decode set of bin (B, W) is one carrier sequence whose blocks fall
        in bin W and B//2 one-block fillers; a prefill set of bin (P, C, W)
        is one lead sequence extended by a chunk of C tokens to a length in
        bin W (and rewound after), and P//2 one-token newcomers."""
        eng, bs = self.engine, self.icfg.kv_block_size
        budget = self.icfg.serving.token_budget
        keys = self.ladder(traffic)
        next_uid = [10 ** 6]

        def new_uid():
            next_uid[0] += 1
            return next_uid[0]

        def grow(uid, total):
            """Prefill the new ``uid`` to ``total`` tokens, a budget at a
            time."""
            base[uid] = total
            for have in range(0, total, budget):
                eng.step([], [], [(uid, [1] * min(budget, total - have))])

        def least(w):                       # fewest tokens whose blocks bin to w
            return 1 if w == 1 else (w // 2) * bs + 1

        base = {}
        widths = sorted({k[2] for k in keys if k[0] == "decode"})
        carriers = {w: new_uid() for w in widths}
        for w, uid in carriers.items():
            grow(uid, least(w))
        fillers = [new_uid() for _ in range(self.icfg.serving.max_running // 2)]
        for uid in fillers:
            grow(uid, 1)
        leads = {}

        def decode_set(b, w):
            return [carriers[w]] + fillers[:b // 2]

        def prefill_set(p, c, w):
            start = max(0, least(w) - c)
            if (start, w) not in leads:
                leads[start, w] = new_uid()
                if start:
                    grow(leads[start, w], start)
            fresh = [new_uid() for _ in range(p // 2)]
            return ([(leads[start, w], [1] * c)] + [(u, [1]) for u in fresh],
                    leads[start, w], start, fresh)

        for key in keys:
            kind = key[0]
            duids = decode_set(key[1], key[2]) if kind != "extend" else []
            rows, lead, start, fresh = (prefill_set(*key[-3:])
                                        if kind != "decode" else ([], 0, 0, []))
            eng.step(duids, [1] * len(duids), rows)
            for uid in duids:           # a decode row grew by its one token
                eng.rewind(uid, base[uid])
            if kind == "decode":
                continue
            if start:
                eng.rewind(lead, start)
            else:
                eng.flush([lead])
                del leads[start, key[-1]]
            if fresh:
                eng.flush(fresh)
        eng.flush(list(carriers.values()) + fillers + list(leads.values()))
        missing = [k for k in keys if k not in eng.program_shapes]
        if missing:
            raise harness.BenchError(
                f"the ladder walk missed {len(missing)} of {len(keys)} "
                f"programs, e.g. {missing[:3]}")
        return len(keys)


def request_stats(server: Server, trace: dict, offered: dict,
                  seconds: float) -> dict:
    """TTFT from the due time, inter-token gaps, and output tokens emitted
    inside the window, over every request of the trace."""
    t0 = offered["t0"]
    ttft, itl, in_window, failed, done = [], [], 0, 0, []
    for i, arrival in enumerate(trace["arrivals"]):
        if i >= len(offered["uids"]):
            failed += 1                       # never submitted
            continue
        r = server.sched.requests[offered["uids"][i]]
        if r.first_token_at is not None:
            times = r.first_token_at + np.concatenate(
                [[0.0], np.cumsum(r.tpot_s)])
            in_window += int((times <= t0 + seconds).sum())
        if len(r.generated) < trace["max_new"][i] or r.error is not None:
            failed += 1                       # refused, failed or undrained
            continue
        ttft.append(r.first_token_at - (t0 + arrival))
        itl.extend(r.tpot_s)
        done.append(i)
    return {"ttft_s": ttft, "itl_s": itl, "tokens_in_window": in_window,
            "failed": failed, "done": done}


def run(ctx: dict) -> dict:
    cell, meter, spans = ctx["cell"], ctx["meter"], ctx["spans"]
    rehearsal = ctx.get("rehearsal") or {}
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    seconds = ctx["seconds"]
    mark = meter.mark()
    server = Server(ctx)
    engine, sched, mcfg, icfg = (server.engine, server.sched, server.mcfg,
                                 server.icfg)
    trace = traffic_gen.serve_trace(traffic, ctx["seed"], seconds,
                                    mcfg.vocab_size)
    n = len(trace["arrivals"])
    drain_s = float(traffic.get("drain_s", 20.0))

    # -- set-up: every program the cell can reach, before the window -------
    walked = meter.mark()
    n_programs = server.warm_ladder(traffic)
    walk = meter.since(walked)
    shapes = engine.program_shapes
    pool = engine.cache.k
    from shuffle_exchange_tpu.ops import fused_decode as fd

    harness.emit(
        phase="setup", cell=cell["name"], model=cell["config_name"],
        reduced=cell["reduced"], layers=mcfg.n_layers, dtype=icfg.dtype,
        kv_pool_tokens=(icfg.num_kv_blocks - 1) * icfg.kv_block_size,
        kv_pool_bytes=engine.cache.pool_nbytes(), requests=n,
        rate=traffic["rate"], prompt_tokens=sum(map(len, trace["prompts"])),
        answer_tokens=sum(trace["max_new"]),
        serving={"token_budget": icfg.serving.token_budget,
                 "max_running": icfg.serving.max_running},
        routes={"decode_kernel": getattr(engine, "_decode_kernel", None),
                "fused_qkv": bool(getattr(engine, "_fuse_qkv", False)),
                "fused_mlp": bool(getattr(engine, "_fuse_mlp", False)),
                "kv_append": (fd.qkv_append_route(pool.shape, pool.dtype)
                              if getattr(engine, "_fuse_qkv", False)
                              else "xla-scatter")},
        ladder={"programs": n_programs, "walk_s": walk["run_s"] + walk["compile_s"],
                "compile_s": walk["compile_s"],
                "cache_hits": walk["compile_cache_hits"]},
        **meter.since(mark))

    # -- the window --------------------------------------------------------
    traced = bool(ctx["trace"])
    if traced:
        server.instrument(spans)
    trace_s = min(float(traffic.get("trace_seconds", 3.0)), seconds / 2)
    state = {"tracing": False, "done": False}

    def on_time(now):
        if traced and not state["tracing"] and not state["done"] \
                and now >= seconds - trace_s:
            ctx["start_trace"]()
            state["tracing"] = True
        if state["tracing"] and now >= seconds:
            ctx["stop_trace"]()
            state.update(tracing=False, done=True)

    in_window = meter.mark()
    d0, k0, p0 = engine.dispatch_count, sched.ticks, sched.preemptions
    t_open = time.perf_counter()
    ctx["window_start"](t_open)
    offered = server.offer(trace, seconds, drain_s=drain_s, on_time=on_time)
    if state["tracing"]:
        ctx["stop_trace"]()
    in_win = meter.since(in_window)
    ticks, dispatches = sched.ticks - k0, engine.dispatch_count - d0
    stats = request_stats(server, trace, offered, seconds)
    new_shapes = len(engine.program_shapes - shapes)

    # -- correct, outside the window ----------------------------------------
    rng = np.random.default_rng(ctx["seed"])
    k = min(int(traffic.get("check_requests", 4)), len(stats["done"]))
    sample = sorted(rng.choice(stats["done"], size=k, replace=False).tolist()) \
        if k else []
    served = [server.sched.requests[offered["uids"][i]].generated
              for i in sample]
    check = reference.reference_logit_check(
        mcfg, server.params, [trace["prompts"][i] for i in sample], served,
        pad_to=icfg.max_seq_len) if sample else \
        {"tokens": 0, "exact_argmax": 0, "worst_gap_sigma": float("inf")}
    tol = float(traffic.get("gap_tol_sigma", 0.1))
    checks = [
        (stats["failed"] == 0, f"{stats['failed']} of {n} requests failed, "
         f"were refused or did not drain in {drain_s} s"),
        (dispatches == ticks,
         f"{dispatches} dispatches over {ticks} ticks: not one per tick"),
        (check["worst_gap_sigma"] <= tol,
         f"a served token sits {check['worst_gap_sigma']} logit-sigmas under "
         f"the plain forward's best (bound {tol})"),
    ]
    pct = harness.percentile
    e2e = {"serve_tokens_per_s": stats["tokens_in_window"] / seconds}
    if stats["ttft_s"]:
        e2e["ttft_p95_ms"] = 1e3 * pct(stats["ttft_s"], 95)
    if stats["itl_s"]:
        e2e["itl_p95_ms"] = 1e3 * pct(stats["itl_s"], 95)
    harness.emit(
        phase="window", requests=n, completed=len(stats["done"]),
        ttft_ms={"p50": 1e3 * (pct(stats["ttft_s"], 50) or 0),
                 "p95": e2e.get("ttft_p95_ms"), "n": len(stats["ttft_s"])},
        itl_ms={"p50": 1e3 * (pct(stats["itl_s"], 50) or 0),
                "p95": e2e.get("itl_p95_ms"), "n": len(stats["itl_s"])},
        tokens_in_window=stats["tokens_in_window"],
        gen_late_ms_p95=1e3 * (pct(offered["late_s"], 95) or 0),
        ticks=ticks, dispatches=dispatches,
        preemptions=sched.preemptions - p0, new_program_shapes=new_shapes,
        reference=check, gap_tol_sigma=tol,
        tol_reason="bf16 near-ties flip greedy tokens; a wrong KV row or a "
                   "lower precision would sit whole sigmas under the best",
        failed_checks=[m for c, m in checks if not c], **in_win)
    # decode-only dispatches of the traced sub-window: the bytes each had to
    # read, for the kernels' share of the HBM roofline
    lo = t_open + seconds - trace_s
    decode_bytes = [arith.decode_bytes(mcfg, live) for t, nd, live, npre
                    in server.calls if t >= lo and nd and not npre]
    return {
        "correct": all(c for c, _ in checks), "attempted": n,
        "failed": stats["failed"], "end_to_end": e2e, "window_s": seconds,
        "counters": {"compiles_in_window": in_win["programs_compiled"],
                     "kv_preemptions": sched.preemptions - p0,
                     "ticks": ticks, "new_program_shapes": new_shapes},
        "facts": {"model_cfg": mcfg, "late_s": offered["late_s"],
                  "decode_bytes": decode_bytes},
    }
