#!/usr/bin/env python
"""Find a serving cell's knee, once, on the chip: the highest offered rate
whose backlog does not grow.

    python chipbench/sweep.py --workload mistral7b-chat --rates 4,4.5,5,5.5 --seconds 45

One process and one engine (loaded and walked through its program ladder
once), then the cell's traffic at each rate for ``--seconds``, drained before
the next. Per rate one JSON line: requests in the system (queued + active) at
the middle and at the end of the offered span, their least-squares slope over
the second half, TTFT from the due time, tokens per second. The knee is read
off by hand and written into the traffic file as ``knee``, with ``rate`` at
0.8 of it; ``chipbench/README.md`` records the sweep.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, traffic_gen  # noqa: E402
from chipbench.drivers import serve_open_loop  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=45.0,
                    help="the benchmark's run_seconds: each rate then sees the "
                         "requests the cell would see at that rate")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)

    import numpy as np

    devices = harness.require_chips(cell["chips"])
    harness.cache_programs()
    meter = harness.CompileMeter()
    server = serve_open_loop.Server({"cell": cell, "seed": args.seed})
    traffic = cell["traffic"]
    mark = meter.mark()
    programs = server.warm_ladder(traffic)
    harness.emit(phase="ladder", programs=programs, **meter.since(mark),
                 device=harness.describe_device(devices))
    sched = server.sched
    for rate in [float(r) for r in args.rates.split(",")]:
        trace = traffic_gen.serve_trace(traffic, args.seed, args.seconds,
                                        server.mcfg.vocab_size, rate=rate)
        depth = []

        def on_time(now):
            depth.append((now, len(sched.queue) + len(sched.active),
                          len(sched.queue)))

        mark = meter.mark()
        t0 = time.perf_counter()
        offered = server.offer(trace, args.seconds, drain_s=60.0,
                               on_time=on_time)
        wall = time.perf_counter() - t0
        stats = serve_open_loop.request_stats(server, trace, offered,
                                              args.seconds)
        rows = np.array([d for d in depth if d[0] <= args.seconds])
        half = rows[rows[:, 0] >= args.seconds / 2]
        slope = float(np.polyfit(half[:, 0], half[:, 1], 1)[0])
        pct = harness.percentile
        harness.emit(
            rate=rate, requests=len(trace["arrivals"]),
            failed=stats["failed"], in_system_mid=int(half[0, 1]),
            in_system_end=int(rows[-1, 1]), queued_end=int(rows[-1, 2]),
            in_system_slope_per_s=slope,
            in_system_mean=float(rows[:, 1].mean()),
            ttft_ms_p50=1e3 * (pct(stats["ttft_s"], 50) or 0),
            ttft_ms_p95=1e3 * (pct(stats["ttft_s"], 95) or 0),
            itl_ms_p50=1e3 * (pct(stats["itl_s"], 50) or 0),
            itl_ms_p95=1e3 * (pct(stats["itl_s"], 95) or 0),
            tokens_per_s=stats["tokens_in_window"] / args.seconds,
            drained_after_s=wall - args.seconds,
            compiles=meter.since(mark)["programs_compiled"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
