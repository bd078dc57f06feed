#!/usr/bin/env python
"""The UNTRACED window's step log, for a builder on the chip (PR 69).

    python scripts/window_step_log.py --workload gpt2m-train --seed 7 --seconds 45

runs ``chipbench.run.run_cell(workload, seed, seconds, False)`` in this
process, as the driver's untraced run does (the cell's phase lines come
first; the rate's tokens and seconds are on its ``window`` line), then
prints the program's own log of the window's steps
(``trace.steps("train")``, cut to the window: the first ``attempted`` records
after the driver's ``setup`` phase line, which every driver prints between
its last warm-up step and the window; some train once more after it) as one
JSON line ``window_step_log`` with
the fields of the traced run's ``program_step_log``
(``chipbench/STEPLOG.md``) beside the run's rate, and last the run's own last
line. In the untraced window two steps are in flight, so a step's interval is
the device's step time and its record (``host_ms``) the host's dispatch.

``--train-config`` merges a JSON object into the cell's ``train_config``
before the engine is built (``{"wall_clock_breakdown": true}``, a monitor):
what an operator's tracing costs, against the plain run.

This script goes when ``chipbench/run.py`` prints the untraced window's log
itself: a ``benchmark`` PR's edit, the two lines ``STEPLOG.md`` gives.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness, run  # noqa: E402
from chipbench.reducers import program_step_log  # noqa: E402
from shuffle_exchange_tpu.profiling import trace  # noqa: E402


def merge_train_config(overlay: dict) -> None:
    """Every cell this process loads gets ``overlay`` in its train_config."""
    load = harness.load_cell

    def load_with(workload, root=harness.ROOT):
        cell = load(workload, root)
        cell["config"]["chipbench"]["train_config"].update(overlay)
        return cell

    harness.load_cell = load_with


def note_setup_lines() -> list:
    """The times, on ``perf_counter``, at which a ``setup`` phase line was
    printed: the window begins after the last of them."""
    emit, at = harness.emit, []

    def emit_and_note(**record):
        if record.get("phase") == "setup":
            at.append(time.perf_counter())
        emit(**record)

    harness.emit = emit_and_note
    return at


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--train-config", type=json.loads, default={})
    args = ap.parse_args(argv)
    if args.train_config:
        merge_train_config(args.train_config)
    setup_at = note_setup_lines()
    last = json.loads(run.run_cell(args.workload, args.seed, args.seconds,
                                   False))
    records = trace.steps("train", since=setup_at[-1])[:last["attempted"]]
    rate = last["metrics"]["train_tokens_per_s_chip"]["value"]
    harness.emit(phase="window_step_log", cell=args.workload, seed=args.seed,
                 train_config=args.train_config, correct=last["correct"],
                 train_tokens_per_s_chip=rate,
                 setup_s=last["metrics"]["setup_s"]["value"],
                 **program_step_log.log_line(records))
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
