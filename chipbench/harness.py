"""What every cell of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the device check, the compile meter, spans
recorded from outside the program, and the one JSON object a run ends with.

Nothing here imports JAX at module level: ``run.py`` reads the cell first and
touches JAX only after that.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The benchmark's own files are wrong, or the machine is not the one the
    cell asks for: the run prints no result and exits non-zero."""


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def emit(**record) -> None:
    """A phase line: free-form JSON before the last line."""
    print(json.dumps(record, default=float), flush=True)


# ---------------------------------------------------------------------------
# A cell and its files
# ---------------------------------------------------------------------------


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration file, its traffic file and the metrics it reports. Every
    file is found by the name the benchmark gives it."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = read_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = read_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": int(cell["chips"]), "why": cell["why"],
        "config_name": cell["config"], "config": config,
        "reduced": entry["reduced"], "source": entry["source"],
        "traffic_name": cell["traffic"], "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "bench_dir": bench_dir, "root": root,
    }


def named_module(package: str, name: str, named_by: str):
    """``chipbench/<package>/<name>.py``, for a name read from a data file."""
    if not name.replace("_", "").isalnum():
        raise BenchError(f"{named_by} names {name!r}: not a module name")
    full = f"chipbench.{package}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise BenchError(f"{named_by} names {name!r}; there is no "
                         f"chipbench/{package}/{name}.py") from e


def load_driver(name: str):
    """``chipbench/drivers/<name>.py``: a traffic file names its driver."""
    return named_module("drivers", name, "the traffic file's driver")


def model_config(cell: dict, rehearsal):
    """The cell's ``TransformerConfig``: the configuration file's keys are
    the source's own (an HF ``config.json``), read by the program's importer.
    A rehearsal (tests) brings a tiny one of its own."""
    if rehearsal and rehearsal.get("model_cfg") is not None:
        return rehearsal["model_cfg"]
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return config_from_hf(cell["config"])


def cache_programs(every: bool = True) -> str:
    """Turn the persistent compile cache on where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.cache/jax``) and
    return the directory. ``every``: cache fast compiles too, so that only a
    cell's first run in a checkout compiles."""
    import jax

    from shuffle_exchange_tpu.utils.compile_cache import enable_compile_cache

    if every:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable_compile_cache()


def chip_peaks(device_kind: str, bench_dir: str = HERE) -> dict:
    """Published peaks of one chip, by ``device_kind``. A device that is not
    in ``peaks.json`` is an error, not a default."""
    table = read_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"chipbench/peaks.json (has {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# The device
# ---------------------------------------------------------------------------


def require_chips(chips: int) -> List[Any]:
    """The cell's TPU chips, or a BenchError: there is no CPU branch."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"no accelerator: JAX's first device is "
                         f"{d0.platform!r} ({d0.device_kind}); nothing was run")
    if len(devices) != chips or d0.memory_stats() is None:
        raise BenchError(f"the cell needs exactly {chips} TPU chip(s) with "
                         f"memory_stats(); JAX reports {len(devices)}")
    return devices


def describe_device(devices) -> dict:
    """As JAX reports it; ``memory_peak_bytes`` is the fullest chip's."""
    d0 = devices[0]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def seed32(seed: int) -> int:
    """``--seed`` may be wider than 31 bits; PRNG keys and sampling seeds
    are not."""
    return int(seed) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# Compilations (copied from chip_smoke.CompileMeter)
# ---------------------------------------------------------------------------


class CompileMeter:
    """Counts the programs JAX compiles (or reads back from the persistent
    cache) and the seconds that takes, through ``jax.monitoring`` - the
    backend-compile event wraps the cache lookup, so a hit is counted as a
    program with a small duration and also as a ``cache_hit``."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self._COMPILE:
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def mark(self) -> Tuple[int, float, int, float]:
        return (self.programs, self.seconds, self.cache_hits,
                time.perf_counter())

    def since(self, mark) -> Dict[str, float]:
        p, s, h, t = mark
        wall = time.perf_counter() - t
        compile_s = self.seconds - s
        return {"programs_compiled": self.programs - p,
                "compile_cache_hits": self.cache_hits - h,
                "compile_s": round(compile_s, 2),
                "run_s": round(max(0.0, wall - compile_s), 2)}


# ---------------------------------------------------------------------------
# Spans, recorded from outside the program
# ---------------------------------------------------------------------------


class Spans:
    """Spans kept in memory as (name, start, end) on one clock. ``wrap``
    replaces a bound method of an object the benchmark built with a timed
    one: the program itself is not edited."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 annotate: bool = False):
        self.clock = clock
        self.annotate = annotate
        self.rows: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        """Time a block. In a traced run the span is also written into the
        profiler's trace (``cb:<name>``), on the device trace's clock, so
        that idle gaps can be laid against it."""
        note = contextlib.nullcontext()
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation("cb:" + name)
        t0 = self.clock()
        try:
            with note:
                yield
        finally:
            self.rows.append((name, t0, self.clock()))

    def wrap(self, obj, attr: str, name: str) -> None:
        inner = getattr(obj, attr)

        def timed(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)

        setattr(obj, attr, timed)

    def named(self, name: str) -> List[Tuple[float, float]]:
        return [(a, b) for n, a, b in self.rows if n == name]


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None of nothing."""
    if not len(values):
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def stat(values, name: str) -> Optional[float]:
    """``median``, ``mean``, ``sum`` or ``p<q>`` of a list; None of nothing."""
    if not len(values):
        return None
    if name == "median":
        return float(statistics.median(values))
    if name == "mean":
        return float(statistics.fmean(values))
    if name == "sum":
        return float(sum(values))
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise BenchError(f"unknown statistic {name!r}")


# ---------------------------------------------------------------------------
# Per-layer metrics: a file each, a reducer by name
# ---------------------------------------------------------------------------


def layer_metrics(cell: dict, ctx: dict) -> Dict[str, dict]:
    """Every per-layer metric this cell reports, each from its own file
    ``layer_metrics/<name>.json`` through the reducer it names. A reducer
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        spec = read_json(os.path.join(cell["bench_dir"], "layer_metrics",
                                      m["name"] + ".json"))
        reducer = named_module("reducers", spec["reducer"],
                               f"layer_metrics/{m['name']}.json")
        value = reducer.reduce(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def last_line(*, correct: bool, attempted: int, failed: int,
              metrics: Dict[str, dict], device: dict,
              breakdown: Optional[dict] = None) -> str:
    """The one JSON object a run ends with: these keys and no other."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    return json.dumps(out, default=float)
