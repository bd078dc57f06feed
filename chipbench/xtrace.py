"""From the profiler's trace to numbers: the reduction every PR shares.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData.from_file`` reads it with nothing but JAX. The
trace is first cut down to a plain table (``extract``): per device plane the
``XLA Ops`` and ``XLA Modules`` lines as ``[name, start_ns, duration_ns]``
rows, and of the host only the benchmark's own ``cb:`` annotations (they sit
on the trace's clock, so idle gaps can be laid against them). Everything else
works on that table, which is what ``tests/data`` records (PR 25:
``json.dump(extract(path), gzip.open(out, "wt"))``, cut to one step).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

HOST_PREFIX = "cb:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|all-to-all")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def extract(xplane_path: str) -> dict:
    """The table: device planes with their op and module lines, and the
    host's ``cb:`` annotations."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = [], []
    for plane in data.planes:
        if is_device_plane(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [[e.name, e.start_ns, e.duration_ns]
                                        for e in line.events]
            devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    devices.sort(key=lambda p: p["name"])
    return {"devices": devices, "host": host}


def load_table(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of the union ``a`` that the union ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# ---------------------------------------------------------------------------
# The summary
# ---------------------------------------------------------------------------


def short_name(name: str) -> str:
    """An op's name as the breakdown shows it: the trace gives the whole HLO
    instruction (``%fusion.399 = (bf16[...]) fusion(...)``); kept is the
    instruction's name without ``%`` and without its ``.<n>`` suffix, so that
    ``fusion.399`` and ``fusion.400`` add up. Kernels keep the name their
    ``pallas_call`` gave them."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head) or head


def self_times(rows) -> List[Tuple[str, float]]:
    """(name, self duration) per event of one line. The ops line nests: a
    ``while`` spans the ops of its body, so an op's own time is its duration
    less the events directly inside it."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []          # (end, index into out)
    for n, a, b in sorted(rows, key=lambda r: (r[1], -(r[2] - r[1]))):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(b, stack[-1][0]) - a
        out.append([n, b - a])
        stack.append((b, len(out) - 1))
    return [(n, max(0.0, d)) for n, d in out]


def _rows(plane: dict, line: str, lo: float, hi: float):
    return [(n, max(s, lo), min(s + d, hi)) for n, s, d in
            plane["lines"].get(line, []) if min(s + d, hi) > max(s, lo)]


def summarize_table(table: dict) -> dict:
    """Busy and idle seconds, the ops that took most device time, idle gaps
    by the host span that covered them, module executions and the time inside
    collective ops. The window is the benchmark's ``cb:window``
    annotation where the trace has one, else the extent of the device ops.
    Per-device numbers are of the first device; ``busy_s`` is the mean over
    the devices. ``collective_sync_s`` is the self time of the ops the trace
    names as collectives on the ops line."""
    devices, host = table["devices"], table["host"]
    windows = [(s, s + d) for n, s, d in host if n == HOST_PREFIX + "window"]
    if windows:
        lo, hi = windows[0]
    else:
        every = [(s, s + d) for p in devices
                 for _, s, d in p["lines"].get(OPS_LINE, [])]
        lo = min((a for a, _ in every), default=0.0)
        hi = max((b for _, b in every), default=0.0)
    ns = 1e-9
    out = {"window_s": (hi - lo) * ns, "busy_s": 0.0, "devices": len(devices),
           "ops": {}, "modules": [], "collective_sync_s": 0.0,
           "idle_by_span": {},
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not devices:
        return out
    busy_each = [length(union((a, b) for _, a, b in _rows(p, OPS_LINE, lo, hi)))
                 for p in devices]
    out["busy_s"] = sum(busy_each) / len(busy_each) * ns
    first = devices[0]
    rows = _rows(first, OPS_LINE, lo, hi)
    ops: Dict[str, float] = {}
    sync = 0.0
    for n, d in self_times(rows):
        key = short_name(n)
        ops[key] = ops.get(key, 0.0) + d * ns
        if COLLECTIVE.search(key):
            sync += d * ns
    out["ops"] = ops
    out["modules"] = [[n.split("(", 1)[0], (b - a) * ns]
                      for n, a, b in _rows(first, MODULES_LINE, lo, hi)]
    # a core runs one op at a time: while a collective op (a synchronous
    # all-gather, the -done half of an asynchronous one) is the innermost
    # event of the ops line, nothing else runs there. What an asynchronous
    # collective overlaps is not on this line
    out["collective_sync_s"] = sync
    # idle gaps of the first device, by the innermost host span over each
    # gap's midpoint
    busy = union((a, b) for _, a, b in rows)
    gaps = subtract([(lo, hi)], busy)
    spans = sorted(((s, s + d, n[len(HOST_PREFIX):]) for n, s, d in host
                    if n != HOST_PREFIX + "window"), key=lambda r: r[1] - r[0])
    idle: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        name = next((n for s, e, n in spans if s <= mid < e), "no_span")
        idle[name] = idle.get(name, 0.0) + (b - a) * ns
    out["idle_by_span"] = idle
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    out["breakdown"] = {
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:10]]}
    return out


def summarize(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        return summarize_table({"devices": [], "host": []})
    return summarize_table(extract(path))
