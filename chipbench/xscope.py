"""The traced run's trace, read again for what the program reports about
itself (PR 26): the scope of every device op, the program's ``sxt:`` host spans
and step annotations, module executions. ``xtrace.extract`` keeps only the
benchmark's ``cb:`` host events and drops event stats, so the reducers that
need more cut their own table here, from the ``.xplane.pb`` that is still on
disk while reducers run (``<root>/.cache/chipbench_trace/<workload>``).

The table (what ``tests/data/*.scoped.json.gz`` records; ``xtrace.load_table``
reads them):

    {"devices": [{"name", "ops": [[instruction, start_ns, dur_ns, scope_index]],
                  "modules": [[module, start_ns, dur_ns]]}],
     "scopes":  [op_name path, ...]            # ops index into this list
     "host":    [[name, start_ns, dur_ns, line, numbers]]  # sxt: and cb: events
     "program_ops": {instruction: [scope, opcode, contains_collective]}}

An op's scope is the ``op_name`` the compiler kept for it. The chip's ``XLA
Ops`` events carry no such stat (only ``device_offset_ps`` and
``device_duration_ps``: first traced run of PR 26), so it comes from the
program's ``program_ops`` table joined on the instruction name
(``trace.registered_ops``). A program without the tracer (the parent of PR 26)
has neither ``sxt:`` events nor that table: ``table`` returns None and every
reducer built on it leaves its metric out.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import xtrace

PROGRAM_PREFIX = "sxt:"
STEP_NAMES = ("sxt:train", "sxt:serve")         # StepTraceAnnotations
CONTROL_FLOW = ("while", "call", "conditional")

_KEY = "_xscope"


def instruction(name: str) -> str:
    """``%fusion.399 = (bf16[...]) fusion(...)`` -> ``fusion.399``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def extract(xplane_path: str, program_ops: Optional[dict] = None,
            program: str = "train_step") -> dict:
    """Cut the xplane to the table. ``program_ops`` is joined, by instruction
    name, onto the ops that ran inside an execution of ``jit_<program>`` (the
    names of another module's instructions mean nothing in this table)."""
    import bisect

    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    scopes: Dict[str, int] = {"": 0}
    devices, host = [], []
    program_ops = program_ops or {}

    for plane in data.planes:
        if xtrace.is_device_plane(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines
                     if line.name in (xtrace.OPS_LINE, xtrace.MODULES_LINE)}
            modules = [[e.name.split("(", 1)[0], e.start_ns, e.duration_ns]
                       for e in lines.get(xtrace.MODULES_LINE, [])]
            runs = sorted((s, s + d) for n, s, d in modules
                          if n == "jit_" + program)
            starts = [a for a, _ in runs]
            ops = []
            for e in lines.get(xtrace.OPS_LINE, []):
                name, path = instruction(e.name), ""
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns < runs[i][1]:
                    path = (program_ops.get(name) or [""])[0]
                ops.append([name, e.start_ns, e.duration_ns,
                            scopes.setdefault(path, len(scopes))])
            devices.append({"name": plane.name, "ops": ops, "modules": modules})
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                host += [[e.name, e.start_ns, e.duration_ns, i, numbers(e)]
                         for e in line.events
                         if e.name.startswith((PROGRAM_PREFIX, xtrace.HOST_PREFIX))]
    devices.sort(key=lambda p: p["name"])
    return {"devices": devices, "host": host,
            "scopes": sorted(scopes, key=scopes.get),
            "program_ops": program_ops}


def numbers(event) -> dict:
    """The numbers a program span carries (``span(name, wait_ms=...)``)."""
    return {str(k): float(v) for k, v in event.stats
            if isinstance(v, (int, float)) and not str(k).startswith("_")}


def registered_program_ops(name: str = "train_step") -> dict:
    """The program's own instruction table, if the program has a tracer."""
    try:
        from shuffle_exchange_tpu.profiling import trace

        return {k: list(v) for k, v in
                (trace.registered_ops(name) or {}).items()}
    except (ImportError, AttributeError):
        return {}


def table(ctx: dict) -> Optional[dict]:
    """The traced run's table, cut once per run and kept in ``ctx``; None
    where there is no trace or the program wrote no ``sxt:`` event into it."""
    if _KEY not in ctx:
        cell = ctx["cell"]
        path = xtrace.find_xplane(os.path.join(
            cell["root"], ".cache", "chipbench_trace", cell["name"]))
        found = extract(path, registered_program_ops()) if path else None
        if found is not None and not any(
                n.startswith(PROGRAM_PREFIX) for n, *_ in found["host"]):
            found = None
        ctx[_KEY] = found
    return ctx[_KEY]


# ---------------------------------------------------------------------------
# Reading the table
# ---------------------------------------------------------------------------


def window(tab: dict) -> Tuple[float, float]:
    """The benchmark's ``cb:window`` annotation, else the extent of the ops."""
    for n, s, d, *_ in tab["host"]:
        if n == xtrace.HOST_PREFIX + "window":
            return s, s + d
    every = [(s, s + d) for p in tab["devices"] for _, s, d, _ in p["ops"]]
    return (min((a for a, _ in every), default=0.0),
            max((b for _, b in every), default=0.0))


@functools.lru_cache(maxsize=None)
def components(path: str) -> Tuple[str, ...]:
    """``jit(f)/transpose(jvp(mlp))/while/body/mul`` -> ``(f, mlp, while,
    body, mul)``: each path component without its transform wrappers. A
    trace has thousands of ops on a few hundred paths."""
    return tuple(re.sub(r"^(?:\w+\()*|\)*$", "", c) for c in path.split("/"))


def innermost(path: str, names: Iterable[str]) -> str:
    """The last component of ``path`` that is one of ``names``, or ""."""
    names = set(names)
    return next((c for c in reversed(components(path)) if c in names), "")


def op_self_times(tab: dict):
    """(name, scope path, self ns) for every op event of the first device
    inside the window; nesting as ``xtrace.self_times``."""
    if not tab["devices"]:
        return []
    lo, hi = window(tab)
    ops = tab["devices"][0]["ops"]
    rows = [(i, max(s, lo), min(s + d, hi)) for i, (_, s, d, _) in
            enumerate(ops) if min(s + d, hi) > max(s, lo)]
    return [(ops[i][0], tab["scopes"][ops[i][3]], d)
            for i, d in xtrace.self_times(rows)]


def program_spans(tab: dict, steps: bool = False):
    """The program's host spans as (name, start, end, line), without the
    prefix; step annotations only where asked for."""
    return [(n[len(PROGRAM_PREFIX):], s, s + d, line)
            for n, s, d, line, _ in tab["host"]
            if n.startswith(PROGRAM_PREFIX) and (n in STEP_NAMES) == steps]


def innermost_segments(spans) -> List[Tuple[float, float, str]]:
    """Cut the host's timeline into non-overlapping (start, end, name)
    pieces, each named for the innermost span open there. Lines (threads) are
    cut one by one; where two threads both have a span open the earlier
    line's piece comes first and the reader takes the first that covers."""
    out = []
    for line in sorted({ln for *_, ln in spans}):
        rows = sorted(((a, b, n) for n, a, b, ln in spans if ln == line),
                      key=lambda r: (r[0], -(r[1] - r[0])))
        stack: List[Tuple[float, float, str]] = []
        cursor = 0.0
        for a, b, n in rows + [(float("inf"), float("inf"), "")]:
            while stack and stack[-1][1] <= a:      # spans that ended before
                _, end, name = stack.pop()
                if end > cursor:
                    out.append((cursor, end, name))
                    cursor = end
            if stack and a > cursor:                # the parent, up to here
                out.append((cursor, a, stack[-1][2]))
            cursor = a
            stack.append((a, b, n))
    return sorted(out)
