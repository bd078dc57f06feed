"""Crash-safe experiment running: the trial journal and the runner.

ISSUE 14 tentpole, part 2b. An autotune search is hours of measured
trials on a TPU window that can be preempted at any moment; the
reference autotuner survives this by journaling every experiment to its
results dir and resuming from what is already measured. Same discipline
here, with the repo's checkpoint idioms applied:

- every committed trial is ONE file written tmp+rename
  (:func:`atomic_write_json` — the ``write_latest_tag`` idiom), so a
  kill at any byte leaves either a committed trial or a stale ``.tmp-*``
  file, never a torn JSON;
- :meth:`TrialJournal.resume` sweeps stale ``.tmp-*`` partials from a
  killed run and loads every committed trial, and
  :meth:`ExperimentRunner.run_one` consults the journal BEFORE running,
  so a resumed search re-runs nothing it already measured;
- the kill itself is continuously exercised through the
  ``testing/faults`` seam (site ``autotune_trial``: crash between the
  tmp write and the rename — the exact window a preemption tears).

The runner is objective-agnostic: the training tuner
(``autotuner.Autotuner``) and the serving search (``search.py``) both
ride it, which is what makes one chip session able to retune training
AND serving from a shared results dir.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import uuid
from typing import Callable, Dict, List, Optional, Tuple

from ..testing import faults
from ..utils.invariants import atomic_on_reject
from ..utils.logging import logger

__all__ = ["Trial", "TrialJournal", "ExperimentRunner", "atomic_write_json"]

_TMP_RE = re.compile(r"\.tmp-[0-9a-f-]+$")


def _fsync_dir(dirpath: str) -> None:
    """Make a rename durable: fsync the parent directory (the
    checkpoint ``write_latest_tag`` discipline — without it a power cut
    after os.replace can lose the committed entry)."""
    try:
        fd = os.open(dirpath, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def atomic_write_json(path: str, obj) -> str:
    """Write ``obj`` as JSON via tmp+rename(+dir fsync) in the target
    directory — atomic AND durable, so readers (and resumed runs after a
    power loss) only ever see a complete document. Returns ``path``."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp-{uuid.uuid4().hex[:12]}"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=2, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(d)
    return path


def sweep_stale_tmp(dirpath: str) -> int:
    """Remove ``*.tmp-*`` partials a killed run left behind; returns how
    many were swept (logged — a nonzero count documents the crash)."""
    swept = 0
    if not os.path.isdir(dirpath):
        return 0
    for name in os.listdir(dirpath):
        if _TMP_RE.search(name):
            try:
                os.remove(os.path.join(dirpath, name))
                swept += 1
            except OSError:   # concurrent sweep / perms: not our crash
                pass
    if swept:
        logger.warning(
            f"autotuning: swept {swept} stale partial trial file(s) from "
            f"{dirpath} (a previous run was killed mid-commit)")
    return swept


@dataclasses.dataclass
class Trial:
    """One measured (or to-be-measured) experiment: a candidate at a
    fidelity. ``key`` is the journal identity — stable across process
    restarts as long as the search space and schedule are unchanged."""

    key: str
    candidate_name: str
    round: int = 0
    fidelity: int = 0            # e.g. trace length measured at
    status: str = "pending"      # pending | ok | error | pruned_static
    metric: Optional[float] = None
    detail: Dict[str, object] = dataclasses.field(default_factory=dict)
    from_journal: bool = False   # True when resume() satisfied this trial

    def payload(self) -> Dict[str, object]:
        return {
            "key": self.key,
            "candidate": self.candidate_name,
            "round": self.round,
            "fidelity": self.fidelity,
            "status": self.status,
            "metric": self.metric,
            "detail": self.detail,
        }

    @classmethod
    def from_payload(cls, p: Dict[str, object]) -> "Trial":
        return cls(key=str(p["key"]), candidate_name=str(p["candidate"]),
                   round=int(p.get("round", 0)),
                   fidelity=int(p.get("fidelity", 0)),
                   status=str(p.get("status", "ok")),
                   metric=p.get("metric"),
                   detail=dict(p.get("detail") or {}),
                   from_journal=True)


def _safe_name(key: str) -> str:
    safe = re.sub(r"[^A-Za-z0-9._@=-]+", "_", key)
    if len(safe) > 120:   # stay far under the 255-byte filename limit
        digest = hashlib.blake2b(key.encode(), digest_size=8).hexdigest()
        safe = f"{safe[:100]}-{digest}"
    return safe


class TrialJournal:
    """Per-trial results journal under ``<results_dir>/trials/``: one
    committed JSON file per trial key, written tmp+rename. ``resume()``
    (run at construction) sweeps stale partials and loads everything
    committed, so the runner can skip already-measured work."""

    def __init__(self, results_dir: str):
        self.dir = os.path.join(results_dir, "trials")
        os.makedirs(self.dir, exist_ok=True)
        self.swept_stale = 0
        self._committed: Dict[str, Dict[str, object]] = {}
        self.resume()

    def __len__(self) -> int:
        return len(self._committed)

    def keys(self) -> List[str]:
        return sorted(self._committed)

    def get(self, key: str) -> Optional[Dict[str, object]]:
        return self._committed.get(key)

    def resume(self) -> Dict[str, Dict[str, object]]:
        """Sweep stale ``.tmp-*`` partials, then (re)load every committed
        trial file. A file that fails to parse is impossible through this
        writer (rename is atomic) and is treated as foreign: skipped with
        a warning, never deleted."""
        self.swept_stale += sweep_stale_tmp(self.dir)
        self._committed = {}
        for name in sorted(os.listdir(self.dir)):
            if not name.endswith(".json"):
                continue
            path = os.path.join(self.dir, name)
            try:
                with open(path) as f:
                    p = json.load(f)
                self._committed[str(p["key"])] = p
            except (json.JSONDecodeError, KeyError, OSError) as e:
                logger.warning(
                    f"autotuning: ignoring unreadable trial file {path}: {e}")
        return dict(self._committed)

    @atomic_on_reject(check="validate")
    def record(self, key: str, payload: Dict[str, object]) -> str:
        """Commit one trial atomically. Validates serializability BEFORE
        touching the filesystem or journal state (a rejected record
        mutates nothing); the ``autotune_trial`` fault site sits between
        the tmp write and the rename-commit — the window a kill tears —
        so the crash→resume contract is continuously drilled."""
        if key in self._committed:
            raise ValueError(f"trial {key!r} is already journaled "
                             f"(keys are run-unique; resume skips them)")
        payload = dict(payload)
        payload.setdefault("key", key)   # files are self-describing
        body = json.dumps(payload)   # raises on non-serializable detail
        del body
        path = os.path.join(self.dir, _safe_name(key) + ".json")
        tmp = f"{path}.tmp-{uuid.uuid4().hex[:12]}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        # the preemption window: a kill here leaves the stale tmp a
        # resume must sweep, and NO committed trial — exactly what a real
        # mid-commit SIGKILL leaves behind
        if faults.ACTIVE:
            faults.maybe_crash("autotune_trial", index=0)
        os.replace(tmp, path)
        _fsync_dir(self.dir)
        self._committed[key] = payload
        return path


class ExperimentRunner:
    """Runs trials through an optional journal: a journaled key is
    restored without execution, anything else is measured, committed,
    and counted in ``executed`` — the list tests (and the ci_full smoke)
    use to prove a resumed search re-ran nothing and that
    statically-pruned candidates were never measured."""

    def __init__(self, journal: Optional[TrialJournal] = None):
        self.journal = journal
        self.executed: List[str] = []

    def run_one(self, key: str,
                fn: Callable[[], Dict[str, object]]
                ) -> Tuple[Dict[str, object], bool]:
        """(payload, from_journal). ``fn`` produces the trial payload —
        a JSON-serializable dict with at least ``status``."""
        if self.journal is not None:
            cached = self.journal.get(key)
            if cached is not None:
                return cached, True
        payload = fn()
        if self.journal is not None:
            self.journal.record(key, payload)
        self.executed.append(key)
        return payload, False
