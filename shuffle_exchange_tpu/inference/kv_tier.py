"""Host tier for paged KV blocks (ISSUE 15): spill, prefetch, fetch.

The serving pool (``PagedKVCache``) is sized by HBM; production contexts
are sized by books and codebases. This module is the tier between them:
COLD blocks of parked sequences move host-ward as raw pool storage —
data planes plus int8/fp8 scale planes, byte-exact, never re-quantized
(the disagg wire-format discipline of ``KVBlockPayload`` applied
vertically instead of horizontally) — and move back into FRESH device
blocks when the scheduler un-parks the sequence.

Substrate: the same AIO machinery the disaggregated transfer stages
through (``ops/native/aio.py``) — spilled bytes live in host arrays (or
an ``AsyncIOEngine``-written file per sequence when ``spill_dir`` is
set, the NVMe tier below host RAM), and prefetch assembles them into
long-lived page-aligned ``PinnedBufferPool`` staging buffers one tick
AHEAD of the expected fetch, so the fetch's critical path is only the
device scatter (the FPDT double-buffered-offload idiom, SURVEY §2.6 and
§5.7, at block granularity).

Threading: the tier is touched from replica threads (scheduler ticks)
and the failover path (export of a spilled sequence), so its state rides
one lock — ``HostKVTier._mu``, rank 20 in ``utils.invariants.LOCK_ORDER``
next to the transfer substrate's locks, sanitizer-wrapped at the
construction site like every other fleet lock.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..testing import sanitizer
from ..utils.invariants import locked_by, requires_lock
from ..utils.logging import logger


@dataclasses.dataclass
class TierEntry:
    """One sequence's spilled blocks: ``indices`` are BLOCK POSITIONS in
    the owning descriptor (not pool block ids — those were freed back to
    the allocator), ``shapes``/``dtypes`` describe the stacked
    pool-storage planes over those positions in index order
    ([L, nb, KV, bs, Dh] data; [L, nb, KV, bs] scales for quantized
    pools). ``planes`` holds the bytes in host RAM; ``path`` replaces it
    when the bytes live in a spill file."""

    indices: List[int]
    shapes: List[Tuple[int, ...]]
    dtypes: List[np.dtype]
    planes: Optional[List[np.ndarray]]
    path: Optional[str]
    nbytes: int


@locked_by("_mu", "_entries", "_staged", "_slots", "_free_slots",
           "_next_slot", "spills", "fetches", "prefetches",
           "prefetch_hits", "prefetch_misses", "spilled_blocks",
           "host_bytes")
class HostKVTier:
    """Host-side store of spilled KV blocks, keyed by sequence uid.

    ``store`` / ``load`` / ``drop`` are the engine's spill/fetch halves;
    ``prefetch`` stages a uid's bytes into pinned buffers ahead of its
    fetch (a fetch that finds its staging ready is a *prefetch hit* —
    the scheduler's ``kv_tier/hit_rate``)."""

    _next_tier_id = itertools.count()

    def __init__(self, spill_dir: Optional[str] = None,
                 prefetch_depth: int = 1):
        from ..ops.native.aio import get_buffer_pool

        self.pool = get_buffer_pool()
        self._tid = next(HostKVTier._next_tier_id)
        # rank 20 (utils.invariants.LOCK_ORDER): the tier is a transfer-
        # substrate leaf — nothing else is acquired while holding it
        self._mu = sanitizer.wrap(threading.Lock(), "HostKVTier._mu")
        self.spill_dir = spill_dir
        self.prefetch_depth = int(prefetch_depth)
        self._entries: Dict[int, TierEntry] = {}
        # uid -> pinned staging views of the entry's planes (prefetch
        # output; consumed — or invalidated — by the next store/drop)
        self._staged: Dict[int, List[np.ndarray]] = {}
        # pinned stagings are keyed by a RECYCLED slot id, never by uid:
        # uids grow without bound over a serving process's life, and the
        # PinnedBufferPool caches per key forever — uid keys would pin
        # one staging's worth of host memory per request served under
        # pressure. _slots maps uid -> its slot (reserved at prefetch
        # start, so an in-flight copy is never evicted into); a slot
        # recycles when its staging is evicted/consumed, or — when a
        # store/drop cancels an in-flight prefetch — by that prefetch's
        # own failed commit (its copy has finished by then, so the slot's
        # buffers are quiescent before anyone reuses them).
        self._slots: Dict[int, int] = {}
        self._free_slots: List[int] = []
        self._next_slot = 0
        # counters (the scheduler's kv_tier/* group reads these)
        self.spills = 0            # store() calls (spill events)
        self.fetches = 0           # load() calls on the fetch path
        self.prefetches = 0
        self.prefetch_hits = 0     # fetches served from staged buffers
        self.prefetch_misses = 0   # fetches that had to assemble cold
        self.spilled_blocks = 0    # CURRENT blocks resident in the tier
        self.host_bytes = 0        # CURRENT bytes resident in the tier

    # -- introspection -------------------------------------------------

    def spilled(self, uid: int) -> List[int]:
        """Block positions of ``uid`` currently in the tier ([] = none)."""
        with self._mu:
            e = self._entries.get(uid)
            return list(e.indices) if e is not None else []

    def uids(self) -> List[int]:
        with self._mu:
            return list(self._entries)

    @property
    def hit_rate(self) -> Optional[float]:
        done = self.prefetch_hits + self.prefetch_misses
        return (self.prefetch_hits / done) if done else None

    # -- storage -------------------------------------------------------

    _next_gen = itertools.count()

    def _spill_path(self, uid: int) -> str:
        # generation-suffixed so a merge WRITES its new file before the
        # old entry (and file) is replaced — a failed merged write must
        # leave the previous spill readable, never half-replaced
        return os.path.join(
            self.spill_dir,
            f"kvtier_{self._tid}_{uid}_{next(HostKVTier._next_gen)}.bin")

    def _read_planes(self, e: TierEntry) -> List[np.ndarray]:
        """The entry's planes as host arrays (file entries read back
        through the AIO engine — byte-identical to what was written)."""
        if e.planes is not None:
            return e.planes
        from ..ops.native.aio import get_io_engine

        io = get_io_engine()
        out, reqs, off = [], [], 0
        for shape, dtype in zip(e.shapes, e.dtypes):
            arr = np.empty(shape, dtype)
            reqs.append(io.submit_read(e.path, arr, offset=off))
            off += arr.nbytes
            out.append(arr)
        for r in reqs:
            io.wait(r)
        return out

    def store(self, uid: int, indices: Sequence[int],
              planes: Sequence[np.ndarray]) -> None:
        """Record ``uid``'s blocks at descriptor positions ``indices``
        with their pool-storage ``planes`` (host copies the caller just
        gathered). A second spill of the same uid MERGES (positions must
        be disjoint), so incremental cold-prefix spills compose. With
        ``spill_dir``, bytes go to a generation-suffixed file through
        the AIO engine and the RAM copy is dropped; a failed write
        deletes the partial file and leaves the tier unchanged — on the
        merge path the OLD entry (and its file) survives intact until
        the merged bytes are fully written, so no previously spilled KV
        is ever lost to a failed re-spill."""
        indices = [int(i) for i in indices]
        planes = [np.ascontiguousarray(p) for p in planes]
        with self._mu:
            old = self._entries.get(uid)
        if old is not None:
            overlap = set(old.indices) & set(indices)
            if overlap:
                raise ValueError(
                    f"kv_tier: uid {uid} re-spills positions "
                    f"{sorted(overlap)} already in the tier")
            old_planes = self._read_planes(old)
            order = np.argsort(np.asarray(old.indices + indices),
                               kind="stable")
            planes = [np.ascontiguousarray(
                np.concatenate([op, p], axis=1)[:, order])
                for op, p in zip(old_planes, planes)]
            indices = sorted(old.indices + indices)
        nbytes = sum(p.nbytes for p in planes)
        shapes = [tuple(p.shape) for p in planes]
        dtypes = [p.dtype for p in planes]
        path = None
        if self.spill_dir is not None:
            from ..ops.native.aio import get_io_engine

            path = self._spill_path(uid)
            io = get_io_engine()
            try:
                off, reqs = 0, []
                for p in planes:
                    reqs.append(io.submit_write(path, p, offset=off))
                    off += p.nbytes
                for r in reqs:
                    io.wait(r)
            except BaseException:
                try:
                    os.remove(path)
                except OSError:
                    pass
                raise
        entry = TierEntry(indices=indices, shapes=shapes, dtypes=dtypes,
                          planes=None if path is not None else planes,
                          path=path, nbytes=nbytes)
        with self._mu:
            # refuse when a concurrent store/drop raced the merge read —
            # never clobber state the merge never saw
            raced = self._entries.get(uid) is not old
            if not raced:
                self._entries[uid] = entry
                self._release_staging(uid)   # stale staging, if any
                self.spills += 1
                self.spilled_blocks += len(indices) - (
                    len(old.indices) if old is not None else 0)
                self.host_bytes += nbytes - (old.nbytes if old is not None
                                             else 0)
        if raced:
            if path is not None:
                try:
                    os.remove(path)
                except OSError:
                    pass
            raise RuntimeError(
                f"kv_tier: uid {uid} mutated concurrently with a store "
                f"— spill calls must be serialized per uid")
        if old is not None and old.path is not None:
            try:
                os.remove(old.path)
            except OSError:
                pass

    def prefetch(self, uid: int) -> bool:
        """Stage ``uid``'s spilled bytes into pinned buffers ahead of the
        fetch (the double-buffer half: file read / RAM copy runs here, off
        the fetch critical path). Bounded by ``prefetch_depth`` staged
        uids — the oldest staging is evicted past it. Returns True when a
        staging now exists (already-staged uids are a cheap no-op)."""
        with self._mu:
            e = self._entries.get(uid)
            if e is None:
                return False
            if uid in self._staged:
                return True
            if uid in self._slots:
                return False   # another prefetch of this uid in flight
            # evict committed stagings past the depth bound (oldest
            # first — no in-flight copy targets an evicted slot, since
            # in-flight uids are in _slots but never in _staged yet)
            while len(self._staged) >= max(1, self.prefetch_depth):
                evicted = next(iter(self._staged))
                self._staged.pop(evicted)
                self._free_slots.append(self._slots.pop(evicted))
            if self._free_slots:
                slot = self._free_slots.pop()
            else:
                slot = self._next_slot
                self._next_slot += 1
            self._slots[uid] = slot
        try:
            planes = self._read_planes(e)
            staged = []
            for i, p in enumerate(planes):
                buf = self.pool.staging(("kv_tier", self._tid, slot, i),
                                        p.shape, p.dtype)
                np.copyto(buf, p)
                staged.append(buf)
        except Exception as exc:
            # prefetch is pure optimization — a failed read/copy must not
            # crash the tick that requested it, and the reservation must
            # recycle or this uid could never be staged again (and the
            # slot's staging keys would leak in the pinned pool). The
            # slot recycles UNCONDITIONALLY (same as the stale-commit
            # path below): a concurrent store/drop pops an uncommitted
            # reservation without freeing it, expecting exactly this
            # cleanup to return the slot id
            with self._mu:
                if self._slots.get(uid) == slot:
                    del self._slots[uid]
                self._free_slots.append(slot)
            logger.warning(
                f"kv_tier: prefetch of uid {uid} failed ({exc!r}) — "
                f"fetch will assemble cold")
            return False
        with self._mu:
            if self._entries.get(uid) is not e or \
                    self._slots.get(uid) != slot:
                # raced a store/drop; the staging is stale — recycle the
                # reservation (the copy above has finished, so the
                # slot's buffers are quiescent before reuse)
                if self._slots.get(uid) == slot:
                    del self._slots[uid]
                self._free_slots.append(slot)
                return False
            self._staged[uid] = staged
            self.prefetches += 1
        return True

    def load(self, uid: int,
             count: bool = True) -> Tuple[List[int], List[np.ndarray]]:
        """(indices, planes) for the fetch path — NON-destructive (the
        engine drops the entry only after the device scatter committed,
        so a crashed fetch leaves the tier byte-identically intact).
        Served from the prefetch staging when present (hit), assembled
        cold otherwise (miss). ``count=False`` reads without touching
        the fetch/hit counters (the export path — a failover migration
        reading spilled bytes is not a decode-window fetch)."""
        with self._mu:
            e = self._entries.get(uid)
            if e is None:
                raise KeyError(f"kv_tier: uid {uid} has no spilled blocks")
            # the export path (count=False) runs on the failover thread;
            # the staged pinned buffers belong to the tick thread, whose
            # next prefetch eviction recycles their slot and copytos
            # ANOTHER sequence's bytes into them mid-read — exports
            # assemble from the entry's own host bytes instead of
            # borrowing live staging views
            staged = self._staged.get(uid) if count else None
            if count:
                self.fetches += 1
                if staged is not None:
                    self.prefetch_hits += 1
                else:
                    self.prefetch_misses += 1
        if staged is not None:
            return list(e.indices), staged
        return list(e.indices), self._read_planes(e)

    @requires_lock("_mu")
    def _release_staging(self, uid: int) -> None:
        """Under ``_mu``: forget ``uid``'s staging. A COMMITTED staging's
        slot recycles immediately; an in-flight prefetch (slot reserved
        but not yet committed) recycles its own slot when its commit
        check fails — never here, while its copy may still be writing."""
        committed = self._staged.pop(uid, None) is not None
        slot = self._slots.pop(uid, None)
        if committed and slot is not None:
            self._free_slots.append(slot)

    def drop(self, uid: int) -> None:
        """Forget ``uid``'s tier state (fetch committed, or the sequence
        flushed). Deletes the spill file; safe for unknown uids."""
        with self._mu:
            e = self._entries.pop(uid, None)
            self._release_staging(uid)
            if e is not None:
                self.spilled_blocks -= len(e.indices)
                self.host_bytes -= e.nbytes
        if e is not None and e.path is not None:
            try:
                os.remove(e.path)
            except OSError:
                pass

    def reset_counters(self) -> None:
        """Zero the traffic counters (spills/fetches/prefetch hits and
        misses) without touching resident entries — a measurement epoch
        (e.g. a measured pass after its warm pass) starts from a clean
        count."""
        with self._mu:
            self.spills = self.fetches = self.prefetches = 0
            self.prefetch_hits = self.prefetch_misses = 0

    def stats(self) -> Dict[str, object]:
        with self._mu:
            return {
                "spills": self.spills,
                "fetches": self.fetches,
                "prefetches": self.prefetches,
                "prefetch_hits": self.prefetch_hits,
                "prefetch_misses": self.prefetch_misses,
                "hit_rate": self.hit_rate,
                "spilled_blocks": self.spilled_blocks,
                "host_bytes": self.host_bytes,
                "spilled_uids": len(self._entries),
                "spill_dir": self.spill_dir,
            }
