"""Paged multi-tenant LoRA adapter pool (ISSUE 18).

Serving many tenants means per-tenant fine-tunes, and the hybrid-engine
answer — fuse ONE adapter into the base weights
(``linear/optimized_linear.py``, SURVEY §2.3) — serializes the fleet per
tenant. This module is the S-LoRA/Punica-shaped alternative: a fixed-slot
HBM pool of rank-padded LoRA factor pairs that a mixed-adapter batch
gathers from *per row* inside the existing one-dispatch serving step
(``ops/lora_gemm.lora_delta``). Slot indices are data riding the
sequence descriptors; the pool's device arrays are ordinary jitted-step
operands whose shapes never depend on which adapters are loaded — a
warmed server admits brand-new adapter ids with zero recompiles.

Pool discipline is the host KV tier's (``kv_tier.py``), applied to
adapters instead of KV blocks:

- **Slot 0 is the reserved all-zeros null adapter** — no-adapter rows
  gather it and add an exact ``0.0`` (the scratch-block idiom of the
  paged KV cache, applied to weights). Device slot count is config
  ``slots`` + 1.
- **Content-keyed** like the prefix cache: registration digests the raw
  factors; re-registering identical bytes is a no-op, changed bytes
  bump the adapter's version (and rewrite its slot in place when
  resident) — the RLHF ``publish_adapter`` loop rides this.
- **Refcounted residency + LRU paging**: ``acquire`` pins an adapter's
  slot for a running sequence; a miss evicts the least-recently-used
  refs==0 slot; when every slot is pinned the pool is DRY and the
  scheduler *parks* the request (``AdapterPoolDry``) — park, never
  preempt, the kv_tier admission stance.
- **Double-buffered prefetch** through the pinned ``PinnedBufferPool``
  (recycled stage ids, never adapter-id keys), so a predicted fetch's
  critical path is only the host→HBM copy of pre-staged pinned bytes.
- **Scaling folded at registration**: stored B is ``B * (alpha / r)``
  and ranks are zero-padded to ``max_rank``, so runtime needs no
  per-adapter scaling operand and padded columns contribute exactly 0.

Threading: touched from replica scheduler threads and the fleet publish
path, so all mutable state rides ``AdapterPool._mu`` — rank 20 in
``utils.invariants.LOCK_ORDER``, a transfer-substrate leaf like
``HostKVTier._mu`` (device installs run under it; they acquire nothing).

Fault site: ``adapter_fetch`` fires at the top of a miss-path acquire,
BEFORE any pool mutation — a crashed fetch leaves residency, refcounts,
and device slots exactly as they were (the chaos drill's replay relies
on it).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..testing import faults, sanitizer
from ..utils.invariants import locked_by, requires_lock

NULL_SLOT = 0

# attention projections the pool serves; FFN adapters are out of scope
# (the serving delta seam lives in the engine's attention layer body)
SUPPORTED_TARGETS = ("wq", "wk", "wv", "wo")


class AdapterPoolDry(RuntimeError):
    """Every pool slot is pinned by a running sequence — the scheduler
    parks the requesting sequence until a release frees a slot."""


def target_dims(tcfg, target: str) -> Tuple[int, int]:
    """(d_in, d_out) of one attention projection — the base matmul the
    adapter delta parallels."""
    q_dim = tcfg.n_heads * tcfg.head_dim
    kv_dim = tcfg.kv_heads * tcfg.head_dim
    return {
        "wq": (tcfg.d_model, q_dim),
        "wk": (tcfg.d_model, kv_dim),
        "wv": (tcfg.d_model, kv_dim),
        "wo": (q_dim, tcfg.d_model),
    }[target]


def pool_bytes(tcfg, slots: int, max_rank: int,
               targets: Sequence[str] = SUPPORTED_TARGETS,
               bytes_per_elem: int = 4) -> int:
    """Static HBM footprint of a pool geometry (slots incl. the null
    slot x padded-rank factor pairs over all layers/targets) — the
    autotuner's pruned_static feasibility check, computed without
    building a pool."""
    total = 0
    for t in targets:
        din, dout = target_dims(tcfg, t)
        total += tcfg.n_layers * (slots + 1) * max_rank * (din + dout)
    return total * bytes_per_elem


@dataclasses.dataclass
class _Resident:
    """One occupied device slot: which adapter, how many running
    sequences pin it, and which content version is installed."""

    adapter_id: str
    slot: int
    refs: int
    version: int


@locked_by("_mu", "_host", "_resident", "_slot_owner", "_free_slots",
           "_staged", "_stage_ids", "_free_stages", "_next_stage",
           "hits", "misses", "evictions", "installs", "prefetches",
           "prefetch_hits", "prefetch_misses", "a", "b")
class AdapterPool:
    """Fixed-slot device pool of padded LoRA factor pairs.

    Device layout (per target ``t``): ``a[t]`` is [L, S, d_in, R] and
    ``b[t]`` is [L, S, R, d_out] with S = ``slots`` + 1 and R =
    ``max_rank`` — leading L so the pair joins the engine's layer-scan
    ``xs`` and each layer body sees its own [S, d_in, R] stack."""

    _next_pool_id = itertools.count()

    def __init__(self, tcfg, slots: int, max_rank: int,
                 targets: Sequence[str] = SUPPORTED_TARGETS,
                 prefetch_depth: int = 1, dtype=None):
        import jax.numpy as jnp

        from ..ops.native.aio import get_buffer_pool

        for t in targets:
            if t not in SUPPORTED_TARGETS:
                raise ValueError(
                    f"adapters: unsupported target {t!r} "
                    f"(supported: {SUPPORTED_TARGETS})")
        if slots < 1:
            raise ValueError("adapters: slots must be >= 1")
        if max_rank < 1:
            raise ValueError("adapters: max_rank must be >= 1")
        self.tcfg = tcfg
        self.slots = int(slots)
        self.max_rank = int(max_rank)
        self.targets = tuple(targets)
        self.prefetch_depth = int(prefetch_depth)
        self.dtype = dtype or jnp.float32
        self.pool = get_buffer_pool()
        self._pid = next(AdapterPool._next_pool_id)
        # rank 20 (utils.invariants.LOCK_ORDER): transfer-substrate leaf
        # — device installs run under it but acquire no further locks
        self._mu = sanitizer.wrap(threading.Lock(), "AdapterPool._mu")
        L, S, R = tcfg.n_layers, self.slots + 1, self.max_rank
        self.a: Dict[str, object] = {}
        self.b: Dict[str, object] = {}
        for t in self.targets:
            din, dout = target_dims(tcfg, t)
            self.a[t] = jnp.zeros((L, S, din, R), self.dtype)
            self.b[t] = jnp.zeros((L, S, R, dout), self.dtype)
        # aid -> {target: (A_pad [L,din,R], B_pad [L,R,dout])} host copies
        # (numpy; the paged backing store the device slots fetch from)
        self._host: Dict[str, Dict[str, Tuple[np.ndarray, np.ndarray]]] = {}
        self._digest: Dict[str, str] = {}
        self._version: Dict[str, int] = {}
        # residency: insertion order of _resident IS the LRU order
        # (acquire-hit re-inserts — the dict is the recency list)
        self._resident: Dict[str, _Resident] = {}
        self._slot_owner: Dict[int, str] = {}
        self._free_slots: List[int] = list(range(1, S))
        # prefetch staging: recycled stage ids keyed into the pinned
        # pool (never adapter ids — the pool caches per key forever,
        # kv_tier's recycled-slot rationale)
        self._staged: Dict[str, List[np.ndarray]] = {}
        self._stage_ids: Dict[str, int] = {}
        self._free_stages: List[int] = []
        self._next_stage = 0
        # counters (the scheduler's adapter/* group reads these)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.installs = 0
        self.prefetches = 0
        self.prefetch_hits = 0
        self.prefetch_misses = 0

    # -- registration (content-keyed) ----------------------------------

    def _pad_factors(self, factors, alpha) -> Dict[
            str, Tuple[np.ndarray, np.ndarray]]:
        """Validate + normalize ``{target: (A, B)}`` (2-D per-layer-tied
        or 3-D [L, ...] factors) into padded [L, din, R] / [L, R, dout]
        host planes with alpha/r folded into B."""
        L, R = self.tcfg.n_layers, self.max_rank
        out = {}
        for t, (A, B) in factors.items():
            if t not in self.targets:
                raise ValueError(
                    f"adapters: target {t!r} not in pool targets "
                    f"{self.targets}")
            A = np.asarray(A)
            B = np.asarray(B)
            if A.ndim == 2:
                A = np.broadcast_to(A, (L,) + A.shape)
            if B.ndim == 2:
                B = np.broadcast_to(B, (L,) + B.shape)
            din, dout = target_dims(self.tcfg, t)
            r = A.shape[-1]
            if A.shape != (L, din, r) or B.shape != (L, r, dout):
                raise ValueError(
                    f"adapters: {t} factors have shapes {A.shape}/"
                    f"{B.shape}, want [L={L}, {din}, r]/[L, r, {dout}]")
            if r > R:
                raise ValueError(
                    f"adapters: {t} rank {r} exceeds pool max_rank {R}")
            scale = (alpha / r) if alpha is not None else 1.0
            A_pad = np.zeros((L, din, R), np.float32)
            B_pad = np.zeros((L, R, dout), np.float32)
            A_pad[:, :, :r] = A
            B_pad[:, :r, :] = B * scale   # padded rows of B stay 0 —
            out[t] = (A_pad, B_pad)       # delta is exactly unchanged
        return out

    def register(self, adapter_id: str, factors, alpha=None,
                 version: Optional[int] = None) -> int:
        """Make ``adapter_id`` known to the pool (host side; residency is
        acquire's business). ``factors`` maps target -> (A, B). Content-
        keyed: identical bytes are a no-op, changed bytes bump the
        version and — when the adapter is resident — rewrite its device
        slot in place so running sequences pick up the new factors next
        step (the publish_adapter semantics). Returns the version."""
        if not adapter_id:
            raise ValueError("adapters: adapter_id must be non-empty")
        padded = self._pad_factors(factors, alpha)
        h = hashlib.blake2b(digest_size=16)
        for t in sorted(padded):
            A_pad, B_pad = padded[t]
            h.update(t.encode())
            h.update(A_pad.tobytes())
            h.update(B_pad.tobytes())
        digest = h.hexdigest()
        with self._mu:
            if self._digest.get(adapter_id) == digest and version is None:
                return self._version[adapter_id]
            self._host[adapter_id] = padded
            self._digest[adapter_id] = digest
            self._version[adapter_id] = (
                version if version is not None
                else self._version.get(adapter_id, 0) + 1)
            self._release_staging(adapter_id)   # staged bytes are stale
            res = self._resident.get(adapter_id)
            if res is not None:
                self._install(adapter_id, res.slot)
                res.version = self._version[adapter_id]
            return self._version[adapter_id]

    def registered(self, adapter_id: str) -> bool:
        with self._mu:
            return adapter_id in self._host

    def version(self, adapter_id: str) -> Optional[int]:
        with self._mu:
            return self._version.get(adapter_id)

    # -- residency -----------------------------------------------------

    @requires_lock("_mu")
    def _install(self, adapter_id: str, slot: int,
                 staged: Optional[List[np.ndarray]] = None) -> None:
        """Write ``adapter_id``'s padded planes into device slot
        ``slot`` (from the prefetch staging when provided)."""
        planes = staged
        if planes is None:
            planes = []
            for t in self.targets:
                pair = self._host[adapter_id].get(t)
                if pair is None:
                    L, R = self.tcfg.n_layers, self.max_rank
                    din, dout = target_dims(self.tcfg, t)
                    pair = (np.zeros((L, din, R), np.float32),
                            np.zeros((L, R, dout), np.float32))
                planes.extend(pair)
        it = iter(planes)
        for t in self.targets:
            A_pad, B_pad = next(it), next(it)
            self.a[t] = self.a[t].at[:, slot].set(
                A_pad.astype(self.a[t].dtype))
            self.b[t] = self.b[t].at[:, slot].set(
                B_pad.astype(self.b[t].dtype))
        self.installs += 1

    def acquire(self, adapter_id: str) -> int:
        """Pin ``adapter_id`` resident and return its device slot.

        Hit: bump the refcount and recency. Miss: take a free slot, else
        evict the LRU refs==0 resident; when every slot is pinned raise
        :class:`AdapterPoolDry` (the caller parks — nothing was
        mutated). The fault site fires before any mutation for the same
        atomicity: a crashed fetch changes nothing."""
        with self._mu:
            if adapter_id not in self._host:
                raise KeyError(
                    f"adapters: {adapter_id!r} is not registered")
            res = self._resident.get(adapter_id)
            if res is not None:
                self.hits += 1
                res.refs += 1
                self._resident.pop(adapter_id)      # refresh recency
                self._resident[adapter_id] = res
                return res.slot
            # miss path — pick the victim/free slot, then crash-test,
            # then mutate (atomic-on-reject AND atomic-on-crash)
            victim = None
            if not self._free_slots:
                for aid, r in self._resident.items():   # LRU first
                    if r.refs == 0:
                        victim = aid
                        break
                if victim is None:
                    raise AdapterPoolDry(
                        f"adapters: all {self.slots} slots pinned "
                        f"({sorted(self._resident)}) — cannot load "
                        f"{adapter_id!r}")
            if faults.ACTIVE:
                faults.maybe_crash("adapter_fetch", 0)
            self.misses += 1
            if victim is not None:
                gone = self._resident.pop(victim)
                self._slot_owner.pop(gone.slot)
                self._free_slots.append(gone.slot)
                self.evictions += 1
            slot = self._free_slots.pop()
            staged = self._staged.get(adapter_id)
            if staged is not None:
                self.prefetch_hits += 1
            else:
                self.prefetch_misses += 1
            self._install(adapter_id, slot, staged=staged)
            self._release_staging(adapter_id)       # consumed
            self._resident[adapter_id] = _Resident(
                adapter_id=adapter_id, slot=slot, refs=1,
                version=self._version[adapter_id])
            self._slot_owner[slot] = adapter_id
            return slot

    def release(self, adapter_id: str) -> None:
        """Unpin one reference. The adapter STAYS resident at refs==0 —
        warm for re-acquire and for placement affinity — until LRU
        eviction reclaims its slot."""
        with self._mu:
            res = self._resident.get(adapter_id)
            if res is None or res.refs <= 0:
                raise RuntimeError(
                    f"adapters: release of {adapter_id!r} without a "
                    f"matching acquire")
            res.refs -= 1

    def can_acquire(self, adapter_id: str) -> bool:
        """Read-only acquirability probe for ``_admission_detail`` —
        True iff an ``acquire`` now would succeed (resident, or a slot
        is free/evictable). Mutates nothing."""
        with self._mu:
            if adapter_id not in self._host:
                return False
            if adapter_id in self._resident or self._free_slots:
                return True
            return any(r.refs == 0 for r in self._resident.values())

    def can_acquire_all(self, adapter_ids) -> Tuple[bool, str]:
        """Batch acquirability probe: would pinning ALL of ``adapter_ids``
        (with duplicates collapsed) succeed right now? Batch-aware where
        per-id :meth:`can_acquire` is not — refs==0 residents the batch
        itself re-acquires are NOT counted evictable, so a mixed batch
        cannot pass by planning to evict its own hits. Mutates nothing;
        ``(ok, why)`` with ``why`` naming the dry pool on refusal."""
        with self._mu:
            batch = {a for a in adapter_ids if a is not None}
            for aid in batch:
                if aid not in self._host:
                    return False, f"adapter {aid!r} is not registered"
            need = {a for a in batch if a not in self._resident}
            evictable = sum(1 for aid, r in self._resident.items()
                            if r.refs == 0 and aid not in batch)
            cap = len(self._free_slots) + evictable
            if len(need) > cap:
                return False, (
                    f"adapter pool dry: batch needs {len(need)} new "
                    f"slot(s) for {sorted(need)} but only {cap} of "
                    f"{self.slots} are free or evictable")
            return True, ""

    def slot_of(self, adapter_id: str) -> Optional[int]:
        with self._mu:
            res = self._resident.get(adapter_id)
            return res.slot if res is not None else None

    def resident_ids(self) -> List[str]:
        """Resident adapter ids, LRU-oldest first (the placement
        affinity signal ``load_report`` ships)."""
        with self._mu:
            return list(self._resident)

    # -- prefetch ------------------------------------------------------

    def prefetch(self, adapter_id: str) -> bool:
        """Stage ``adapter_id``'s padded planes into pinned buffers so
        the eventual acquire-miss install copies from pinned host memory
        (kv_tier's double-buffer half). Depth-bounded; True when a
        staging now exists."""
        with self._mu:
            if adapter_id not in self._host or \
                    adapter_id in self._resident:
                return False
            if adapter_id in self._staged:
                return True
            while len(self._staged) >= max(1, self.prefetch_depth):
                evicted = next(iter(self._staged))
                self._staged.pop(evicted)
                self._free_stages.append(self._stage_ids.pop(evicted))
            if self._free_stages:
                stage = self._free_stages.pop()
            else:
                stage = self._next_stage
                self._next_stage += 1
            staged = []
            i = 0
            for t in self.targets:
                pair = self._host[adapter_id].get(t)
                if pair is None:
                    L, R = self.tcfg.n_layers, self.max_rank
                    din, dout = target_dims(self.tcfg, t)
                    pair = (np.zeros((L, din, R), np.float32),
                            np.zeros((L, R, dout), np.float32))
                for p in pair:
                    buf = self.pool.staging(
                        ("adapter", self._pid, stage, i), p.shape,
                        p.dtype)
                    np.copyto(buf, p)
                    staged.append(buf)
                    i += 1
            self._staged[adapter_id] = staged
            self._stage_ids[adapter_id] = stage
            self.prefetches += 1
            return True

    @requires_lock("_mu")
    def _release_staging(self, adapter_id: str) -> None:
        committed = self._staged.pop(adapter_id, None) is not None
        stage = self._stage_ids.pop(adapter_id, None)
        if committed and stage is not None:
            self._free_stages.append(stage)

    # -- engine operands -----------------------------------------------

    def device_operands(self):
        """The layer-scan xs contribution: per-target (A-stack, B-stack)
        device arrays with leading L. Snapshot under the lock — a
        concurrent publish swaps whole arrays, never mutates in place."""
        with self._mu:
            return {"a": dict(self.a), "b": dict(self.b)}

    # -- observability -------------------------------------------------

    def reset_counters(self) -> None:
        with self._mu:
            self.hits = self.misses = self.evictions = 0
            self.installs = self.prefetches = 0
            self.prefetch_hits = self.prefetch_misses = 0

    def stats(self) -> Dict[str, object]:
        with self._mu:
            return {
                "slots": self.slots,
                "resident": len(self._resident),
                "pinned": sum(1 for r in self._resident.values()
                              if r.refs > 0),
                "registered": len(self._host),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "installs": self.installs,
                "prefetches": self.prefetches,
                "prefetch_hits": self.prefetch_hits,
                "prefetch_misses": self.prefetch_misses,
            }
