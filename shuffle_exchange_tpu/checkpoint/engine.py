"""Checkpoint engines.

Capability parity with the reference's pluggable checkpoint stack
(SURVEY.md §5.4): the ``CheckpointEngine`` ABC
(``runtime/checkpoint_engine/checkpoint_engine.py:21``), the default Torch
engine, the async **Fast**/**Decoupled** writers (``io/fast_file_writer.py:44``,
``decoupled_checkpoint_engine.py:68``), tag files (``latest``), and
cross-topology resume (universal checkpoints, §5.4 — sharding-aware restore
makes regridding native here: Orbax records per-array metadata and restores
into whatever NamedShardings the new topology asks for).

Atomicity contract (the resilience layer depends on it): every engine writes
each item into a ``<path>.tmp-<nonce>`` staging directory and rename-commits
it at ``commit()`` — a crash at ANY point during a save leaves the previous
committed checkpoint untouched. The native manifest carries per-shard
checksum + byte-length fields that are verified on load (a corrupted shard is
rejected with an error naming the leaf and file), and the tag helpers expose
``resolve_tag_candidates`` so loaders can fall back to the newest *complete*
tag when the ``latest`` pointer is torn or the tag it names fails checksum.

Engines:
- ``OrbaxCheckpointEngine`` — sharding-aware, optionally async.
- ``NativeCheckpointEngine`` — fast/decoupled writer over the csrc async IO
  engine (raw shard files + manifest; background writes until ``commit()``).
- ``MockCheckpointEngine`` — the test seam (reference io/mock_file_writer.py).
"""

from __future__ import annotations

import json
import os
import re
import shutil
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils.logging import logger

LATEST_FILE = "latest"
STAGING_MARKER = ".tmp-"
_ASIDE_MARKER = ".old-"


class CheckpointCorruption(RuntimeError):
    """A checkpoint failed an integrity check (torn write, bad checksum,
    missing manifest). Loaders treat this as recoverable: fall back to an
    earlier committed tag."""


#: Exceptions a loader may recover from by falling back to an earlier tag.
RECOVERABLE_ERRORS = (FileNotFoundError, CheckpointCorruption,
                      json.JSONDecodeError, EOFError)


# ----------------------------------------------------------------------
# Checksums (native manifest integrity)
# ----------------------------------------------------------------------

try:  # hardware CRC-32C when a binding is present; never a hard dependency
    from crc32c import crc32c as _crc32c_fn  # type: ignore

    CHECKSUM_ALGO = "crc32c"
except Exception:  # pragma: no cover - environment dependent
    try:
        from google_crc32c import value as _crc32c_fn  # type: ignore

        CHECKSUM_ALGO = "crc32c"
    except Exception:
        _crc32c_fn = None
        CHECKSUM_ALGO = "crc32"


def _crc32c(view: memoryview) -> int:
    # both bindings take buffer-protocol objects; never copy a multi-GB
    # shard through bytes() just to checksum it
    try:
        return int(_crc32c_fn(view))
    except TypeError:  # pragma: no cover - binding-version dependent
        return int(_crc32c_fn(bytes(view)))


def checksum_bytes(buf) -> int:
    """Checksum of a buffer under ``CHECKSUM_ALGO`` (crc32c when a C binding
    is importable, zlib crc32 otherwise — the manifest records which)."""
    view = memoryview(buf).cast("B")
    if _crc32c_fn is not None:
        return _crc32c(view)
    return zlib.crc32(view) & 0xFFFFFFFF


def _verify_checksum(buf, expected: int, algo: str) -> bool:
    view = memoryview(buf).cast("B")
    if algo == "crc32":
        return (zlib.crc32(view) & 0xFFFFFFFF) == expected
    if algo == "crc32c":
        if _crc32c_fn is None:
            logger.warning("manifest records crc32c but no crc32c binding is "
                           "available; skipping checksum verification")
            return True
        return _crc32c(view) == expected
    logger.warning(f"unknown checksum algo {algo!r}; skipping verification")
    return True


# ----------------------------------------------------------------------
# Atomic rename plumbing
# ----------------------------------------------------------------------


def _fsync_dir(path: str) -> None:
    """Best-effort durability for a directory's entries (rename/replace)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def staging_path(path: str) -> str:
    """Deterministic staging sibling for ``path``: every process of a
    multi-host job computes the same name (the nonce is a digest of the
    final path), and the saver clears it before reuse — stale bytes from a
    crashed earlier attempt never leak into a commit."""
    path = os.path.abspath(path)
    nonce = zlib.crc32(path.encode()) & 0xFFFFFFFF
    return f"{path}{STAGING_MARKER}{nonce:08x}"


def is_staging_name(name: str) -> bool:
    return STAGING_MARKER in name or _ASIDE_MARKER in name


def commit_staged(tmp: str, final: str) -> None:
    """Rename-commit ``tmp`` over ``final``. If ``final`` exists it is moved
    aside first and deleted only AFTER the new version is in place — at no
    point is the only good copy gone."""
    parent = os.path.dirname(os.path.abspath(final))
    aside = None
    if os.path.exists(final):
        aside = f"{final}{_ASIDE_MARKER}{os.path.basename(tmp).split(STAGING_MARKER)[-1]}"
        if os.path.exists(aside):
            shutil.rmtree(aside, ignore_errors=True)
        os.rename(final, aside)
    os.rename(tmp, final)
    _fsync_dir(parent)
    if aside is not None:
        shutil.rmtree(aside, ignore_errors=True)


class CheckpointEngine:
    """ABC (reference checkpoint_engine.py:21: create/save/load/commit)."""

    def create(self, tag: str) -> None: ...

    def save(self, state: Any, path: str) -> None:
        raise NotImplementedError

    def load(self, path: str, target: Any = None, shardings: Any = None) -> Any:
        raise NotImplementedError

    def commit(self, tag: str) -> bool:
        return True


class OrbaxCheckpointEngine(CheckpointEngine):
    def __init__(self, use_async: bool = False):
        import orbax.checkpoint as ocp

        self.use_async = use_async
        self._ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler()) if use_async \
            else ocp.Checkpointer(ocp.StandardCheckpointHandler())
        self._pending_commits: List[Tuple[str, str]] = []

    def save(self, state: Any, path: str) -> None:
        import jax
        import orbax.checkpoint as ocp

        path = os.path.abspath(path)
        tmp = staging_path(path)
        if jax.process_index() == 0 and os.path.exists(tmp):
            shutil.rmtree(tmp)
        self._ckptr.save(tmp, args=ocp.args.StandardSave(state))
        if self.use_async:
            # writes are still in flight; the rename lands at commit()
            self._pending_commits.append((tmp, path))
        elif jax.process_index() == 0:
            # orbax's sync save is internally multihost-synchronized, so
            # every process has finished writing; one process renames
            commit_staged(tmp, path)

    def load(self, path: str, target: Any = None, shardings: Any = None) -> Any:
        import jax
        import orbax.checkpoint as ocp

        path = os.path.abspath(path)
        if not os.path.isdir(path):
            raise FileNotFoundError(f"no checkpoint item at {path}")
        if target is None:
            # Host-side restore (consolidation CLI, single-process tools):
            # the checkpoint may have been written from any device layout, so
            # rebuild an abstract target from metadata placed on the HOST
            # (the CPU backend exists beside any accelerator) instead of
            # replaying the original sharding: a whole checkpoint piled on
            # accelerator 0 need not fit there, and a host-side tool has
            # no business holding a chip.
            # orbax-API drift: Checkpointer.metadata() returns the metadata
            # tree directly on 0.7.x; newer releases wrap it in a
            # StepMetadata whose ``item_metadata`` holds the tree
            meta = self._ckptr.metadata(path)
            meta = getattr(meta, "item_metadata", meta)
            sharding = jax.sharding.SingleDeviceSharding(
                jax.local_devices(backend="cpu")[0])

            def to_abstract(m):
                return jax.ShapeDtypeStruct(tuple(m.shape), m.dtype, sharding=sharding)

            abstract = jax.tree_util.tree_map(to_abstract, meta,
                                              is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
            return self._ckptr.restore(path, args=ocp.args.StandardRestore(abstract))
        abstract = jax.tree_util.tree_map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            target, shardings) if shardings is not None else jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None)), target)
        return self._ckptr.restore(path, args=ocp.args.StandardRestore(abstract))

    def commit(self, tag: str) -> bool:
        # Async path: join outstanding writes (decoupled-engine commit at
        # step boundary, reference runtime/engine.py:2431), then rename the
        # staged items into place. The sync Checkpointer already did both.
        import jax

        if hasattr(self._ckptr, "wait_until_finished"):
            self._ckptr.wait_until_finished()
        pending, self._pending_commits = self._pending_commits, []
        if not pending:
            return True
        multihost = jax.process_count() > 1
        if multihost:
            from ..parallel import comm as _comm

            _comm.barrier("orbax_ckpt_commit")
        if jax.process_index() == 0:
            for tmp, final in pending:
                commit_staged(tmp, final)
        if multihost:
            # non-zero processes must not return (and e.g. immediately load)
            # before the rename has landed
            from ..parallel import comm as _comm

            _comm.barrier("orbax_ckpt_committed")
        return True


class NativeCheckpointEngine(CheckpointEngine):
    """Fast/decoupled writer over the native async IO engine.

    Capability parity with the reference's **Fast** checkpoint engine
    (``io/fast_file_writer.py:44`` double-buffered direct-IO writes) and the
    **Decoupled** engine (``decoupled_checkpoint_engine.py:68`` — writes
    proceed while training does; ``commit()`` at the step boundary joins).
    Layout: one ``manifest.json`` per process + one raw ``.bin`` per unique
    local shard, written through the csrc thread-pool IO engine. Loading
    assembles the global array from shard files and re-places it with the
    target's shardings — so a checkpoint written at one (dp, fsdp, tp)
    layout restores into any other (the universal-checkpoint property).

    Every shard entry records ``nbytes`` + a checksum; ``load`` verifies
    both and rejects a corrupted shard with an error naming the leaf.
    """

    def __init__(self, num_threads: int = 4, blocking: bool = False):
        from ..ops.native.aio import AsyncIOEngine

        self.io = AsyncIOEngine(num_threads=num_threads)
        self.blocking = blocking
        self._keepalive: list = []
        self._pending_commits: List[Tuple[str, str]] = []

    def _manifest_path(self, path: str) -> str:
        import jax

        return os.path.join(path, f"manifest_{jax.process_index()}.json")

    def save(self, state: Any, path: str) -> None:
        import jax

        path = os.path.abspath(path)
        tmp = staging_path(path)
        # Clear any previous staging attempt at this path: stale
        # manifests/shards from a crashed save (or a run with a different
        # process count) would be merged on load (single cleaner + barrier
        # on multi-host). The FINAL path is never deleted here — the old
        # committed checkpoint survives until the new one renames over it.
        if jax.process_index() == 0 and os.path.isdir(tmp):
            shutil.rmtree(tmp)
        if jax.process_count() > 1:
            from ..parallel import comm as _comm

            _comm.barrier("native_ckpt_clean")
        os.makedirs(tmp, exist_ok=True)
        try:
            self._save_into(state, tmp)
        except BaseException:
            # A failed/killed save must leave the IO engine quiescent: the
            # writes already submitted would otherwise still be running when
            # the engine (and its native thread pool) is torn down.
            try:
                self.io.wait_all()
            except Exception:
                pass
            self._keepalive.clear()
            raise
        self._pending_commits.append((tmp, path))
        if self.blocking:
            self.commit("")

    def _save_into(self, state: Any, tmp: str) -> None:
        import jax

        from ..testing import faults

        flat = jax.tree_util.tree_flatten_with_path(state)[0]
        manifest = {"leaves": [], "checksum_algo": CHECKSUM_ALGO}
        ordinal = 0
        for i, (keypath, leaf) in enumerate(flat):
            name = ".".join(str(getattr(e, "key", getattr(e, "idx", getattr(e, "name", "?"))))
                            for e in keypath)
            entry = {"name": name, "shards": []}

            def _submit(data: np.ndarray, fname: str, shard_index) -> None:
                nonlocal ordinal
                fpath = os.path.join(tmp, fname)
                if faults.ACTIVE:
                    faults.on_write("ckpt_shard_write", ordinal, fpath, data)
                ordinal += 1
                self.io.submit_write(fpath, data)
                self._keepalive.append(data)
                entry["shards"].append({
                    "file": fname, "index": shard_index, "shape": list(data.shape),
                    "nbytes": int(data.nbytes),
                    "crc32c": checksum_bytes(data),
                })

            if hasattr(leaf, "addressable_shards"):
                entry["global_shape"] = list(leaf.shape)
                entry["dtype"] = str(np.dtype(leaf.dtype))
                seen = set()
                for s in leaf.addressable_shards:
                    key = tuple((sl.start, sl.stop, sl.step) for sl in s.index)
                    if key in seen:
                        continue
                    seen.add(key)
                    data = np.array(s.data, order="C", copy=True)
                    fname = f"leaf{i}_shard{len(entry['shards'])}_p{jax.process_index()}.bin"
                    _submit(data, fname, [list(k) for k in key])
            else:
                data = np.array(leaf, order="C", copy=True)
                entry["global_shape"] = list(data.shape)
                entry["dtype"] = str(data.dtype)
                _submit(data, f"leaf{i}_full_p{jax.process_index()}.bin", None)
            manifest["leaves"].append(entry)
        if faults.ACTIVE:
            faults.maybe_crash("ckpt_manifest_write")
        with open(self._manifest_path(tmp), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())

    def load(self, path: str, target: Any = None, shardings: Any = None) -> Any:
        import glob as _glob

        import jax

        path = os.path.abspath(path)
        manifests = sorted(_glob.glob(os.path.join(path, "manifest_*.json")))
        if not manifests:
            raise FileNotFoundError(f"no native-checkpoint manifest under {path}")
        # Merge per-process manifests: same leaf order, union of shards.
        merged = None
        algo = "crc32"
        for mp in manifests:
            with open(mp) as f:
                m = json.load(f)   # a truncated manifest raises JSONDecodeError
            algo = m.get("checksum_algo", algo)
            if merged is None:
                merged = m
            else:
                if len(m["leaves"]) != len(merged["leaves"]):
                    raise CheckpointCorruption(
                        f"manifest {mp} lists {len(m['leaves'])} leaves but "
                        f"{manifests[0]} lists {len(merged['leaves'])} — "
                        "per-process manifests disagree (torn save?)")
                for a, b in zip(merged["leaves"], m["leaves"]):
                    a["shards"].extend(b["shards"])
        # Submit every shard read first so the IO thread pool overlaps them,
        # then wait and assemble.
        reads = []  # (leaf_idx, shard_meta, buffer, request)
        for li, entry in enumerate(merged["leaves"]):
            dtype = np.dtype(entry["dtype"])
            for sm in entry["shards"]:
                buf = np.empty(tuple(sm["shape"]), dtype=dtype)
                fpath = os.path.join(path, sm["file"])
                if "nbytes" in sm:
                    if not os.path.exists(fpath):
                        raise CheckpointCorruption(
                            f"checkpoint {path}: shard file {sm['file']} for leaf "
                            f"{entry['name']!r} is missing")
                    actual = os.path.getsize(fpath)
                    if actual != sm["nbytes"]:
                        raise CheckpointCorruption(
                            f"checkpoint {path}: shard {sm['file']} of leaf "
                            f"{entry['name']!r} is {actual} bytes, manifest "
                            f"says {sm['nbytes']} (torn write)")
                req = self.io.submit_read(fpath, buf)
                reads.append((li, sm, buf, req))
        for _, _, _, req in reads:
            self.io.wait(req)
        # Integrity: verify each shard's recorded checksum before any bytes
        # reach the model (a flipped bit restores as silent weight damage).
        for li, sm, buf, _ in reads:
            if "crc32c" in sm and not _verify_checksum(buf, sm["crc32c"], algo):
                entry = merged["leaves"][li]
                raise CheckpointCorruption(
                    f"checkpoint {path}: checksum mismatch in shard "
                    f"{sm['file']} of leaf {entry['name']!r} — the file is "
                    "corrupted")
        # Coverage check: distinct shard indices must tile the global shape —
        # a missing per-process manifest would otherwise leave np.empty
        # regions as uninitialized memory.
        import math as _math

        def _span(idx, shape, total):
            if idx is None:
                return total
            n = 1
            for (a, b, _), dim in zip(idx, shape):
                a = 0 if a is None else a
                b = dim if b is None else b   # slice(None) bounds mean the full dim
                n *= max(0, b - a)
            return n if idx else 1            # scalar leaves: empty index = 1 elem
        for entry in merged["leaves"]:
            total = _math.prod(entry["global_shape"]) if entry["global_shape"] else 1
            distinct = {tuple(map(tuple, sm["index"])) if sm["index"] is not None else None
                        for sm in entry["shards"]}
            covered = sum(_span(idx, entry["global_shape"], total) for idx in distinct)
            if covered < total:
                raise CheckpointCorruption(
                    f"checkpoint {path} is incomplete for leaf {entry['name']!r}: shards "
                    f"cover {covered}/{total} elements (missing per-process manifests?)")
        arrays = [np.empty(tuple(e["global_shape"]), dtype=np.dtype(e["dtype"]))
                  for e in merged["leaves"]]
        for li, sm, buf, _ in reads:
            if sm["index"] is None:
                arrays[li] = buf
            else:
                idx = tuple(slice(a, b, c) for a, b, c in sm["index"])
                arrays[li][idx] = buf
        if target is None:
            names = [e["name"] for e in merged["leaves"]]
            return dict(zip(names, arrays))
        flat_target, treedef = jax.tree_util.tree_flatten(target)
        if len(flat_target) != len(arrays):
            raise ValueError(f"checkpoint has {len(arrays)} leaves, target expects {len(flat_target)}")
        for entry, tleaf in zip(merged["leaves"], flat_target):
            if tuple(entry["global_shape"]) != tuple(np.shape(tleaf)):
                raise ValueError(
                    f"checkpoint leaf {entry['name']!r} has global shape "
                    f"{tuple(entry['global_shape'])} but the target expects "
                    f"{tuple(np.shape(tleaf))} — the checkpoint was written "
                    "for a different model")
        from ..utils.placement import owned_device_put

        sh_flat = (treedef.flatten_up_to(shardings) if shardings is not None
                   else [getattr(l, "sharding", None) for l in flat_target])
        # owned_device_put: restored leaves land in the engine's donated
        # TrainState — they must never alias host numpy memory, or a
        # cache-deserialized donated executable corrupts the resumed run
        # (utils/placement.py has the full story).
        placed = [owned_device_put(a.astype(np.dtype(t.dtype)), s) if s is not None else a
                  for a, t, s in zip(arrays, flat_target, sh_flat)]
        return jax.tree_util.tree_unflatten(treedef, placed)

    def commit(self, tag: str) -> bool:
        import jax

        self.io.wait_all()
        self._keepalive.clear()
        pending, self._pending_commits = self._pending_commits, []
        if not pending:
            return True
        multihost = jax.process_count() > 1
        if multihost:
            # every process must have finished writing into the staging dir
            # before the single rename happens
            from ..parallel import comm as _comm

            _comm.barrier("native_ckpt_commit")
        if jax.process_index() == 0:
            for tmp, final in pending:
                commit_staged(tmp, final)
        if multihost:
            from ..parallel import comm as _comm

            _comm.barrier("native_ckpt_committed")
        return True


class MockCheckpointEngine(CheckpointEngine):
    """In-memory store for tests (reference MockFileWriter seam)."""

    def __init__(self):
        self.store: Dict[str, Any] = {}
        self.commits = []

    def save(self, state, path):
        import jax

        self.store[path] = jax.device_get(state)

    def load(self, path, target=None, shardings=None):
        # FileNotFoundError like the real engines, so engine-level fallback
        # logic treats every writer uniformly.
        if path not in self.store:
            raise FileNotFoundError(path)
        return self.store[path]

    def commit(self, tag):
        self.commits.append(tag)
        return True


def get_checkpoint_engine(config) -> CheckpointEngine:
    """Engine selection parity (config.checkpoint.writer: torch|fast|decoupled).

    torch → Orbax (sharding-aware, optionally async); fast → native IO
    writer joining at save; decoupled → native IO writer streaming in the
    background until ``commit()``."""
    writer = config.checkpoint.writer
    if writer in ("fast", "decoupled"):
        return NativeCheckpointEngine(blocking=(writer == "fast"))
    return OrbaxCheckpointEngine(use_async=config.checkpoint.async_save)


# ----------------------------------------------------------------------
# Tag helpers (reference: `latest` file, tag validation engine.py:3326)
# ----------------------------------------------------------------------


def read_latest_tag(load_dir: str) -> Optional[str]:
    path = os.path.join(load_dir, LATEST_FILE)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        tag = f.read().strip()
    if not tag:
        # A torn/empty pointer must not resolve to load_dir itself.
        logger.warning(f"'{LATEST_FILE}' file in {load_dir} is empty or "
                       "whitespace (torn write?); treating as absent")
        return None
    return tag


def write_latest_tag(save_dir: str, tag: str) -> None:
    """Atomic pointer update: tmp + fsync + rename — a crash mid-update
    leaves the previous pointer intact, never a torn file."""
    os.makedirs(save_dir, exist_ok=True)
    final = os.path.join(save_dir, LATEST_FILE)
    tmp = f"{final}{STAGING_MARKER}{os.getpid():08x}"
    with open(tmp, "w") as f:
        f.write(tag)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, final)
    _fsync_dir(save_dir)


def tag_step(tag: str) -> Optional[int]:
    """Trailing step number of a tag name (``global_step120`` -> 120)."""
    m = re.search(r"(\d+)$", tag)
    return int(m.group(1)) if m else None


def is_complete_tag(save_dir: str, tag: str) -> bool:
    """A tag is complete iff its directory was rename-committed: it exists,
    is not a staging/aside leftover, and contains a committed model item."""
    if is_staging_name(tag):
        return False
    return os.path.isdir(os.path.join(save_dir, tag, "model"))


def list_complete_tags(save_dir: str) -> List[str]:
    """Fully-committed tags under ``save_dir``, newest first (by trailing
    step number when present, mtime as tiebreak)."""
    if not os.path.isdir(save_dir):
        return []
    out = []
    for name in os.listdir(save_dir):
        if name == LATEST_FILE or not is_complete_tag(save_dir, name):
            continue
        step = tag_step(name)
        try:
            mtime = os.stat(os.path.join(save_dir, name)).st_mtime_ns
        except OSError:
            continue
        out.append((step if step is not None else -1, mtime, name))
    out.sort(reverse=True)
    return [name for _, _, name in out]


def resolve_tag_candidates(load_dir: str, tag: Optional[str] = None) -> List[str]:
    """Ordered tags a loader should try: the requested (or ``latest``) tag
    first, then every other complete tag newest-first. An explicitly given
    ``tag`` is returned alone — the caller asked for that one, falling back
    silently would mask the problem."""
    if tag is not None:
        return [tag]
    latest = read_latest_tag(load_dir)
    rest = list_complete_tags(load_dir)
    if latest is None:
        return rest
    return [latest] + [t for t in rest if t != latest]


class NoLoadableCheckpoint(FileNotFoundError):
    """Every candidate tag was missing or failed an integrity check."""


def load_with_fallback(load_dir: str, tag: Optional[str], loader,
                       what: str = "checkpoint"):
    """Run ``loader(tag)`` over :func:`resolve_tag_candidates`, falling back
    past integrity failures (``RECOVERABLE_ERRORS``) to the newest complete
    earlier tag with one warning per fallback. The shared fallback protocol
    for the trainer, the serving loaders, and the consolidation CLI — one
    place owns the exception filter and the messages. Structural errors
    (wrong model shape etc.) propagate immediately; exhaustion raises
    :class:`NoLoadableCheckpoint`."""
    candidates = resolve_tag_candidates(load_dir, tag)
    if not candidates:
        raise NoLoadableCheckpoint(
            f"no 'latest' tag in {load_dir}, none given, and no complete "
            f"{what} tags found")
    last_err = None
    for i, cand in enumerate(candidates):
        if i > 0:
            logger.warning(
                f"{what} tag {candidates[i - 1]!r} in {load_dir} is unusable "
                f"({last_err}); falling back to the newest complete earlier "
                f"tag {cand!r}")
        try:
            return loader(cand)
        except RECOVERABLE_ERRORS as e:
            last_err = f"{type(e).__name__}: {e}"
    raise NoLoadableCheckpoint(
        f"no loadable {what} in {load_dir}: tried {candidates}; "
        f"last error: {last_err}")


def validate_tag(tag: str, mode: str) -> None:
    """Cross-process tag agreement check (reference engine.py:3326-3342).

    Single-controller JAX already agrees by construction; in multi-host runs
    we broadcast rank 0's tag and compare."""
    import jax

    if jax.process_count() == 1 or mode == "Ignore":
        return
    from jax.experimental import multihost_utils
    import numpy as np

    digest = np.frombuffer(tag.encode().ljust(64, b"\0")[:64], dtype=np.uint8)
    agreed = multihost_utils.broadcast_one_to_all(digest)
    if not np.array_equal(digest, agreed):
        msg = f"Checkpoint tag '{tag}' differs across processes"
        if mode == "Fail":
            raise RuntimeError(msg)
        logger.warning(msg)
