"""Paged KV cache: block allocator + block-table attention.

Capability analog of the reference v2 ragged stack:
  - ``BlockedAllocator`` (ragged/blocked_allocator.py:11) — host-side
    free-list of KV blocks, here grown into a REF-COUNTED, CONTENT-ADDRESSED
    block store (round 11): full committed blocks are registered under a
    chained token hash, refcount-0 registered blocks park in a reusable LRU
    instead of losing their KV, and admission can acquire a matching prefix
    chain instead of re-prefilling it (the vLLM/FastGen prefix-cache idiom
    over the SURVEY §2.10 ragged substrate).
  - ``BlockedKVCache`` (ragged/kv_cache.py:40) — here ``PagedKVCache``:
    per-layer-stacked block pool [L, nblocks, KV, block, Dh] on device,
    optionally int8/fp8 STORAGE with per-token-per-head scale planes
    (``kv_cache_dtype``; the §2.11/§2.8 compression machinery applied to
    the serving cache — decode is KV-bandwidth-bound, so halving resident
    KV bytes is ~2x on the binding resource).
  - ``blocked_flash`` + ``atom_builder`` + ``linear_blocked_kv_rotary``
    (inference/v2/kernels/ragged_ops/) — here ``paged_decode_attention``
    (gather-by-block-table attention; the Pallas kernel variant lives in
    ops/paged_attention.py and is dispatched when on TPU).

TPU-first notes: block tables are static-shape int32 arrays padded with -1;
gathers/scatters are XLA ops inside jit, so a whole decode step (append +
attention over all layers) is one compiled program. Quantized pools pass
per-layer KV to the kernels as ``(data, scale)`` pairs; the kernels
dequantize in-register on stream and the XLA gather path doubles as the
CPU-testable numerics oracle.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from functools import lru_cache
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Content-addressed block keys
# ---------------------------------------------------------------------------


def _chain_key(parent: bytes, tokens: Sequence[int]) -> bytes:
    """Key for one full block given its parent block's key (b"" for the
    first block): position-dependent by construction, so two identical
    blocks at different depths never collide."""
    chunk = np.asarray(tokens, np.int64).tobytes()
    return hashlib.blake2b(parent + chunk, digest_size=16).digest()


@lru_cache(maxsize=512)
def _chain_keys_cached(tokens: Tuple[int, ...], block_size: int,
                       parent: bytes) -> Tuple[bytes, ...]:
    out: List[bytes] = []
    for i in range(len(tokens) // block_size):
        parent = _chain_key(parent, tokens[i * block_size:(i + 1) * block_size])
        out.append(parent)
    return tuple(out)


def chain_block_keys(tokens: Sequence[int], block_size: int,
                     parent: bytes = b"") -> List[bytes]:
    """Chained content keys for every FULL block of ``tokens`` (the partial
    tail has no key — only committed, immutable blocks are addressable).
    Keys are pure functions of (tokens, block_size, parent), so they are
    LRU-memoized: the scheduler peeks every QUEUED request's prompt every
    tick while it waits for admission, and without the cache a long
    prompt's whole blake2b chain would be re-hashed each time."""
    return list(_chain_keys_cached(tuple(int(t) for t in tokens),
                                   block_size, parent))


class BlockedAllocator:
    """Ref-counted, content-addressed allocator over ``num_blocks`` KV
    blocks (host side).

    Extends ragged/blocked_allocator.py:11's free-list with the three
    mechanisms prefix caching needs:

      - **refcounts**: ``allocate`` hands out blocks at refcount 1;
        ``retain`` shares them (prefix hit, fork); ``free`` decrements and
        only a refcount-0 block leaves a sequence's ownership. Freeing a
        block that is not allocated raises (the ISSUE 6 double-free fix —
        the old total-count assert missed per-id double frees).
      - **content registry**: ``register(key, block)`` binds a committed
        full block to its chained token hash; ``peek``/``acquire`` walk a
        key chain and return the longest registered prefix.
      - **cached-free LRU**: a registered block whose refcount hits 0
        parks in an LRU of reusable blocks instead of losing its KV; it
        still counts as allocatable (``free_blocks``) and is evicted —
        registration dropped — only when a fresh allocation needs it.
        ``acquire`` revives parked hits at refcount 1.
    """

    def __init__(self, num_blocks: int):
        if num_blocks <= 0:
            raise ValueError("num_blocks must be positive")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks))
        self._ref: Dict[int, int] = {}                # live block -> refcount
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # LRU, ref 0
        self._block_of: Dict[bytes, int] = {}         # key -> block
        self._key_of: Dict[int, bytes] = {}           # block -> key
        # counters (observability: the serving prefix_cache/* group and the
        # multichip dryrun's zero-new-allocation gate read these)
        self.fresh_allocs = 0     # blocks handed out by allocate()
        self.shared_acquires = 0  # prefix hits on LIVE blocks (ref +1)
        self.revives = 0          # prefix hits on parked cached-free blocks
        self.evictions = 0        # parked blocks recycled for fresh allocs

    # -- capacity ------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: truly free + parked cached-free (reusable
        content, but evictable the moment capacity is needed)."""
        return len(self._free) + len(self._cached)

    @property
    def live_blocks(self) -> int:
        return len(self._ref)

    @property
    def cached_blocks(self) -> int:
        return len(self._cached)

    @property
    def shared_blocks(self) -> int:
        """Live blocks held by more than one sequence."""
        return sum(1 for c in self._ref.values() if c > 1)

    def ref_count(self, block: int) -> int:
        return self._ref.get(block, 0)

    # -- allocate / retain / free --------------------------------------

    def allocate(self, n: int) -> List[int]:
        if n > self.free_blocks:
            raise RuntimeError(
                f"out of KV blocks: want {n}, have {self.free_blocks}")
        take = min(n, len(self._free))
        out, self._free = self._free[:take], self._free[take:]
        while len(out) < n:
            # recycle the least-recently-parked cached block; its content
            # is gone for good, so drop the registration with it
            b, _ = self._cached.popitem(last=False)
            self._unregister(b)
            self.evictions += 1
            out.append(b)
        for b in out:
            self._ref[b] = 1
        self.fresh_allocs += n
        return out

    def retain(self, blocks: Sequence[int]) -> None:
        """Add one reference to already-live blocks (fork / shared batch)."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"retain of unallocated block {b}")
        for b in blocks:
            self._ref[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block. Validates EVERY id before mutating
        anything, so a bad call leaves the allocator untouched; freeing a
        block that is not allocated raises (per-id double-free detection —
        the old ``len(self._free) <= num_blocks`` assert only caught
        aggregate overflows, never a specific id freed twice while another
        stayed leaked)."""
        drops: Dict[int, int] = {}
        for b in blocks:
            drops[b] = drops.get(b, 0) + 1
        for b, n in drops.items():
            if not (0 <= b < self.num_blocks):
                raise ValueError(f"bad block id {b}")
            have = self._ref.get(b, 0)
            if have < n:
                raise ValueError(
                    f"double free: block {b} dropped {n}x but holds "
                    f"{have} reference{'' if have == 1 else 's'}")
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._key_of:
                    # committed content stays reusable until evicted
                    self._cached[b] = None
                else:
                    self._free.append(b)

    # -- content addressing --------------------------------------------

    def register(self, key: bytes, block: int) -> bool:
        """Bind a committed full block to its chained content key. First
        writer wins: a key that is already registered (another sequence
        committed the same content first) keeps its existing block and this
        one stays private. Returns True when the binding was recorded."""
        if block not in self._ref:
            raise ValueError(f"register of unallocated block {block}")
        if key in self._block_of or block in self._key_of:
            return False
        self._block_of[key] = block
        self._key_of[block] = key
        return True

    def _unregister(self, block: int) -> None:
        key = self._key_of.pop(block, None)
        if key is not None:
            self._block_of.pop(key, None)

    def unregister(self, block: int) -> None:
        """Drop a block's content registration (the block stays live under
        its holder). For speculative-decode rewinds (ISSUE 8): a rejected
        draft invalidates a committed block's bytes-under-key binding —
        the rewinding sequence is about to overwrite part of the block, so
        future admissions must not resolve the stale key to it. Only legal
        on a block the caller holds exclusively (refcount 1); a ref-shared
        committed block must be COW-cloned instead, never unregistered out
        from under its other holders' future re-admissions."""
        if self.ref_count(block) != 1:
            raise ValueError(
                f"unregister of block {block} with refcount "
                f"{self.ref_count(block)}: only an exclusively-held block "
                "may lose its registration (shared committed blocks take "
                "the copy-on-write path)")
        self._unregister(block)

    def peek(self, keys: Sequence[bytes]) -> Tuple[int, int]:
        """(live, parked) counts for the longest registered prefix of
        ``keys`` — live blocks cost an admission ZERO new allocations,
        parked ones consume a slot from the free pool (they are already
        counted allocatable) but no prefill compute."""
        live = parked = 0
        for key in keys:
            b = self._block_of.get(key)
            if b is None:
                break
            if b in self._ref:
                live += 1
            else:
                parked += 1
        return live, parked

    def invalidate_registry(self) -> None:
        """Drop EVERY content registration and all parked blocks (back to
        the plain free list). For weight hot-swaps: cached KV was computed
        under the old weights, so a later admission hashing the same
        tokens must MISS — the keys are pure functions of token history
        and would otherwise resolve to stale content. Live blocks stay
        live (their holders own them); they just stop being addressable."""
        self._block_of.clear()
        self._key_of.clear()
        self._free.extend(self._cached)
        self._cached.clear()

    def acquire(self, keys: Sequence[bytes]) -> List[int]:
        """Acquire the longest registered prefix of ``keys``: live hits
        gain a reference, parked hits revive at refcount 1. Returns the
        blocks in chain order (possibly empty)."""
        out: List[int] = []
        for key in keys:
            b = self._block_of.get(key)
            if b is None:
                break
            if b in self._ref:
                self._ref[b] += 1
                self.shared_acquires += 1
            else:
                del self._cached[b]
                self._ref[b] = 1
                self.revives += 1
            out.append(b)
        return out


# ---------------------------------------------------------------------------
# KV quantization helpers (kv_cache_dtype: bf16 | int8 | fp8)
# ---------------------------------------------------------------------------

KV_CACHE_DTYPES = ("bf16", "int8", "fp8")


def kv_storage_dtype(kv_cache_dtype: str, compute_dtype):
    """Pool storage dtype for a kv_cache_dtype mode ("bf16" = the engine's
    serving dtype, the pre-round-11 behavior)."""
    import jax.numpy as jnp

    if kv_cache_dtype == "int8":
        return jnp.int8
    if kv_cache_dtype == "fp8":
        return jnp.float8_e4m3fn
    return compute_dtype


def _kv_maxval(qdtype) -> float:
    import jax.numpy as jnp

    if qdtype == jnp.int8:
        return 127.0
    return float(jnp.finfo(qdtype).max)   # e4m3: 448


def quantize_kv(x, qdtype):
    """Per-token-per-head symmetric quantization over the last (Dh) axis:
    x [..., Dh] -> (q [..., Dh] in ``qdtype``, scale [...] f32) with each
    row's absmax mapped to the storage dtype's max (the ops/quant.py
    group-wise idiom at row granularity — one scale per written KV row, so
    append/scatter paths stay single-scatter)."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    maxv = _kv_maxval(qdtype)
    scale = jnp.where(absmax > 0, absmax / maxv, 1.0)
    y = x32 / scale[..., None]
    if qdtype == jnp.int8:
        q = jnp.clip(jnp.round(y), -maxv, maxv).astype(jnp.int8)
    else:
        q = y.astype(qdtype)
    return q, scale


def dequantize_kv(q, scale, dtype=None):
    """q [..., Dh] storage + scale [...] -> f32 (or ``dtype``) values."""
    import jax.numpy as jnp

    out = q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
    return out.astype(dtype) if dtype is not None else out


def kv_parts(c):
    """Split a per-layer KV operand into (data, scale_or_None): quantized
    pools travel as ``(data, scale)`` pairs through the layer scans and
    kernel wrappers; bf16 pools stay bare arrays."""
    if isinstance(c, tuple):
        return c[0], c[1]
    return c, None


class PagedKVCache(NamedTuple):
    """Device block pool. k/v: [L, num_blocks, KV, block_size, Dh].

    KV is a LEADING dim (round 3): the Pallas decode kernel DMAs one kv
    head's block per grid step, which TPU block specs only allow on
    non-minor dims; {block_size, Dh} minor also makes blocks native
    (8,128)-tileable.

    Round 11: ``kv_cache_dtype`` int8/fp8 stores k/v at 1 byte/element and
    grows per-token-per-head scale planes ``k_scale``/``v_scale``
    [L, num_blocks, KV, block_size] (f32). bf16 mode keeps the scale
    fields as empty pytrees so every jitted program signature is stable
    within an engine."""

    k: "object"
    v: "object"
    k_scale: "object" = ()
    v_scale: "object" = ()

    @classmethod
    def create(cls, n_layers: int, num_blocks: int, block_size: int,
               kv_heads: int, head_dim: int, dtype,
               kv_cache_dtype: str = "bf16") -> "PagedKVCache":
        import jax.numpy as jnp

        if kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(f"kv_cache_dtype must be one of "
                             f"{KV_CACHE_DTYPES}, got {kv_cache_dtype!r}")
        store = kv_storage_dtype(kv_cache_dtype, dtype)
        shape = (n_layers, num_blocks, kv_heads, block_size, head_dim)
        k, v = jnp.zeros(shape, store), jnp.zeros(shape, store)
        if kv_cache_dtype == "bf16":
            return cls(k, v)
        sshape = shape[:-1]
        return cls(k, v, jnp.ones(sshape, jnp.float32),
                   jnp.ones(sshape, jnp.float32))

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return not isinstance(self.k_scale, tuple)

    def pool_nbytes(self) -> int:
        """Resident bytes of the KV pool including scale planes — the
        figure the kv_cache_dtype modes halve (tests/test_kv_quant.py pins
        the ratio)."""
        total = 0
        for x in self:
            if not isinstance(x, tuple):
                total += int(np.prod(x.shape)) * x.dtype.itemsize
        return total


def blocks_needed(n_tokens: int, block_size: int) -> int:
    return max(1, -(-n_tokens // block_size))


def gather_kv(ck, cv, block_table):
    """ck/cv [nblk, KV, bs, Dh] (one layer) — or quantized ``(data, scale)``
    pairs with scale [nblk, KV, bs] — block_table [B, maxblk] (-1 pad)
    -> k/v [B, maxblk*bs, KV, Dh]. Quantized pools dequantize after the
    gather (this is the CPU numerics oracle for the in-kernel dequant).
    Padding rows gather block 0; callers mask by seq length so the junk
    never contributes."""
    import jax.numpy as jnp

    bt = jnp.maximum(block_table, 0)
    B, M = bt.shape

    def g(c):
        nblk, KV, bs, Dh = c.shape
        x = jnp.take(c, bt.reshape(-1), axis=0)          # [B*M, KV, bs, Dh]
        x = x.reshape(B, M, KV, bs, Dh).transpose(0, 1, 3, 2, 4)
        return x.reshape(B, M * bs, KV, Dh)

    def gs(s):
        nblk, KV, bs = s.shape
        x = jnp.take(s, bt.reshape(-1), axis=0)          # [B*M, KV, bs]
        x = x.reshape(B, M, KV, bs).transpose(0, 1, 3, 2)
        return x.reshape(B, M * bs, KV)

    kq, ks = kv_parts(ck)
    vq, vs = kv_parts(cv)
    if ks is None:
        return g(kq), g(vq)
    return (g(kq).astype(jnp.float32) * gs(ks)[..., None],
            g(vq).astype(jnp.float32) * gs(vs)[..., None])


def append_token_kv(ck, cv, newk, newv, block_table, pos, layer=None):
    """Scatter one new token's K/V per sequence into the block pool.

    ck/cv [nblk, KV, bs, Dh] — or the stacked [L, nblk, KV, bs, Dh] pool
    with ``layer`` set, which scatters into layer ``layer`` WITHOUT ever
    slicing the pool (the decode loop carries one pool buffer and XLA
    updates it in place; a per-layer slice would read+write the whole
    layer each step). Quantized pools ride as ``(data, scale)`` pairs:
    the new rows are quantized per (sequence, kv head) on write and the
    scale plane gets the matching scatter. newk/newv [B, KV, Dh];
    block_table [B, maxblk]; pos [B] = token index within the sequence
    (the slot being written).
    Reference: linear_blocked_kv_rotary's KV append half.
    """
    import jax.numpy as jnp

    kq, ks = kv_parts(ck)
    vq, vs = kv_parts(cv)
    pooled = kq.ndim == 5
    bs = kq.shape[3] if pooled else kq.shape[2]
    blk = jnp.take_along_axis(jnp.maximum(block_table, 0), (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    if ks is not None:
        newk, sk = quantize_kv(newk, kq.dtype)     # q [B,KV,Dh], scale [B,KV]
        newv, sv = quantize_kv(newv, vq.dtype)
    # advanced indices around the KV slice: result is [B, KV, Dh] (numpy
    # moves the advanced dims to the front), matching newk/newv exactly
    if pooled:
        kq = kq.at[layer, blk, :, off].set(newk.astype(kq.dtype))
        vq = vq.at[layer, blk, :, off].set(newv.astype(vq.dtype))
        if ks is not None:
            ks = ks.at[layer, blk, :, off].set(sk)
            vs = vs.at[layer, blk, :, off].set(sv)
    else:
        kq = kq.at[blk, :, off].set(newk.astype(kq.dtype))
        vq = vq.at[blk, :, off].set(newv.astype(vq.dtype))
        if ks is not None:
            ks = ks.at[blk, :, off].set(sk)
            vs = vs.at[blk, :, off].set(sv)
    if ks is None:
        return kq, vq
    return (kq, ks), (vq, vs)


def write_prefill_kv(ck, cv, ks_, vs_, block_table):
    """Write a whole prompt's K/V (one sequence) into its blocks.

    ck/cv [nblk, KV, bs, Dh] (or quantized ``(data, scale)`` pairs);
    ks_/vs_ [Tpad, KV, Dh] with Tpad == nseq_blocks*bs (caller pads);
    block_table [nseq_blocks] real ids.
    """
    kq, ksc = kv_parts(ck)
    vq, vsc = kv_parts(cv)
    bs = kq.shape[2]
    n = block_table.shape[0]

    def blocks(x):
        KV, Dh = x.shape[1], x.shape[2]
        return x.reshape(n, bs, KV, Dh).transpose(0, 2, 1, 3)

    def scale_blocks(s):           # [Tpad, KV] -> [n, KV, bs]
        KV = s.shape[1]
        return s.reshape(n, bs, KV).transpose(0, 2, 1)

    if ksc is not None:
        ks_, sk = quantize_kv(ks_, kq.dtype)
        vs_, sv = quantize_kv(vs_, vq.dtype)
        ksc = ksc.at[block_table].set(scale_blocks(sk))
        vsc = vsc.at[block_table].set(scale_blocks(sv))
    kq = kq.at[block_table].set(blocks(ks_).astype(kq.dtype))
    vq = vq.at[block_table].set(blocks(vs_).astype(vq.dtype))
    if ksc is None:
        return kq, vq
    return (kq, ksc), (vq, vsc)


def paged_decode_attention(q, ck, cv, block_table, kv_len, alibi_slopes=None,
                           layer=None):
    """q [B,1,H,Dh] against paged KV (one layer) [nblk, KV, bs, Dh], or
    the stacked [L, nblk, KV, bs, Dh] pool with ``layer`` set; quantized
    pools ride as ``(data, scale)`` pairs and dequantize in-register.

    On TPU this dispatches to the fused Pallas kernel
    (``ops/paged_attention.py``): the block table rides in scalar memory and
    KV blocks stream through VMEM once — no materialized [B,S,KV,Dh] gather
    (reference blocked_flash + atom_builder). Elsewhere (and as the numerics
    oracle) it gathers by table, dequantizes, and runs dense decode
    attention. ``alibi_slopes`` [H] rides the kernel (BLOOM serving).
    """
    from ..ops.paged_attention import paged_decode_attention as _dispatch

    return _dispatch(q, ck, cv, block_table, kv_len,
                     alibi_slopes=alibi_slopes, layer=layer)
