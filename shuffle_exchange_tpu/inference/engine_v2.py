"""Inference engine v2 — continuous batching over a paged KV cache.

Capability analog of the reference FastGen stack (``inference/v2/engine_v2.py:30``
InferenceEngineV2, ``ragged/ragged_manager.py:19`` DSStateManager,
``ragged/sequence_descriptor.py:59``): host-side sequence state + block
allocator, device-side paged attention, and the ``put / query / flush``
serving API. Logits come back to the host (the reference samples on host
too); the v1 engine's fused generate covers the on-device loop.

TPU-first: every device program has static shapes — prompts are bucketed to
block multiples, decode batches to power-of-two widths — so a serving
process compiles a handful of programs total and replays them (the XLA
equivalent of the reference's CUDA-graph strategy).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..profiling import trace
from ..utils.invariants import atomic_on_reject
from ..utils.logging import logger
from .config import InferenceConfig
from .engine import InferenceEngine, _bucket
from .paged import (BlockedAllocator,
                    PagedKVCache, _chain_key, append_token_kv, blocks_needed,
                    chain_block_keys, kv_parts, paged_decode_attention,
                    quantize_kv)




def _program_name(key) -> str:
    """A step program's cache key as the tracer's compile events name it:
    ``mixed/8/4/2/64/4`` (kind, then the binned shapes)."""
    return "/".join(map(str, key))


def _donate_cache():
    """KV-pool donation for the paged programs (argument 1 of each), through
    the package's one donation seam (utils/placement.py)."""
    from ..utils.placement import cache_safe_donate_argnums

    return cache_safe_donate_argnums((1,))


@dataclasses.dataclass
class SequenceDescriptor:
    """Host state for one live sequence (ragged/sequence_descriptor.py:59).

    Round 11 prefix-cache fields: ``tokens`` is the full written-token
    history (every KV slot this sequence has filled — prompt plus decode
    inputs), ``committed`` counts the full blocks already registered in
    the allocator's content index, and ``last_key`` is the chained hash
    of the last committed block (parent for the next registration)."""

    uid: int
    seen_tokens: int = 0
    blocks: List[int] = dataclasses.field(default_factory=list)
    last_logits: Optional[np.ndarray] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    committed: int = 0
    last_key: bytes = b""
    # a sequence that lived through a force reload_weights() carries KV
    # from MIXED weights: its blocks must never enter the content index
    # (a fresh admission would hash the same tokens and hit stale KV)
    no_commit: bool = False
    # tiered KV (ISSUE 15): descriptor positions whose blocks were
    # spilled host-ward — ``blocks[i]`` holds the -1 sentinel for every
    # i in here, and the sequence cannot be dispatched until
    # ``fetch_spilled`` restores full residency
    spilled: set = dataclasses.field(default_factory=set)
    # per-request sampling (ISSUE 16): a SamplingParams for step_sampled's
    # fused in-dispatch sampler. None means greedy with no EOS — exactly
    # the pre-sampling engine contract, so step() callers never see it.
    sampling: Optional[object] = None
    # multi-tenant LoRA (ISSUE 18): the adapter this sequence decodes
    # under and its pinned AdapterPool slot. Slot 0 is the all-zeros
    # null adapter — no-adapter rows ride the same program and add an
    # exact 0.0, so the slot is ALWAYS a valid gather index.
    adapter_id: Optional[str] = None
    adapter_slot: int = 0


@dataclasses.dataclass
class KVBlockPayload:
    """One sequence's KV blocks in the POOL's own storage layout — the
    disaggregated prefill→decode wire format (ISSUE 7). ``k``/``v`` are
    [L, nb, KV, block, Dh] in the pool's storage dtype (bf16, or int8/fp8
    raw bytes), ``k_scale``/``v_scale`` the matching [L, nb, KV, block]
    f32 scale planes for quantized pools (None for bf16). Because the
    payload is a straight gather of pool storage, a transfer is bit-exact
    for bf16 and byte-exact (payload + scales) for quantized modes —
    nothing is ever re-quantized on the wire."""

    uid: int
    tokens: List[int]
    seen_tokens: int
    last_logits: Optional[np.ndarray]
    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray]
    v_scale: Optional[np.ndarray]
    kv_cache_dtype: str
    block_size: int
    # the serving weight version the exported KV was computed under
    # (ISSUE 12): KV bytes are only valid against the weights that wrote
    # them, so a failover migration from a replica that missed a fleet
    # publish must be refused (commit_import validates) and fall back to
    # re-prefill under the survivor's weights. None (a pre-ISSUE-12
    # payload) skips the check.
    weight_version: Optional[int] = None

    def arrays(self) -> List[np.ndarray]:
        """The device payload planes in wire order (data, then scales)."""
        out = [self.k, self.v]
        if self.k_scale is not None:
            out += [self.k_scale, self.v_scale]
        return out

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays())


@dataclasses.dataclass
class ImportReservation:
    """Decode-side half of the disagg admission handshake: KV blocks
    acquired (``begin_import``) before any payload bytes move, released
    by ``abort_import`` or consumed by ``commit_import``."""

    uid: int
    blocks: List[int]
    n_tokens: int
    done: bool = False


class InferenceEngineV2(InferenceEngine):
    """Paged continuous-batching engine.

    ``put(uids, tokens)`` runs prefill for new uids and single/multi-token
    extension for known ones, returning next-token logits per uid in order.
    """

    _fused_attention = True   # the paged decode step has a fused-attention
    # form (split-K kernel + in-pool append) independent of qkv/mlp fusion
    _has_verify_lane = True   # speculative verify rows exist here (ISSUE 8)

    def __init__(self, model, params, config: Optional[InferenceConfig] = None):
        super().__init__(model, params, config)
        cfg, mcfg = self.config, self._mcfg
        if cfg.max_seq_len % cfg.kv_block_size:
            raise ValueError("max_seq_len must be a multiple of kv_block_size")
        self.cache = PagedKVCache.create(mcfg.n_layers, cfg.num_kv_blocks, cfg.kv_block_size,
                                         mcfg.kv_heads, mcfg.head_dim, cfg.jax_dtype(),
                                         kv_cache_dtype=cfg.kv_cache_dtype)
        self.allocator = BlockedAllocator(cfg.num_kv_blocks)
        # prefix-cache observability (the scheduler's prefix_cache/* group
        # reads these; cow_copies also counts fork divergence with
        # prefix_caching off)
        self.prefix_hit_tokens = 0
        self.prefix_miss_tokens = 0
        self.cow_copies = 0
        # speculative-decode observability (ISSUE 8): rewinds of rejected
        # draft KV and the slots they returned (the scheduler's
        # speculative/* counter group reads these alongside its own
        # proposed/accepted tallies)
        self.spec_rollbacks = 0
        self.spec_rolled_tokens = 0
        # one-dispatch sampling observability (ISSUE 16): KV blocks
        # returned to the pool by EOS/stop early termination (the
        # scheduler's sampling/* counter group reads this), and the output
        # avals of every sampled program dispatched — the no-logits-to-host
        # proof (tests assert no [*, vocab]-shaped leaf ever ships).
        self.early_stop_freed_blocks = 0
        self.sampled_output_shapes: Dict[Tuple, Tuple] = {}
        # SamplingParams registered before their uid's first prefill lands
        # (configure_sampling on a not-yet-live uid); step_sampled pops
        # these into the descriptor it creates.
        self._pending_sampling: Dict[int, object] = {}
        # block 0 is scratch: padding table entries scribble here, never read.
        self._scratch = self.allocator.allocate(1)[0]
        self._seqs: Dict[int, SequenceDescriptor] = {}
        self._max_blocks = cfg.max_seq_len // cfg.kv_block_size
        self._prefill_cache: Dict[Tuple[int, int], object] = {}
        self._decode_cache: Dict[int, object] = {}
        self._extend_cache: Dict[int, object] = {}
        self._mixed_cache: Dict[Tuple, object] = {}
        # device programs launched (observability + the <=2-dispatch/step
        # contract for mixed batches; reference counts ragged-batch launches)
        self.dispatch_count = 0
        # distinct compiled-program shapes dispatched — the shape-bin
        # ladder's footprint. Serving tests assert this stays bounded by
        # the ladder while ticks grow unbounded.
        self._program_keys: set = set()
        # versioned serving weights (ISSUE 11): the RLHF train->serve flip
        # stamps every publication so rollout replay logs can name the
        # exact weights a token was sampled under. ``_staged_weights``
        # holds a prepared-but-uncommitted tree (the two-phase fleet
        # publish), ``_pending_weights`` a committed-but-deferred one
        # (applied at the next tick boundary — see apply_pending_weights).
        self.weight_version = 0
        self._staged_weights: Optional[Tuple[object, Optional[int]]] = None
        self._pending_weights: Optional[Tuple[object, Optional[int]]] = None
        # tiered paged KV (ISSUE 15): host tier for cold spilled blocks —
        # the scheduler parks sequences here under KV pressure instead of
        # preempting them (byte-exact spill/fetch over the AIO substrate)
        self.tier = None
        if cfg.kv_tier.enabled:
            from .kv_tier import HostKVTier

            self.tier = HostKVTier(spill_dir=cfg.kv_tier.spill_dir,
                                   prefetch_depth=cfg.kv_tier.prefetch_depth)
        # multi-tenant LoRA serving (ISSUE 18): paged pool of adapter
        # factor pairs; per-row slot indices gather from it inside every
        # serving program. ``_pending_adapter`` mirrors
        # ``_pending_sampling`` — bindings registered before the uid's
        # first prefill, consumed when admission creates the descriptor.
        self.adapters = None
        if cfg.adapters.enabled:
            from .adapters import AdapterPool

            self.adapters = AdapterPool(
                mcfg, slots=cfg.adapters.slots,
                max_rank=cfg.adapters.max_rank,
                targets=cfg.adapters.targets,
                prefetch_depth=cfg.adapters.prefetch_depth,
                dtype=cfg.jax_dtype())
        self._pending_adapter: Dict[int, str] = {}
        # expert-parallel MoE serving (ISSUE 19): the engine serves MoE
        # models through the same one-dispatch step — top-k routing is
        # per-token DATA inside the layer scan (sorted-by-expert grouped
        # GEMM / capacity dispatch), so expert assignment never keys a
        # program shape and the warmed server's zero-recompile invariant
        # holds. Per-tick routing counts ride out of every dispatch as an
        # extra [L, E] output (the "_pop_moe" seam) and feed the
        # scheduler's expert-capacity admission + the moe/* counters.
        self._moe_serving = self._mcfg.n_experts > 0
        self._moe_tap = None           # armed per layer-scan body (engine._ffn appends)
        self.moe_dispatched = 0        # expert assignments routed (post-drop)
        self.moe_dropped = 0           # assignments dropped at expert capacity
        self.moe_expert_load_max = 0   # peak per-(layer, expert) load seen
        self._moe_last_counts = None   # [E] worst-layer per-expert load, last tick
        self._moe_last_total = 0       # S*k of the last tick (capacity denominator)
        if self._moe_serving:
            mo = cfg.serving.moe
            # "auto" defers to the model config's moe_impl (which itself
            # resolves scanned "auto" -> capacity, moe/resolve_moe_impl);
            # an explicit serving impl wins over the model config
            self._moe_impl_override = (None if mo.moe_impl == "auto"
                                       else mo.moe_impl)
            self._moe_cf_override = mo.capacity_factor
            self._shard_expert_weights()

    # -- scheduling queries (engine_v2.py:158-232) ---------------------

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def program_shapes(self) -> frozenset:
        """Distinct compiled device-program shape keys dispatched so far —
        the shape-bin ladder's compile footprint. Serving runs of any
        length stay bounded by the ladder (tests assert it)."""
        return frozenset(self._program_keys)

    def query(self, uid: int) -> Tuple[int, int]:
        """(max further tokens for uid, free blocks) — engine_v2.py:158."""
        desc = self._seqs.get(uid)
        seen = desc.seen_tokens if desc else 0
        have = len(desc.blocks) * self.cache.block_size if desc else 0
        headroom = (have - seen) + self.allocator.free_blocks * self.cache.block_size
        return min(self.config.max_seq_len - seen, headroom), self.allocator.free_blocks

    def can_schedule(self, uids: Sequence[int], lengths: Sequence[int]) -> bool:
        """Admission check (engine_v2.py:184 can_schedule)."""
        return self._admission_detail(uids, lengths)[0]

    def _admission_detail(self, uids: Sequence[int], lengths: Sequence[int],
                          new_tokens: Optional[Dict[int, Sequence[int]]] = None
                          ) -> Tuple[bool, int, str]:
        """(ok, blocks_from_free_pool, why-not): the named-numbers
        admission check behind can_schedule/put()/step() — failures say
        how many KV blocks the batch wants vs how many are free and which
        uid asks for the most (decode_loop's error discipline). With
        ``new_tokens`` (uid -> prompt for NEW uids) and prefix_caching on,
        prefix-cached blocks are netted out: a live shared hit costs zero
        free-pool slots, a parked hit costs its revival slot but no
        prefill, and the message names cached vs new. A known uid whose
        next write lands in a still-shared block budgets one extra block
        for the copy-on-write clone."""
        bs = self.cache.block_size
        need, worst_uid, worst_ask, worst_cached = 0, None, -1, 0
        for uid, n in zip(uids, lengths):
            desc = self._seqs.get(uid)
            seen = desc.seen_tokens if desc else 0
            have = len(desc.blocks) if desc else 0
            if seen + n > self.config.max_seq_len:
                return False, 0, (
                    f"uid {uid} would overrun max_seq_len: {seen} seen + {n} "
                    f"new > {self.config.max_seq_len} (split the request or "
                    f"raise max_seq_len)")
            cached = 0
            if desc is None and new_tokens and uid in new_tokens:
                _, live, parked = self.prefix_peek(new_tokens[uid])
                cached = live + parked
                # only the LIVE hits are free; parked revivals consume a
                # slot from the free pool (they are counted free until
                # acquired)
                ask = max(0, blocks_needed(n, bs) - live)
            else:
                ask = max(0, blocks_needed(seen + n, bs) - have)
                if desc is not None and desc.blocks:
                    first, last = seen // bs, (seen + n - 1) // bs
                    ask += sum(
                        1 for i in range(first, min(last + 1, len(desc.blocks)))
                        if self.allocator.ref_count(desc.blocks[i]) > 1)
            need += ask
            if ask > worst_ask:
                worst_uid, worst_ask, worst_cached = uid, ask, cached
        if need > self.allocator.free_blocks:
            cache_note = (f" after {worst_cached} prefix-cached" if worst_cached
                          else "")
            tier_note = ""
            if self.tier is not None:
                # tier-aware accounting (ISSUE 15): spillable blocks are
                # reclaimable-not-free — a spill pass could fund this ask
                # without losing any sequence's KV, so the refusal names
                # them next to the free count for the scheduler's
                # park-instead-of-preempt decision
                tier_note = (f" + {self.spillable_blocks(exclude=uids)} "
                             f"reclaimable via kv_tier spill")
            stop_note = ""
            if self.early_stop_freed_blocks:
                # EOS/stop accounting (ISSUE 16): early terminations have
                # already been returning blocks — name them so a refusal
                # under sampled load reads against the right baseline
                stop_note = (f"; early stops have returned "
                             f"{self.early_stop_freed_blocks} blocks to the "
                             f"pool so far")
            return False, need, (
                f"needs {need} KV blocks, {self.allocator.free_blocks} free"
                f"{tier_note} "
                f"(largest single ask: uid {worst_uid} wants {worst_ask} new"
                f"{cache_note}); flush finished sequences or raise "
                f"num_kv_blocks{stop_note}")
        if self.adapters is not None:
            # adapter residency is the THIRD admission resource (ISSUE 18,
            # after KV blocks and max_seq_len): a batch whose pending
            # adapters cannot all be pinned is refused atomically, and the
            # refusal names the adapter pool — NOT KV — so the scheduler
            # parks the request instead of spilling KV that would not help
            want = []
            for uid in uids:
                if self._seqs.get(uid) is None:
                    aid = self._pending_adapter.get(uid)
                    if aid is not None:
                        want.append(aid)
            if want:
                aok, awhy = self.adapters.can_acquire_all(want)
                if not aok:
                    return False, need, (
                        f"adapter pool (KV is fine: {need} blocks needed, "
                        f"{self.allocator.free_blocks} free): {awhy}; park "
                        f"until a running sequence releases its slot")
        if self._moe_serving and any(self._seqs.get(u) is None for u in uids):
            # expert capacity is the FOURTH admission resource (ISSUE 19,
            # after KV blocks, max_seq_len, and adapter slots): when the
            # previous tick's routing saturated some expert's buffer,
            # NEW sequences are refused — named as expert-vs-KV pressure
            # so the scheduler parks instead of spilling KV that would
            # not help. Known uids always pass (running sequences keep
            # ticking, which is also what drains the pressure; the
            # ``self._seqs`` guard below keeps a stale reading from
            # blocking an idle engine forever).
            mo = self.config.serving.moe
            pr = self.moe_pressure()
            if (mo.overload_policy == "park" and self._seqs
                    and pr > mo.overload_threshold):
                return False, need, (
                    f"expert capacity (KV is fine: {need} blocks needed, "
                    f"{self.allocator.free_blocks} free): last tick's peak "
                    f"expert ran at {pr:.2f}x capacity (threshold "
                    f"{mo.overload_threshold:g}, policy park); hold new "
                    f"sequences until routing pressure drains")
        return True, need, ""

    # -- device programs ----------------------------------------------

    def _kv_xs(self, cache: PagedKVCache):
        """Per-layer KV operands for the layer scans: bf16 pools scan the
        bare [L, ...] arrays; quantized pools scan ``(data, scale)`` pairs
        so every layer body sees the pair the kernels take."""
        if cache.quantized:
            return (cache.k, cache.k_scale), (cache.v, cache.v_scale)
        return cache.k, cache.v

    @staticmethod
    def _cache_of(kp, vp) -> PagedKVCache:
        """Rebuild the pool from stacked scan outputs (pair-aware)."""
        if isinstance(kp, tuple):
            return PagedKVCache(kp[0], vp[0], kp[1], vp[1])
        return PagedKVCache(kp, vp)

    @staticmethod
    def _apool_xs(apool):
        """Adapter-pool xs for the layer scans: the pool's factor stacks
        are [L, S, ...] so they join the per-layer scan alongside weights
        and KV; each layer body sees its own [S, ...] slice. () when the
        program runs without adapters — pytree structure (not values)
        keys the jit specialization, so adapters-off programs are
        byte-identical to the pre-adapter ones."""
        return () if apool is None else (apool,)

    def _aargs(self, descs, B: int):
        """Trailing adapter operands for a dispatch: () when the pool is
        off, else ``(device_operands, slots[B] i32)`` with padding rows on
        the null slot. Slot VALUES are data — the operand shapes are
        fixed by (pool geometry, B-bin), so new adapters never recompile."""
        if self.adapters is None:
            return ()
        return (self.adapters.device_operands(), self._aslots(descs, B))

    @staticmethod
    def _aslots(descs, B: int):
        s = np.zeros((B,), np.int32)
        for i, d in enumerate(descs):
            s[i] = d.adapter_slot
        return s

    # -- expert-parallel MoE serving (ISSUE 19) ------------------------

    def _shard_expert_weights(self) -> None:
        """Expert-parallel weight placement: the stacked ``moe_*`` expert
        leaves are [L, E, ...], sharded over the mesh "expert" axis so
        each device holds E/ep experts and XLA lowers the dispatch/return
        all-to-all pair from the sharding constraints (the moe/layer.py
        pattern — ``_constrain_expert`` marks the activations inside the
        layer). No-op off-topology or when the expert axis is 1
        (single-chip serving: replicated experts)."""
        from ..parallel.mesh import get_topology, topology_is_initialized
        from ..utils.logging import logger

        if not topology_is_initialized():
            return
        topo = get_topology()
        ep = topo.expert_parallel_world_size
        if ep <= 1:
            return
        import jax

        E = self._mcfg.n_experts
        if E % ep:
            raise ValueError(
                f"n_experts={E} is not divisible by the mesh expert axis "
                f"({ep}) — expert-parallel serving shards whole experts")
        sharding = topo.named_sharding(None, "expert")
        layers = dict(self.params["layers"])
        moved = []
        for name, leaf in layers.items():
            if (name.startswith("moe_") and name != "moe_gate"
                    and not name.startswith("moe_shared")
                    and getattr(leaf, "ndim", 0) >= 2):
                # int8/fp8 QuantizedMatrix expert stacks shard the same
                # way: device_put broadcasts the sharding over the
                # pytree's children, and both q and scales carry E on
                # dim 1 (scale groups run along K), so the expert split
                # never cuts a scale group
                layers[name] = jax.device_put(leaf, sharding)
                moved.append(name)
        if moved:
            params = dict(self.params)
            params["layers"] = layers
            self.params = params
            logger.info(
                f"MoE serving: sharded {moved} over expert axis ({ep}-way, "
                f"{E // ep} experts/device); dispatch/return all-to-all "
                f"lowered by XLA from sharding constraints")

    def _moe_arm(self):
        """Arm the per-layer routing-counts tap consumed by the base
        engine's ``_ffn`` (it appends ``(expert_counts [E], dropped)`` per
        MoE FFN call, up to one per lane). Called at the top of every
        layer-scan body — the tracers stay inside the scan trace and are
        folded into the scan's ys by :meth:`_moe_ys`."""
        if not self._moe_serving:
            return None
        tap = []
        self._moe_tap = tap
        return tap

    def _moe_ys(self, tap):
        """Close the tap and fold its entries (one per lane that ran this
        layer) into scan-ys elements ``(counts [E] i32, dropped [] f32)``.
        Returns ``()`` when MoE serving is off, so dense programs keep a
        byte-identical pytree structure."""
        if tap is None:
            return ()
        import jax.numpy as jnp

        self._moe_tap = None
        assert tap, "MoE serving armed a layer tap but no FFN appended " \
            "routing counts — the layer body bypassed engine._ffn"
        counts = sum(c.astype(jnp.int32) for c, _ in tap)
        dropped = sum(jnp.asarray(d, jnp.float32) for _, d in tap)
        return ((counts, dropped),)

    def _pop_moe(self, out):
        """Strip the trailing MoE routing-counts element off a dispatch
        result and fold it into the per-tick accounting; identity when
        MoE serving is off."""
        if not self._moe_serving:
            return out
        self._note_moe_counts(out[-1])
        return out[:-1]

    def _note_moe_counts(self, moe) -> None:
        """Host-side accounting from one dispatch's routing counts.
        ``moe = (counts [..., L, E], dropped [..., L])`` (a leading steps
        axis when the fused decode loop produced them). Updates the moe/*
        counters and the previous-tick load snapshot ``moe_pressure``
        reads — counts are post-drop for the capacity impl and pre-drop
        (dropped == 0) for the dropless ragged impl, so
        ``counts.sum() + dropped`` recovers S*k either way."""
        E = self._mcfg.n_experts
        counts = np.asarray(moe[0]).reshape(-1, E)
        dropped = np.asarray(moe[1], np.float64).reshape(-1)
        self.moe_dispatched += int(counts.sum())
        self.moe_dropped += int(round(float(dropped.sum())))
        self.moe_expert_load_max = max(self.moe_expert_load_max,
                                       int(counts.max()))
        self._moe_last_counts = counts.max(axis=0)
        self._moe_last_total = int(round(float(counts[-1].sum()
                                               + dropped[-1])))

    def moe_pressure(self) -> float:
        """Peak per-expert load from the previous tick's routing as a
        fraction of that tick's expert capacity — the scheduler's
        expert-overload signal (1/capacity_factor under balanced routing;
        > 1.0 means some expert saturated its buffer). 0.0 before the
        first MoE tick or on dense models."""
        if not self._moe_serving or self._moe_last_counts is None:
            return 0.0
        from ..moe.gating import compute_capacity

        k = max(1, self._mcfg.moe_top_k)
        S = max(1, self._moe_last_total // k)
        cap = compute_capacity(S, self._mcfg.n_experts, k,
                               self._moe_cf_override)
        return float(self._moe_last_counts.max()) / float(max(1, cap))

    def _paged_prefill_fn(self, p: int, tpad: int):
        fn = self._prefill_cache.get((p, tpad))
        if fn is not None:
            return fn
        import jax

        fn = jax.jit(self._paged_prefill_impl, donate_argnums=_donate_cache())
        self._prefill_cache[(p, tpad)] = fn
        return fn

    def _paged_prefill_impl(self, params, cache: PagedKVCache, ids, plen, btables,
                            apool=None, aslots=None):
        """BATCHED prefill — all pending new sequences in ONE program
        (reference packs them into one ragged batch, engine_v2.py:107).

        ids [P,tpad]; plen [P]; btables [P, tpad//block] (scratch-padded)
        -> cache, logits [P,V]. Sequences are independent rows; per-row
        block tables scatter each row's K/V into its own blocks (scratch
        rows collide harmlessly on the never-read scratch block)."""
        import jax
        import jax.numpy as jnp

        from ..ops.flash_attention import flash_attention

        P, tpad = ids.shape
        bs = self.cache.block_size
        nblk_pad = tpad // bs
        x, (cos, sin), positions = self._embed_at(params, ids, jnp.zeros((P,), jnp.int32))

        def layer_fn(h, layer_and_cache):
            lw, ck, cv = layer_and_cache[:3]
            lora = None if apool is None else (layer_and_cache[3], aslots)

            def attn_fn(q, k, v):
                KV, Dh = k.shape[2], k.shape[3]

                def blocks(x):   # [P,tpad,KV,Dh] -> pool blocks [P*nblk,KV,bs,Dh]
                    return (x.reshape(P, nblk_pad, bs, KV, Dh)
                            .transpose(0, 1, 3, 2, 4)
                            .reshape(P * nblk_pad, KV, bs, Dh))

                def sblocks(s):  # [P,tpad,KV] scale rows -> [P*nblk,KV,bs]
                    return (s.reshape(P, nblk_pad, bs, KV)
                            .transpose(0, 1, 3, 2)
                            .reshape(P * nblk_pad, KV, bs))

                flat = btables.reshape(-1)
                kq, ksc = kv_parts(ck)
                vq, vsc = kv_parts(cv)
                kw, vw = k, v
                if ksc is not None:
                    # quantize on write; attention below still uses the
                    # full-precision chunk (storage is what's compressed)
                    kw, sk = quantize_kv(k, kq.dtype)
                    vw, sv = quantize_kv(v, vq.dtype)
                    ksc = ksc.at[flat].set(sblocks(sk))
                    vsc = vsc.at[flat].set(sblocks(sv))
                kq2 = kq.at[flat].set(blocks(kw).astype(kq.dtype))
                vq2 = vq.at[flat].set(blocks(vw).astype(vq.dtype))
                ck2 = kq2 if ksc is None else (kq2, ksc)
                cv2 = vq2 if vsc is None else (vq2, vsc)
                return flash_attention(q, k, v, causal=True,
                                       impl=self.config.attention_impl,
                                       alibi_slopes=self._alibi), (ck2, cv2)

            tap = self._moe_arm()
            h2, (ck2, cv2) = self._layer_body(lw, h, cos, sin, positions,
                                              attn_fn, lora=lora)
            return h2, (ck2, cv2) + self._moe_ys(tap)

        x, ys = jax.lax.scan(layer_fn, x,
                             (params["layers"],) + self._kv_xs(cache)
                             + self._apool_xs(apool))
        kp, vp = ys[0], ys[1]
        x_last = jnp.take_along_axis(x, (plen - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = self.model.head(params, x_last)[:, 0]
        return (self._cache_of(kp, vp), logits) + tuple(ys[2:])

    def _extend_fn(self, c: int):
        fn = self._extend_cache.get(c)
        if fn is not None:
            return fn
        import jax

        fn = jax.jit(self._extend_impl, donate_argnums=_donate_cache())
        self._extend_cache[c] = fn
        return fn

    def _extend_layer(self, lw, h, ck, cv, cos, sin, positions, start, nnew,
                      btables, lora=None):
        """One chunked-prefill layer: scatter the chunk's K/V into the pool
        and attend through the block table. Shared by the pure extend
        program and the mixed Dynamic-SplitFuse step (step()). Returns
        ``(h2, (ck2, cv2))``."""
        import jax.numpy as jnp

        B, C = h.shape[:2]
        bs = self.cache.block_size

        def attn_fn(q, k, v):
            # scatter the chunk's K/V: token i of row b -> block
            # btables[b, (start+i)//bs], offset (start+i)%bs. Tokens past
            # nnew land on the scratch block.
            pos = positions                                   # [B,C]
            valid = jnp.arange(C)[None, :] < nnew[:, None]
            blk = jnp.take_along_axis(jnp.maximum(btables, 0),
                                      jnp.minimum(pos // bs, btables.shape[1] - 1),
                                      axis=1)                 # [B,C]
            blk = jnp.where(valid, blk, self._scratch)
            off = pos % bs
            kq, ksc = kv_parts(ck)
            vq, vsc = kv_parts(cv)
            kw, vw = k, v
            if ksc is not None:
                # quantize on write: one scale per (token, kv head) row
                kw, sk = quantize_kv(k, kq.dtype)             # [B,C,KV]
                vw, sv = quantize_kv(v, vq.dtype)
                ksc = ksc.at[blk.reshape(-1), :, off.reshape(-1)].set(
                    sk.reshape(B * C, sk.shape[2]))
                vsc = vsc.at[blk.reshape(-1), :, off.reshape(-1)].set(
                    sv.reshape(B * C, sv.shape[2]))
            # [nblk,KV,bs,Dh] pool: advanced (blk, off) around the KV
            # slice yields [B*C, KV, Dh] rows, matching the new K/V
            kq2 = kq.at[blk.reshape(-1), :, off.reshape(-1)].set(
                kw.reshape(B * C, *kw.shape[2:]).astype(kq.dtype))
            vq2 = vq.at[blk.reshape(-1), :, off.reshape(-1)].set(
                vw.reshape(B * C, *vw.shape[2:]).astype(vq.dtype))
            ck2 = kq2 if ksc is None else (kq2, ksc)
            cv2 = vq2 if vsc is None else (vq2, vsc)
            # paged extend: q chunk attends the pool through the
            # block table — no [B, S_max, KV, Dh] gather (r2 weak #7);
            # ALiBi slopes ride the kernel (round 5)
            from ..ops.paged_attention import paged_extend_attention

            out = paged_extend_attention(q, ck2, cv2, btables, start,
                                         nnew, alibi_slopes=self._alibi)
            return out, (ck2, cv2)

        return self._layer_body(lw, h, cos, sin, positions, attn_fn,
                                lora=lora)

    def _extend_impl(self, params, cache: PagedKVCache, ids, start, nnew, btables,
                     apool=None, aslots=None):
        """Chunked-prefill extension — a C-token chunk per sequence in ONE
        program (one program per CHUNK, not per token; VERDICT r1 weak #4).

        ids [B,C] (zero-padded past nnew); start [B] = first new position;
        nnew [B] <= C; btables [B, W] (W = binned block-table width) ->
        cache, logits [B,V] at each sequence's last new token."""
        import jax
        import jax.numpy as jnp

        x, (cos, sin), positions = self._embed_at(params, ids, start)

        def layer_fn(h, layer_and_cache):
            lw, ck, cv = layer_and_cache[:3]
            lora = None if apool is None else (layer_and_cache[3], aslots)
            tap = self._moe_arm()
            h2, (ck2, cv2) = self._extend_layer(lw, h, ck, cv, cos, sin,
                                                positions, start, nnew,
                                                btables, lora=lora)
            return h2, (ck2, cv2) + self._moe_ys(tap)

        x, ys = jax.lax.scan(layer_fn, x,
                             (params["layers"],) + self._kv_xs(cache)
                             + self._apool_xs(apool))
        kp, vp = ys[0], ys[1]
        x_last = jnp.take_along_axis(x, (nnew - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = self.model.head(params, x_last)[:, 0]
        return (self._cache_of(kp, vp), logits) + tuple(ys[2:])

    def _paged_decode_fn(self, b: int):
        fn = self._decode_cache.get(b)
        if fn is not None:
            return fn
        import jax

        fn = jax.jit(self._paged_decode_impl, donate_argnums=_donate_cache())
        self._decode_cache[b] = fn
        return fn

    def _paged_decode_impl(self, params, cache: PagedKVCache, tok, pos, btables,
                           apool=None, aslots=None):
        """tok [B], pos [B] (next slot), btables [B, max_blocks].

        Cache structure: the layers run as one ``lax.scan`` that takes the
        stacked weights and the KV pool as ``xs`` and restacks the updated
        pool as ``ys``, so one layer body is traced whatever the depth and
        the pool is never both a scan carry and a kernel's aliased input.
        The price is that every token rewrites the pool into the stacked
        outputs; no record compares this with a carried or a per-layer
        pool at real widths (PERF.md section 7: no serving cell yet).

        With ``decode_kernel`` resolved to "pallas" each layer
        runs the FUSED path (``_fused_paged_layer``): one kernel for
        QKV+RoPE+pool-append (``input_output_aliases`` on the layer's pool
        slice — the scatter that used to be an XLA whole-slice update is an
        in-kernel DMA of just the new rows), one split-K flash-decode
        kernel over the block table, and one residual+MLP kernel — the
        next candidate for closing the remaining per-token gap, to be
        traced on silicon against this scan structure."""
        import jax

        x, (cos, sin), _ = self._embed_at(params, tok[:, None], pos)

        def layer_fn(h, layer_and_cache):
            lw, ck, cv = layer_and_cache[:3]
            lora = None if apool is None else (layer_and_cache[3], aslots)
            tap = self._moe_arm()
            h2, (ck2, cv2) = self._decode_layer(lw, h, ck, cv, cos, sin,
                                                pos, btables, lora=lora)
            return h2, (ck2, cv2) + self._moe_ys(tap)

        x, ys = jax.lax.scan(layer_fn, x,
                             (params["layers"],) + self._kv_xs(cache)
                             + self._apool_xs(apool))
        kp, vp = ys[0], ys[1]
        logits = self.model.head(params, x)[:, 0]
        return (self._cache_of(kp, vp), logits) + tuple(ys[2:])

    def _decode_layer(self, lw, h, ck, cv, cos, sin, pos, btables, lora=None):
        """One decode layer (one token per sequence): fused Pallas path
        when eligible, else append + paged attention. Shared by the pure
        decode step, the fused decode_loop, and the mixed step(). Returns
        ``(h2, (ck2, cv2))``.

        With ``lora`` set the fully-fused layer is skipped — its fused
        QKV kernel bypasses ``_layer_body``'s projection seam where the
        per-row adapter deltas apply — but the attention-only split-K
        fusion below still runs (attention reads the pool, adapters only
        touch the projections)."""
        if self._decode_kernel == "pallas" and lora is None:
            fused = self._fused_paged_layer(lw, h, ck, cv, cos, sin,
                                            pos, btables)
            if fused is not None:
                return fused

        def attn_fn(q, k, v):
            ck2, cv2 = append_token_kv(ck, cv, k[:, 0], v[:, 0], btables, pos)
            if self._decode_kernel == "pallas":
                # attention-only fusion: even when QKV fusion is off
                # for this layer (quantized weights, interleaved rope)
                # the split-K kernel still replaces the per-kv-head
                # streaming one
                from ..ops import fused_decode as fd

                return fd.fused_paged_decode_attention(
                    q, ck2, cv2, btables, kv_len=pos + 1,
                    alibi_slopes=self._alibi), (ck2, cv2)
            # round 5: slopes ride the paged kernel (no cache gather
            # for BLOOM serving); the wrapper's CPU fallback gathers
            return paged_decode_attention(q, ck2, cv2, btables,
                                          kv_len=pos + 1,
                                          alibi_slopes=self._alibi), (ck2, cv2)

        return self._layer_body(lw, h, cos, sin, pos, attn_fn, lora=lora)

    def _fused_paged_layer(self, lw, h, ck, cv, cos, sin, pos, btables):
        """One fully-fused decode layer: fused QKV+RoPE+append writes the
        new token's K/V into the pool slice in place, the split-K paged
        kernel attends through the block table, and the shared
        ``_block_tail`` finishes (fusing the MLP when eligible). Returns
        ``(h_new, (ck2, cv2))`` or None to take the XLA path (QKV fusion
        not selected for this model, or quantized attention weights). Once
        selected the kernels run or raise."""
        from ..models.transformer import _norm
        from ..ops import fused_decode as fd

        cfg = self._mcfg
        if not self._fuse_qkv:
            return None
        args = self._fused_qkv_args(lw, cos, sin, pos)
        if args is None:
            return None
        cosr, sinr, bias = args
        with trace.scope("attn_norm"):
            y = _norm(h, lw["ln1_w"], lw.get("ln1_b", 0), cfg.norm,
                      eps=cfg.norm_eps)
        with trace.scope("attn_qkv"):       # with the new token's KV append
            q, k, v, ck2, cv2 = self._fused_qkv_append(
                lw, y, ck, cv, cosr, sinr, bias, pos, btables)
        with trace.scope("attn_core"):
            attn = fd.fused_paged_decode_attention(
                q[:, None], ck2, cv2, btables, pos + 1,
                alibi_slopes=self._alibi)
        return self._block_tail(lw, h, y, attn), (ck2, cv2)

    def _fused_qkv_append(self, lw, y, ck, cv, cosr, sinr, bias, pos, btables):
        import jax.numpy as jnp

        from ..ops import fused_decode as fd

        cfg = self._mcfg
        bs = self.cache.block_size
        if isinstance(ck, tuple):
            # int8/fp8 pool: the in-kernel pool DMA would write raw
            # projections without the scale plane, so the append goes
            # through the XLA quantize-on-write scatter (one token's
            # rows — negligible next to the streamed KV read, which
            # stays fused and dequantizes in-register below)
            q, k, v = fd.fused_qkv_rope(
                y[:, 0], lw["wq"], lw["wk"], lw["wv"], cos=cosr,
                sin=sinr, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                **bias)
            ck2, cv2 = append_token_kv(ck, cv, k, v, btables, pos)
        else:
            blk = jnp.take_along_axis(jnp.maximum(btables, 0),
                                      (pos // bs)[:, None], axis=1)[:, 0]
            off = pos % bs
            q, k, v, ck2, cv2 = fd.fused_qkv_rope(
                y[:, 0], lw["wq"], lw["wk"], lw["wv"], cos=cosr, sin=sinr,
                n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                pool_k=ck, pool_v=cv, blk=blk, off=off, **bias)
        return q, k, v, ck2, cv2

    # -- host-side scheduling ------------------------------------------

    def _clone_block(self, src: int, dst: int) -> None:
        """Device copy of one pool block (all layers, data + scale planes)
        — the copy half of copy-on-write. One cached jitted program with
        the pool donated (same discipline as every other cache-updating
        program here): XLA updates the pool in place and moves O(block)
        bytes, where an eager ``at[].set`` would materialize a full pool
        copy per clone — a transient 2x-pool allocation that could OOM a
        pool sized near HBM capacity. src/dst ride as i32 operands so
        every clone hits the same executable."""
        fn = getattr(self, "_clone_prog", None)
        if fn is None:
            import jax

            from ..utils.placement import cache_safe_donate_argnums

            def impl(cache, src_, dst_):
                def cp(x):
                    return x.at[:, dst_].set(x[:, src_])

                return PagedKVCache(*[cp(x) if not isinstance(x, tuple)
                                      else x for x in cache])

            fn = jax.jit(impl,
                         donate_argnums=cache_safe_donate_argnums((0,)))
            self._clone_prog = fn
        self.cache = fn(self.cache, np.int32(src), np.int32(dst))

    def _ensure_blocks(self, desc: SequenceDescriptor, total_tokens: int) -> None:
        """Grow ``desc`` to cover ``total_tokens``, copy-on-write first:
        the coming write spans [seen, total) — any EXISTING block in that
        span still shared with another sequence (a fork's partial tail, or
        a mid-block divergence from a shared prefix) gets a private clone
        before the dispatch writes into it. Committed full blocks are
        never in the write span (committed <= seen // block), so the
        content registry stays consistent without rollback."""
        bs = self.cache.block_size
        first = desc.seen_tokens // bs
        last = (max(total_tokens, 1) - 1) // bs
        for i in range(first, min(last + 1, len(desc.blocks))):
            b = desc.blocks[i]
            if self.allocator.ref_count(b) > 1:
                assert i >= desc.committed, (desc.uid, i, desc.committed)
                [nb] = self.allocator.allocate(1)
                self._clone_block(b, nb)
                self.allocator.free([b])
                desc.blocks[i] = nb
                self.cow_copies += 1
        need = blocks_needed(total_tokens, bs) - len(desc.blocks)
        if need > 0:
            desc.blocks.extend(self.allocator.allocate(need))

    # -- tiered KV: spill / fetch (ISSUE 15) ----------------------------

    def _pool_planes(self):
        """The pool's storage planes in wire order (data, then scales) —
        the same per-plane layout KVBlockPayload ships."""
        c = self.cache
        return ([c.k, c.v, c.k_scale, c.v_scale] if c.quantized
                else [c.k, c.v])

    def _require_resident(self, uids: Sequence[int], what: str) -> None:
        """Dispatch paths need FULL residency: a sequence with spilled
        blocks must be fetched back before its KV can be read or written
        (the block table addresses pool slots the spill freed)."""
        if self.tier is None:
            return
        for uid in uids:
            desc = self._seqs.get(uid)
            if desc is not None and desc.spilled:
                raise RuntimeError(
                    f"cannot {what}: uid {uid} has {len(desc.spilled)} KV "
                    f"blocks spilled to the host tier — fetch_spilled"
                    f"({uid}) first (the scheduler un-parks before "
                    f"dispatching)")

    def is_resident(self, uid: int) -> bool:
        desc = self._seqs.get(uid)
        return desc is not None and not desc.spilled

    def _keep_hot(self, desc: SequenceDescriptor) -> int:
        """Blocks of ``desc`` kept resident on a spill: the TAIL of the
        decode window (most recently written, first re-read), sized by
        ``kv_tier.hot_block_fraction``."""
        import math

        frac = self.config.kv_tier.hot_block_fraction
        return int(math.ceil(frac * len(desc.blocks)))

    def spillable_blocks(self, exclude: Sequence[int] = ()) -> int:
        """Reclaimable-not-free blocks (ISSUE 15 accounting): exclusively
        held (refcount 1), resident, cold (outside the hot tail) blocks
        of live sequences not in ``exclude`` — what a spill pass could
        return to the free pool without losing any token's KV. Shared
        prefix blocks are NOT spillable (another sequence may be
        dispatched against them this tick)."""
        if self.tier is None:
            return 0
        skip = set(exclude)
        total = 0
        for uid, desc in self._seqs.items():
            if uid in skip:
                continue
            limit = max(0, len(desc.blocks) - self._keep_hot(desc))
            total += sum(
                1 for i in range(limit)
                if i not in desc.spilled
                and self.allocator.ref_count(desc.blocks[i]) == 1)
        return total

    @atomic_on_reject(check="validate")
    def spill_sequence(self, uid: int,
                       keep_hot: Optional[int] = None) -> int:
        """Spill ``uid``'s cold exclusive blocks host-ward, freeing their
        pool slots; returns the number of blocks reclaimed. The host copy
        is the pool's OWN storage bytes (data + quantized scale planes,
        byte-exact — the KVBlockPayload discipline), so a later
        ``fetch_spilled`` restores the sequence with no re-prefill and no
        re-quantization. Shared (refcount > 1) blocks stay resident;
        ``keep_hot`` tail blocks (default from
        ``kv_tier.hot_block_fraction``) stay resident as the hot set.

        Crash discipline (the ``kv_spill`` fault site): the host gather
        happens BEFORE any engine mutation, and the tier store commits
        before the allocator free — a spill killed at the fault site
        leaves pool, allocator, and tier byte-identically unchanged."""
        from ..testing import faults

        if self.tier is None:
            raise RuntimeError("kv_tier is not enabled on this engine "
                               "(set inference config kv_tier.enabled)")
        desc = self._seqs.get(uid)
        if desc is None:
            raise ValueError(f"unknown uid {uid}")
        if keep_hot is None:
            keep_hot = self._keep_hot(desc)
        limit = max(0, len(desc.blocks) - keep_hot)
        cand = [i for i in range(limit)
                if i not in desc.spilled
                and self.allocator.ref_count(desc.blocks[i]) == 1]
        if not cand:
            return 0
        # gather width binned to a power of two (scratch-padded rows,
        # sliced off host-side): the device gather is a compiled program
        # per shape, and unbinned widths would compile a fresh executable
        # for every distinct spill size — a mid-trace compile that poisons
        # goodput exactly like the unbinned block tables of round 9
        n = len(cand)
        W = _bucket(n, minimum=1)
        idx = np.asarray([desc.blocks[i] for i in cand]
                         + [self._scratch] * (W - n), np.int32)
        planes = [np.asarray(p[:, idx])[:, :n]
                  for p in self._pool_planes()]
        if faults.ACTIVE:
            faults.maybe_crash("kv_spill", 0)
        self.tier.store(uid, cand, planes)
        self.allocator.free([desc.blocks[i] for i in cand])
        for i in cand:
            desc.blocks[i] = -1
            desc.spilled.add(i)
        return len(cand)

    @atomic_on_reject(check="validate")
    def fetch_spilled(self, uid: int) -> int:
        """Restore ``uid``'s spilled blocks into FRESH pool slots (one
        jitted scatter — the disagg import program); returns the block
        count fetched. Atomic-on-reject: the free-pool check and the tier
        read happen before any allocation, and a failure after the
        allocation (the ``kv_fetch`` fault site) frees the fresh blocks
        again — engine and tier end exactly as before the call."""
        from ..testing import faults

        desc = self._seqs.get(uid)
        if desc is None:
            raise ValueError(f"unknown uid {uid}")
        if not desc.spilled:
            return 0
        idxs = sorted(desc.spilled)
        n = len(idxs)
        if n > self.allocator.free_blocks:
            raise RuntimeError(
                f"cannot fetch uid {uid}'s {n} spilled KV blocks: only "
                f"{self.allocator.free_blocks} free "
                f"({self.spillable_blocks(exclude=[uid])} reclaimable via "
                f"further spill); park another sequence or raise "
                f"num_kv_blocks")
        tidx, planes = self.tier.load(uid)
        assert tidx == idxs, (uid, tidx, idxs)
        new = self.allocator.allocate(n)
        try:
            if faults.ACTIVE:
                faults.maybe_crash("kv_fetch", 0)
            # scatter width binned like the spill gather: pad the index
            # row with the scratch block (duplicate scratch writes land
            # in the garbage slot) and the planes with zero rows, so the
            # import program compiles once per power-of-two width instead
            # of once per distinct spilled-block count
            W = _bucket(n, minimum=1)
            idx_pad = np.asarray(list(new) + [self._scratch] * (W - n),
                                 np.int32)
            planes_pad = [
                p if W == n else np.concatenate(
                    [p, np.zeros(p.shape[:1] + (W - n,) + p.shape[2:],
                                 p.dtype)], axis=1)
                for p in planes]
            fn = self._import_fn(W, self.cache.quantized)
            self.cache = fn(self.cache, idx_pad, *planes_pad)
        except BaseException:
            self.allocator.free(new)
            raise
        for j, i in enumerate(idxs):
            desc.blocks[i] = new[j]
        desc.spilled.clear()
        self.tier.drop(uid)
        return n

    # -- speculative rollback (ISSUE 8) ---------------------------------

    def rewind(self, uid: int, n_tokens: int) -> None:
        """Roll ``uid``'s written-token history back to its first
        ``n_tokens`` slots — the rejected-draft half of speculative
        decoding. Surplus blocks return to the allocator; the stale KV
        bytes (data AND quantized scale planes) past the boundary are
        never read again (every read path masks by ``seen_tokens``) and
        the next write at those slots overwrites both planes.

        Composition with the prefix-cache commit chain: rewinding INTO a
        committed content-registered block invalidates its bytes-under-key
        binding. An exclusively-held committed block is unregistered; a
        REF-SHARED committed block is never touched — other sequences
        (and future admissions) read it — so the rewind takes the
        copy-on-write fallback: clone it privately first, or raise a
        targeted error naming the block when the pool can't fund the
        clone. Validation and the clone reservation happen BEFORE any
        mutation, so a refused rewind leaves allocator + descriptor
        untouched (the PR 6 free() atomicity discipline)."""
        desc = self._seqs.get(uid)
        if desc is None:
            raise ValueError(f"unknown uid {uid}")
        self._require_resident([uid], "rewind()")
        self._rewind(desc, int(n_tokens))

    def _rewind(self, desc: SequenceDescriptor, n_tokens: int) -> None:
        bs = self.cache.block_size
        if not 1 <= n_tokens <= desc.seen_tokens:
            raise ValueError(
                f"rewind of uid {desc.uid} to {n_tokens} tokens: must be "
                f"in [1, seen_tokens={desc.seen_tokens}]")
        if n_tokens == desc.seen_tokens:
            return
        new_nb = blocks_needed(n_tokens, bs)
        nc = n_tokens // bs            # full blocks that stay fully valid
        # ---- plan (validate + decide the COW before any mutation) ----
        tail_cow = tail_unregister = None
        if nc < desc.committed and n_tokens % bs:
            # the partial tail lands INSIDE a committed block: its tail
            # slots will be rewritten by the sequence's continuation
            b = desc.blocks[nc]
            if self.allocator.ref_count(b) > 1:
                if self.allocator.free_blocks < 1:
                    raise RuntimeError(
                        f"cannot rewind uid {desc.uid} to {n_tokens} "
                        f"tokens: block {b} is a committed prefix block "
                        f"shared by {self.allocator.ref_count(b)} "
                        "sequences and the pool has no free block for the "
                        "copy-on-write clone; flush finished sequences or "
                        "raise num_kv_blocks")
                tail_cow = b
            else:
                tail_unregister = b
        # ---- mutate ----
        if tail_cow is not None:
            [nb] = self.allocator.allocate(1)
            self._clone_block(tail_cow, nb)
            self.allocator.free([tail_cow])
            desc.blocks[nc] = nb
            self.cow_copies += 1
        elif tail_unregister is not None:
            self.allocator.unregister(tail_unregister)
        if new_nb < len(desc.blocks):
            # committed blocks PAST the boundary are freed intact: their
            # registered content still matches its key (the key hashes
            # exactly the tokens written there), so a ref-0 registered
            # block parks reusable in the allocator's cached-free LRU —
            # a re-proposed draft chain can hit it again for free
            self.allocator.free(desc.blocks[new_nb:])
            del desc.blocks[new_nb:]
        self.spec_rolled_tokens += desc.seen_tokens - n_tokens
        self.spec_rollbacks += 1
        desc.seen_tokens = n_tokens
        del desc.tokens[n_tokens:]
        if desc.committed > nc:
            desc.committed = nc
            keys = chain_block_keys(desc.tokens[:nc * bs], bs)
            desc.last_key = keys[-1] if keys else b""

    # -- prefix cache (content-addressed block reuse) -------------------

    def prefix_peek(self, tokens: Sequence[int]) -> Tuple[int, int, int]:
        """(hit_tokens, live_blocks, parked_blocks): the longest committed
        prefix of ``tokens`` currently reusable from the block store. Live
        blocks cost an admission ZERO free-pool slots (another sequence
        holds them resident); parked ones consume a free slot on revival
        but no prefill compute either way. Capped one token short of the
        full prompt so an admission always prefills at least the last
        token (the logits position)."""
        if not self.config.prefix_caching:
            return 0, 0, 0
        bs = self.cache.block_size
        max_full = (len(tokens) - 1) // bs
        if max_full <= 0:
            return 0, 0, 0
        keys = chain_block_keys(list(tokens)[:max_full * bs], bs)
        live, parked = self.allocator.peek(keys)
        return (live + parked) * bs, live, parked

    def acquire_prefix(self, uid: int, tokens: Sequence[int]) -> int:
        """Admit ``uid`` with the longest committed prefix of ``tokens``
        acquired from the block store (live hits gain a reference, parked
        hits revive): the descriptor starts at ``seen_tokens == hit`` and
        the caller prefills only the suffix. Returns the hit token count
        (0 admits a cold descriptor). The sequence's own continuation
        commits new full blocks back to the store as it grows."""
        if uid in self._seqs:
            raise ValueError(f"uid {uid} is already live")
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError(f"new uid {uid} with no tokens")
        desc = SequenceDescriptor(uid=uid)
        aid = self._pending_adapter.get(uid)
        if aid is not None:
            if self.adapters is None:
                raise RuntimeError(
                    f"uid {uid} names adapter {aid!r} but adapters are "
                    f"disabled (set adapters.enabled in the inference "
                    f"config)")
            # pin BEFORE any KV mutation: AdapterPoolDry here leaves the
            # engine untouched (put()'s atomic-on-reject contract); the
            # pending binding is only consumed on success
            desc.adapter_slot = self.adapters.acquire(aid)
            desc.adapter_id = aid
            self._pending_adapter.pop(uid, None)
        if self.config.prefix_caching:
            bs = self.cache.block_size
            max_full = (len(tokens) - 1) // bs
            keys = chain_block_keys(tokens[:max_full * bs], bs)
            blocks = self.allocator.acquire(keys)
            hit = len(blocks) * bs
            desc.blocks = blocks
            desc.seen_tokens = hit
            desc.tokens = tokens[:hit]
            desc.committed = len(blocks)
            desc.last_key = keys[len(blocks) - 1] if blocks else b""
            self.prefix_hit_tokens += hit
            self.prefix_miss_tokens += len(tokens) - hit
        self._seqs[uid] = desc
        return desc.seen_tokens

    def _commit(self, desc: SequenceDescriptor) -> None:
        """Register every newly-FULL block of ``desc`` under its chained
        content key (first writer wins; a lost race keeps the block
        private). Committed blocks are immutable from here on — the write
        paths never touch positions below ``seen_tokens`` and COW guards
        forks — so a later admission can share them by hash alone."""
        if not self.config.prefix_caching or desc.no_commit:
            return
        bs = self.cache.block_size
        nfull = min(desc.seen_tokens, len(desc.tokens)) // bs
        while desc.committed < nfull:
            i = desc.committed
            key = _chain_key(desc.last_key, desc.tokens[i * bs:(i + 1) * bs])
            self.allocator.register(key, desc.blocks[i])
            desc.last_key = key
            desc.committed += 1

    def fork(self, parent_uid: int, new_uid: int) -> None:
        """Clone a live sequence's host state sharing ALL its KV blocks
        (parallel sampling / beam candidates / speculative branches) —
        including the partial tail block, which stays shared until either
        side writes into it and triggers the copy-on-write clone in
        ``_ensure_blocks``."""
        parent = self._seqs.get(parent_uid)
        if parent is None:
            raise ValueError(f"unknown parent uid {parent_uid}")
        if new_uid in self._seqs:
            raise ValueError(f"uid {new_uid} is already live")
        self._require_resident([parent_uid], "fork()")
        if parent.adapter_id is not None:
            # the clone decodes under the parent's adapter: bump the slot
            # refcount (a resident-hit acquire) so eviction respects both
            self.adapters.acquire(parent.adapter_id)
        self.allocator.retain(parent.blocks)
        self._seqs[new_uid] = SequenceDescriptor(
            uid=new_uid, seen_tokens=parent.seen_tokens,
            blocks=list(parent.blocks),
            last_logits=None if parent.last_logits is None
            else np.array(parent.last_logits),
            tokens=list(parent.tokens), committed=parent.committed,
            last_key=parent.last_key, no_commit=parent.no_commit,
            sampling=parent.sampling, adapter_id=parent.adapter_id,
            adapter_slot=parent.adapter_slot)

    def _table(self, desc: SequenceDescriptor,
               width: Optional[int] = None) -> np.ndarray:
        """Block-table row for one sequence, ``width`` entries (default
        max_seq_len//block). Serving paths bin the width to the smallest
        power of two covering the batch's allocated blocks: the decode
        kernels stream EVERY table entry's block through VMEM, padding
        included, so table width is directly per-step HBM read traffic."""
        width = self._max_blocks if width is None else width
        assert len(desc.blocks) <= width, (desc.uid, len(desc.blocks), width)
        t = np.full((width,), self._scratch, dtype=np.int32)
        t[:len(desc.blocks)] = desc.blocks
        return t

    def _binned_width(self, nblocks: int) -> int:
        """Power-of-two block-table width covering ``nblocks``, capped at
        the max_seq_len table."""
        return min(_bucket(max(1, int(nblocks)), minimum=1), self._max_blocks)

    def _pack_decode(self, descs: List[SequenceDescriptor],
                     toks: Sequence[int]):
        """(B, W, tok, pos, tables) for a batched one-token decode step.
        Blocks must already be ensured for seen+1."""
        W = self._binned_width(max(len(d.blocks) for d in descs))
        B = _bucket(len(descs), minimum=1)
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tables = np.full((B, W), self._scratch, np.int32)
        for i, (d, t) in enumerate(zip(descs, toks)):
            tok[i], pos[i] = t, d.seen_tokens
            tables[i] = self._table(d, W)
        return B, W, tok, pos, tables

    def _pack_chunks(self, batch: List[Tuple[SequenceDescriptor, List[int]]],
                     pad_chunk: Optional[int] = None):
        """(B, C, W, ids, start, nnew, tables) for a chunked-prefill batch.
        ``pad_chunk`` pins the padded chunk length (the serving ladder);
        default is the power-of-two bucket of the longest chunk. Blocks
        must already be ensured for seen+len(chunk)."""
        cmax = max(len(c) for _, c in batch)
        C = pad_chunk if pad_chunk is not None else _bucket(cmax, minimum=1)
        assert C >= cmax, (C, cmax)
        W = self._binned_width(max(len(d.blocks) for d, _ in batch))
        B = _bucket(len(batch), minimum=1)
        ids = np.zeros((B, C), np.int32)
        start = np.zeros((B,), np.int32)
        nnew = np.ones((B,), np.int32)
        tables = np.full((B, W), self._scratch, np.int32)
        for i, (d, chunk) in enumerate(batch):
            ids[i, :len(chunk)] = chunk
            start[i] = d.seen_tokens
            nnew[i] = len(chunk)
            tables[i] = self._table(d, W)
        return B, C, W, ids, start, nnew, tables

    def _pack_prefill(self, prefills: List[Tuple[SequenceDescriptor, List[int]]]):
        """(P, tpad, ids, plen, btables) for the batched flash-prefill
        program that put() dispatches. Allocates each descriptor's
        blocks."""
        bs = self.cache.block_size
        tmax = max(len(toks) for _, toks in prefills)
        tpad = max(bs, _bucket(tmax, minimum=bs))
        tpad = min(-(-tpad // bs) * bs, self.config.max_seq_len)
        nblk_pad = tpad // bs
        P = _bucket(len(prefills), minimum=1)
        ids = np.zeros((P, tpad), np.int32)
        plen = np.ones((P,), np.int32)
        btables = np.full((P, nblk_pad), self._scratch, np.int32)
        for i, (desc, toks) in enumerate(prefills):
            T = len(toks)
            self._ensure_blocks(desc, T)
            ids[i, :T] = toks
            plen[i] = T
            btables[i, :len(desc.blocks)] = desc.blocks[:nblk_pad]
        return P, tpad, ids, plen, btables

    @atomic_on_reject
    def put(self, uids: Sequence[int], tokens: Sequence[Sequence[int]]) -> np.ndarray:
        """Serve one engine step (engine_v2.py:107). New uids are prefilled;
        known uids extended by their new tokens. Returns fp32 logits
        [len(uids), vocab] for each sequence's latest position, in order."""
        import jax.numpy as jnp

        if len(uids) != len(tokens):
            raise ValueError("uids and tokens must align")
        if len(set(uids)) != len(uids):
            raise ValueError("duplicate uid in one put() batch: a sequence can "
                             "advance at most one decode position per engine step")
        for uid, toks in zip(uids, tokens):
            if uid not in self._seqs and not len(toks):
                raise ValueError(f"new uid {uid} with no tokens")
        self._require_resident(uids, "put()")
        new_tokens = {u: list(map(int, t)) for u, t in zip(uids, tokens)
                      if u not in self._seqs}
        # Admission check BEFORE any KV mutation (prefix acquisition
        # included): a rejected put() must leave the engine untouched so
        # the caller can retry it verbatim.
        ok, _, why = self._admission_detail(uids, [len(t) for t in tokens],
                                            new_tokens=new_tokens)
        if not ok:
            raise RuntimeError(f"cannot schedule put() batch: {why}")
        n_ext = sum(1 for uid, toks in zip(uids, tokens)
                    if uid in self._seqs and len(toks))
        n_ext += sum(1 for toks in new_tokens.values()
                     if self.prefix_peek(toks)[0] > 0)
        if n_ext > self.config.max_batch_size:
            raise ValueError(f"decode batch {n_ext} exceeds max_batch_size "
                             f"{self.config.max_batch_size} (raise it in the inference config)")
        bs = self.cache.block_size
        prefills: List[Tuple[SequenceDescriptor, List[int]]] = []
        extends: List[Tuple[SequenceDescriptor, List[int]]] = []
        for uid, toks in zip(uids, tokens):
            if uid in self._seqs and uid not in new_tokens:
                toks = list(map(int, toks))
                if toks:
                    extends.append((self._seqs[uid], toks))
        for uid, toks in new_tokens.items():
            # a prefix hit admits the descriptor at the cached boundary and
            # prefills ONLY the suffix through the extend/decode programs
            # (acquire_prefix is a no-op admission when prefix_caching is
            # off); cold prompts take the batched flash-prefill program
            hit = self.acquire_prefix(uid, toks)
            desc = self._seqs[uid]
            if hit:
                extends.append((desc, toks[hit:]))
            else:
                prefills.append((desc, toks))

        # ---- ALL pending prefills: one bucketed batched program ---------
        if prefills:
            P, tpad, ids, plen, btables = self._pack_prefill(prefills)
            fn = self._paged_prefill_fn(P, tpad)
            self.cache, logits = self._pop_moe(
                fn(self.params, self.cache, ids, plen, btables,
                   *self._aargs([d for d, _ in prefills], P)))
            self.dispatch_count += 1
            self._program_keys.add(("prefill", P, tpad))
            logits = np.asarray(logits)
            for i, (desc, toks) in enumerate(prefills):
                desc.seen_tokens = len(toks)
                desc.tokens = list(toks)
                desc.last_logits = logits[i]
                self._commit(desc)

        # ---- single-token extensions: one batched decode program --------
        singles = [(d, toks[0]) for d, toks in extends if len(toks) == 1]
        multis = [(d, toks) for d, toks in extends if len(toks) > 1]
        if singles:
            for d, _ in singles:
                self._ensure_blocks(d, d.seen_tokens + 1)
            B, W, tok, pos, tables = self._pack_decode(
                [d for d, _ in singles], [t for _, t in singles])
            fn = self._paged_decode_fn(B)
            self.cache, logits = self._pop_moe(
                fn(self.params, self.cache, tok, pos, tables,
                   *self._aargs([d for d, _ in singles], B)))
            self.dispatch_count += 1
            self._program_keys.add(("decode", B, W))
            logits = np.asarray(logits)
            for i, (d, t) in enumerate(singles):
                d.seen_tokens += 1
                d.tokens.append(int(t))
                d.last_logits = logits[i]
                self._commit(d)

        # ---- multi-token extensions: chunked prefill, one program/chunk --
        # (reference runs these as ragged atoms in the same batch; we batch
        # chunks across sequences and size them to the KV block, so an
        # N-token extension costs ceil(N/block) dispatches, NOT N —
        # VERDICT r1 weak #4)
        while any(toks for _, toks in multis):
            batch = []
            for d, toks in multis:
                if toks:
                    chunk, remaining = toks[:bs], toks[bs:]
                    toks[:] = remaining
                    batch.append((d, chunk))
            for d, chunk in batch:
                self._ensure_blocks(d, d.seen_tokens + len(chunk))
            B, C, W, ids, start, nnew, tables = self._pack_chunks(batch)
            fn = self._extend_fn((B, C))
            self.cache, logits = self._pop_moe(
                fn(self.params, self.cache, ids, start, nnew, tables,
                   *self._aargs([d for d, _ in batch], B)))
            self.dispatch_count += 1
            self._program_keys.add(("extend", B, C, W))
            logits = np.asarray(logits)
            for i, (d, chunk) in enumerate(batch):
                d.seen_tokens += len(chunk)
                d.tokens.extend(chunk)
                d.last_logits = logits[i]
                self._commit(d)

        return np.stack([self._seqs[uid].last_logits for uid in uids])

    # -- continuous-batching mixed step (Dynamic SplitFuse) ------------

    def _mixed_fn(self, key):
        fn = self._mixed_cache.get(key)
        if fn is not None:
            return fn
        import jax

        fn = jax.jit(self._mixed_step_impl, donate_argnums=_donate_cache())
        self._mixed_cache[key] = fn
        return fn

    def _mixed_step_impl(self, params, cache: PagedKVCache, dtok, dpos,
                         dtables, pids, pstart, pnnew, ptables,
                         apool=None, daslots=None, paslots=None):
        """The Dynamic-SplitFuse mixed step: ONE program advances every
        running sequence by one decode token ([Bd] rows) AND absorbs a
        prefill chunk for every prefilling sequence ([Bp, C] rows) — the
        reference FastGen scheduler's uniform mixed batch (SURVEY §2.10;
        Orca iteration-level scheduling / Sarathi chunked prefill), built
        from the existing paged decode + extend layer bodies over ONE
        layer scan so the KV pool is rewritten once per step, not twice.

        Decode and prefill rows are disjoint sequences (a uid plays one
        role per tick), so within a layer the decode append and the chunk
        scatter write disjoint blocks; both attentions read through their
        own block tables. Returns (cache, decode_logits [Bd,V],
        prefill_logits [Bp,V] at each chunk's last token)."""
        import jax
        import jax.numpy as jnp

        xd, (cos, sin), _ = self._embed_at(params, dtok[:, None], dpos)
        xp, _, ppos = self._embed_at(params, pids, pstart)

        def layer_fn(carry, layer_and_cache):
            hd, hp = carry
            lw, ck, cv = layer_and_cache[:3]
            ap = None if apool is None else layer_and_cache[3]
            tap = self._moe_arm()
            hd2, (ck2, cv2) = self._decode_layer(
                lw, hd, ck, cv, cos, sin, dpos, dtables,
                lora=None if ap is None else (ap, daslots))
            hp2, (ck3, cv3) = self._extend_layer(
                lw, hp, ck2, cv2, cos, sin, ppos, pstart, pnnew, ptables,
                lora=None if ap is None else (ap, paslots))
            return (hd2, hp2), (ck3, cv3) + self._moe_ys(tap)

        (xd, xp), ys = jax.lax.scan(layer_fn, (xd, xp),
                                    (params["layers"],) + self._kv_xs(cache)
                                    + self._apool_xs(apool))
        kp, vp = ys[0], ys[1]
        dlogits = self.model.head(params, xd)[:, 0]
        x_last = jnp.take_along_axis(xp, (pnnew - 1)[:, None, None].astype(jnp.int32),
                                     axis=1)
        plogits = self.model.head(params, x_last)[:, 0]
        return (self._cache_of(kp, vp), dlogits, plogits) + tuple(ys[2:])

    # -- speculative mixed step (ISSUE 8) ------------------------------

    def _spec_fn(self, key):
        fn = self._mixed_cache.get(key)
        if fn is not None:
            return fn
        import jax

        fn = jax.jit(self._spec_step_impl, donate_argnums=_donate_cache())
        self._mixed_cache[key] = fn
        return fn

    def _spec_step_impl(self, params, cache: PagedKVCache, dops, pops, sops,
                        apool=None):
        """The speculative mixed step: ONE program advances plain decode
        rows by one token, absorbs prefill chunks, AND verifies draft
        rows — each draft row is ``[pending_token, d1..dk]`` running
        through the SAME ``_extend_layer`` body as prefill chunks (the
        verifier is the chunked-prefill path; its intra-chunk causal mask
        is exactly the draft-verification mask). Lanes are pytree-absent
        (empty tuple) when unused, so every lane combination is its own
        compiled program on the shape-bin ladder.

        Verification is greedy and ON-DEVICE: per draft row the head runs
        at EVERY chunk position (this is the verify cost — k+1 head
        projections instead of 1), ``ver[j] = argmax`` after position j,
        and the accepted length is the longest prefix where
        ``ver[j] == ids[j+1]`` (draft j+1 matches the verifier). Returns
        per-row ``(ver [Bs,Cs], accepted [Bs], last_logits [Bs,V])`` —
        ``last_logits`` is the row's logits at its accepted position, so
        the host emits ``drafts[:a] + [ver[a]]`` (the correction when
        a < k, the bonus token when a == k) without shipping [Bs,Cs,V]
        logits off device."""
        import jax
        import jax.numpy as jnp

        dops, pops, sops = tuple(dops), tuple(pops), tuple(sops)
        # adapter slots ride INSIDE the lane tuples (one trailing [B] i32
        # per present lane) so lane presence still keys the program via
        # pytree structure alone
        dslots = pslots = sslots = None
        xd = xp = xs = None
        cos = sin = None
        if dops:
            if apool is not None:
                dtok, dpos, dtables, dslots = dops
            else:
                dtok, dpos, dtables = dops
            xd, (cos, sin), _ = self._embed_at(params, dtok[:, None], dpos)
        if pops:
            if apool is not None:
                pids, pstart, pnnew, ptables, pslots = pops
            else:
                pids, pstart, pnnew, ptables = pops
            xp, (cos, sin), ppos = self._embed_at(params, pids, pstart)
        if sops:
            if apool is not None:
                sids, sstart, snnew, stables, sslots = sops
            else:
                sids, sstart, snnew, stables = sops
            xs, (cos, sin), spos = self._embed_at(params, sids, sstart)

        def layer_fn(carry, layer_and_cache):
            hd, hp, hs = carry
            lw, ck, cv = layer_and_cache[:3]
            ap = None if apool is None else layer_and_cache[3]
            tap = self._moe_arm()
            if hd is not None:
                hd, (ck, cv) = self._decode_layer(
                    lw, hd, ck, cv, cos, sin, dpos, dtables,
                    lora=None if ap is None else (ap, dslots))
            if hp is not None:
                hp, (ck, cv) = self._extend_layer(
                    lw, hp, ck, cv, cos, sin, ppos, pstart, pnnew, ptables,
                    lora=None if ap is None else (ap, pslots))
            if hs is not None:
                # the verify lane IS the extend path (ISSUE 8 satellite:
                # k+1-wide rows are outside the single-token fused decode
                # kernels — decode_fusion_eligibility's "verify" gate);
                # with adapters, the verify rows apply their own slots so
                # drafts are verified under the SAME weights they decode
                hs, (ck, cv) = self._extend_layer(
                    lw, hs, ck, cv, cos, sin, spos, sstart, snnew, stables,
                    lora=None if ap is None else (ap, sslots))
            return (hd, hp, hs), (ck, cv) + self._moe_ys(tap)

        (xd, xp, xs), ys = jax.lax.scan(
            layer_fn, (xd, xp, xs), (params["layers"],) + self._kv_xs(cache)
            + self._apool_xs(apool))
        kp, vp = ys[0], ys[1]
        dlogits = self.model.head(params, xd)[:, 0] if dops else None
        plogits = None
        if pops:
            x_last = jnp.take_along_axis(
                xp, (pnnew - 1)[:, None, None].astype(jnp.int32), axis=1)
            plogits = self.model.head(params, x_last)[:, 0]
        sres = None
        if sops:
            slog = self.model.head(params, xs)          # [Bs, Cs, V]
            ver = jnp.argmax(slog, axis=-1).astype(jnp.int32)
            Bs, Cs = sids.shape
            nxt = jnp.concatenate(
                [sids[:, 1:], jnp.zeros((Bs, 1), sids.dtype)], axis=1)
            j = jnp.arange(Cs)[None, :]
            m = jnp.where(j < (snnew - 1)[:, None], ver == nxt, False)
            accepted = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1),
                               axis=1)                   # [Bs] in [0, k]
            slast = jnp.take_along_axis(
                slog, accepted[:, None, None], axis=1)[:, 0]
            sres = (ver, accepted, slast)
        return (self._cache_of(kp, vp), dlogits, plogits, sres) + tuple(ys[2:])

    @atomic_on_reject
    def _admit_step(self, decode_uids, decode_tokens, prefills, speculative,
                    what: str):
        """The shared validation + all-or-nothing admission front half of
        step()/step_sampled(): normalize the lane lists, validate lane
        membership, admit the WHOLE tick before any state mutation, then
        create descriptors for new prefill uids and ensure every
        participant's KV blocks. Returns (prefills, speculative, ddescs,
        pdescs, sdescs)."""
        prefills = [(u, list(map(int, c))) for u, c in prefills]
        speculative = [(u, list(map(int, c))) for u, c in speculative]
        if len(decode_uids) != len(decode_tokens):
            raise ValueError("decode_uids and decode_tokens must align")
        all_uids = (list(decode_uids) + [u for u, _ in prefills]
                    + [u for u, _ in speculative])
        if len(set(all_uids)) != len(all_uids):
            raise ValueError(
                f"duplicate uid in one {what}: a sequence is either "
                "decoding, prefilling or verifying drafts in a tick, never "
                "two at once")
        for uid in decode_uids:
            if uid not in self._seqs:
                raise ValueError(f"decode uid {uid} unknown — prefill it "
                                 "first (step(prefills=...) or put())")
        for uid, chunk in prefills:
            if not chunk:
                raise ValueError(f"prefill uid {uid} with an empty chunk")
        for uid, chunk in speculative:
            if uid not in self._seqs:
                raise ValueError(f"speculative uid {uid} unknown — a draft "
                                 "row verifies an already-running sequence")
            if len(chunk) < 2:
                raise ValueError(
                    f"speculative uid {uid} with {len(chunk)} tokens — a "
                    "verify row is [pending_token, drafts...]; a row with "
                    "no drafts belongs in decode_uids")
        self._require_resident(all_uids, what)
        ok, _, why = self._admission_detail(
            all_uids, [1] * len(decode_uids) + [len(c) for _, c in prefills]
            + [len(c) for _, c in speculative])
        if not ok:
            raise RuntimeError(f"cannot schedule {what}: {why}")

        # admission passed: pin this tick's new adapters FIRST (pool
        # mutations precede any descriptor/KV mutation; a crashed fetch
        # rolls the acquired refs back so the tick rejects atomically).
        # Residents sort first so a miss's LRU eviction can never steal a
        # slot an already-resident hit in this same batch is about to pin.
        abind: Dict[int, Tuple[str, int]] = {}
        if self.adapters is not None:
            order = [(uid, self._pending_adapter[uid])
                     for uid, _ in prefills
                     if uid not in self._seqs
                     and self._pending_adapter.get(uid) is not None]
            order.sort(key=lambda t: self.adapters.slot_of(t[1]) is None)
            done = []
            try:
                for uid, aid in order:
                    abind[uid] = (aid, self.adapters.acquire(aid))
                    done.append(aid)
            except BaseException:
                for aid in done:
                    self.adapters.release(aid)
                raise

        # create descriptors for new prefill uids
        pdescs = []
        for uid, chunk in prefills:
            desc = self._seqs.get(uid)
            if desc is None:
                desc = SequenceDescriptor(uid=uid)
                desc.sampling = self._pending_sampling.pop(uid, None)
                if uid in abind:
                    desc.adapter_id, desc.adapter_slot = abind[uid]
                    self._pending_adapter.pop(uid, None)
                self._seqs[uid] = desc
            pdescs.append(desc)
        ddescs = [self._seqs[u] for u in decode_uids]
        sdescs = [self._seqs[u] for u, _ in speculative]
        for d in ddescs:
            self._ensure_blocks(d, d.seen_tokens + 1)
        for d, (_, chunk) in zip(pdescs, prefills):
            self._ensure_blocks(d, d.seen_tokens + len(chunk))
        for d, (_, chunk) in zip(sdescs, speculative):
            self._ensure_blocks(d, d.seen_tokens + len(chunk))
        return prefills, speculative, ddescs, pdescs, sdescs

    @atomic_on_reject
    def step(self, decode_uids: Sequence[int], decode_tokens: Sequence[int],
             prefills: Sequence[Tuple[int, Sequence[int]]] = (),
             speculative: Sequence[Tuple[int, Sequence[int]]] = ()):
        """One continuous-batching tick: every uid in ``decode_uids``
        advances one token and every ``(uid, chunk)`` in ``prefills``
        absorbs a prompt chunk (new uids start chunked prefill at position
        0; known uids continue where their last chunk stopped), in ONE
        device dispatch — the serving loop's per-tick program
        (inference/scheduler.py packs these against the token budget).

        ``speculative`` (ISSUE 8): ``(uid, [pending_token, d1..dk])`` rows
        for KNOWN uids — the pending decode input plus k drafter
        proposals, verified in the SAME dispatch via the extend path.
        Greedy acceptance: the row advances by the longest draft prefix
        matching the verifier's argmax chain plus the verifier's own next
        token (correction on a reject, bonus on a full accept); rejected
        drafts roll the paged-KV state back (written-token history, block
        refcounts, prefix-cache commit chain — see ``rewind``) before the
        commit, so the engine state after the tick is exactly as if only
        the accepted tokens had ever been decoded.

        Shapes are binned so a serving process compiles a bounded program
        set: decode/prefill/verify row counts and block-table widths round
        up a power-of-two ladder, chunk length rounds up the
        ``serving.chunk_bins`` ladder, verify width rounds up the
        ``serving.speculative.k_bins`` ladder (asserted in
        tests/test_serving_scheduler.py + tests/test_speculative.py).
        Admission is all-or-nothing BEFORE any state mutation, with errors
        naming needed-vs-free KV blocks and the offending uid; the
        admission charges every speculative row its FULL draft+verify
        width (worst case, all accepted).

        Returns ``(decode_logits [len(decode_uids), V], prefill_logits
        [len(prefills), V])`` — prefill logits are at each chunk's last
        token (argmax of a final chunk's row is the sequence's first
        generated token). With ``speculative`` rows the return is a
        3-tuple ``(decode_logits, prefill_logits, spec_results)`` where
        ``spec_results[i] = (accepted_count, emitted_tokens)`` for row i —
        ``emitted_tokens`` is the accepted drafts plus the verifier's
        correction/bonus token, every one of them exactly the greedy
        reference chain."""
        prefills, speculative, ddescs, pdescs, sdescs = self._admit_step(
            decode_uids, decode_tokens, prefills, speculative, "step()")

        if sdescs:
            return self._speculative_dispatch(
                decode_tokens, ddescs, prefills, pdescs, speculative, sdescs)

        V = self._mcfg.vocab_size
        dlogits = np.zeros((0, V), np.float32)
        plogits = np.zeros((0, V), np.float32)
        if ddescs and pdescs:
            with trace.span("serve/pack"):
                Bd, Wd, tok, pos, dtables = self._pack_decode(ddescs, decode_tokens)
                chunks = [(d, c) for d, (_, c) in zip(pdescs, prefills)]
                cmax = max(len(c) for _, c in chunks)
                Bp, C, Wp, ids, start, nnew, ptables = self._pack_chunks(
                    chunks, pad_chunk=self.config.serving.bin_chunk(cmax))
                fn = self._mixed_fn((Bd, Wd, Bp, C, Wp))
                ax = ()
                if self.adapters is not None:
                    ax = (self.adapters.device_operands(),
                          self._aslots(ddescs, Bd), self._aslots(pdescs, Bp))
            key = ("mixed", Bd, Wd, Bp, C, Wp)
            with trace.span("serve/launch", program=_program_name(key)):
                self.cache, dl, pl = self._pop_moe(
                    fn(self.params, self.cache, tok, pos,
                       dtables, ids, start, nnew, ptables, *ax))
            self._program_keys.add(key)
            with trace.span("serve/readback"):
                dlogits, plogits = np.asarray(dl), np.asarray(pl)
        elif ddescs:
            with trace.span("serve/pack"):
                Bd, Wd, tok, pos, dtables = self._pack_decode(ddescs, decode_tokens)
                fn = self._paged_decode_fn(Bd)
                ax = self._aargs(ddescs, Bd)
            key = ("decode", Bd, Wd)
            with trace.span("serve/launch", program=_program_name(key)):
                self.cache, dl = self._pop_moe(
                    fn(self.params, self.cache, tok, pos, dtables, *ax))
            self._program_keys.add(key)
            with trace.span("serve/readback"):
                dlogits = np.asarray(dl)
        elif pdescs:
            with trace.span("serve/pack"):
                chunks = [(d, c) for d, (_, c) in zip(pdescs, prefills)]
                cmax = max(len(c) for _, c in chunks)
                Bp, C, Wp, ids, start, nnew, ptables = self._pack_chunks(
                    chunks, pad_chunk=self.config.serving.bin_chunk(cmax))
                fn = self._extend_fn((Bp, C))
                ax = self._aargs(pdescs, Bp)
            key = ("extend", Bp, C, Wp)
            with trace.span("serve/launch", program=_program_name(key)):
                self.cache, pl = self._pop_moe(
                    fn(self.params, self.cache, ids, start, nnew,
                       ptables, *ax))
            self._program_keys.add(key)
            with trace.span("serve/readback"):
                plogits = np.asarray(pl)
        else:
            return dlogits, plogits
        self.dispatch_count += 1

        for i, d in enumerate(ddescs):
            d.seen_tokens += 1
            d.tokens.append(int(decode_tokens[i]))
            d.last_logits = dlogits[i]
            self._commit(d)
        for i, (d, (_, chunk)) in enumerate(zip(pdescs, prefills)):
            d.seen_tokens += len(chunk)
            d.tokens.extend(chunk)
            d.last_logits = plogits[i]
            self._commit(d)
        return dlogits[:len(ddescs)], plogits[:len(pdescs)]

    def _speculative_dispatch(self, decode_tokens, ddescs, prefills, pdescs,
                              speculative, sdescs):
        """The spec-lane tail of step(): pack all three lanes, run ONE
        ``_spec_step_impl`` dispatch, then apply acceptance — advance each
        verify row by its full chunk, rewind the rejected suffix, commit,
        and hand back ``(accepted, emitted_tokens)`` per row."""
        sv = self.config.serving
        V = self._mcfg.vocab_size
        dops = pops = sops = ()
        Bd = Wd = Bp = C = Wp = 0
        lora = self.adapters is not None
        with trace.span("serve/pack"):
            if ddescs:
                Bd, Wd, tok, pos, dtables = self._pack_decode(ddescs,
                                                              decode_tokens)
                dops = (tok, pos, dtables)
                if lora:
                    dops += (self._aslots(ddescs, Bd),)
            if pdescs:
                chunks = [(d, c) for d, (_, c) in zip(pdescs, prefills)]
                cmax = max(len(c) for _, c in chunks)
                Bp, C, Wp, ids, start, nnew, ptables = self._pack_chunks(
                    chunks, pad_chunk=sv.bin_chunk(cmax))
                pops = (ids, start, nnew, ptables)
                if lora:
                    pops += (self._aslots(pdescs, Bp),)
            schunks = [(d, c) for d, (_, c) in zip(sdescs, speculative)]
            # verify width off the k ladder: a row carrying j drafts is j+1
            # tokens; pad to bin_k(max j) + 1 so the warmed server's verify
            # programs stay bounded exactly like chunk lengths do
            kmax = max(len(c) for _, c in schunks) - 1
            Bs, Cs, Ws, sids, sstart, snnew, stables = self._pack_chunks(
                schunks, pad_chunk=sv.speculative.bin_k(kmax) + 1)
            sops = (sids, sstart, snnew, stables)
            if lora:
                sops += (self._aslots(sdescs, Bs),)

            key = ("spec", Bd, Wd, Bp, C, Wp, Bs, Cs, Ws)
            fn = self._spec_fn(key)
        with trace.span("serve/launch", program=_program_name(key)):
            self.cache, dl, pl, sres = self._pop_moe(fn(
                self.params, self.cache, dops, pops, sops,
                *((self.adapters.device_operands(),) if lora else ())))
        self.dispatch_count += 1
        self._program_keys.add(key)
        with trace.span("serve/readback"):
            dlogits = (np.asarray(dl) if dl is not None
                       else np.zeros((0, V), np.float32))
            plogits = (np.asarray(pl) if pl is not None
                       else np.zeros((0, V), np.float32))
            ver, accepted, slast = (np.asarray(x) for x in sres)

        for i, d in enumerate(ddescs):
            d.seen_tokens += 1
            d.tokens.append(int(decode_tokens[i]))
            d.last_logits = dlogits[i]
            self._commit(d)
        for i, (d, (_, chunk)) in enumerate(zip(pdescs, prefills)):
            d.seen_tokens += len(chunk)
            d.tokens.extend(chunk)
            d.last_logits = plogits[i]
            self._commit(d)
        spec_results = []
        for i, (d, chunk) in enumerate(schunks):
            n, a = len(chunk), int(accepted[i])
            d.seen_tokens += n
            d.tokens.extend(chunk)
            # keep [pending_token, d1..da]; roll back the n-1-a rejected
            # draft slots BEFORE the commit so the content registry never
            # sees a rejected token
            if a < n - 1:
                self._rewind(d, d.seen_tokens - (n - 1 - a))
            d.last_logits = slast[i]
            self._commit(d)
            spec_results.append((a, chunk[1:1 + a] + [int(ver[i, a])]))
        return dlogits[:len(ddescs)], plogits[:len(pdescs)], spec_results

    # -- one-dispatch sampling (ISSUE 16) ------------------------------
    # The sampled serving tick: temperature/top-k/top-p (greedy as the
    # temp=0 degenerate case) runs INSIDE the mixed/spec step programs, so
    # the host receives int32 tokens + bool EOS flags and logits never
    # cross to the host. Every sampling knob is a traced per-row
    # operand, so the warmed server's program-key ladder is the SAME one
    # the greedy step compiles — a greedy/sampled mix in one tick is one
    # program. Randomness is the Gumbel-max coupling
    # ``argmax(filtered/T + gumbel(fold_in(PRNGKey(seed), position)))``
    # with ``position`` the token's ABSOLUTE sequence index, computed
    # in-dispatch from operands the tick already carries (decode: dpos+1,
    # prefill finish: pstart+pnnew, verify slot j: sstart+j+1) — the chain
    # is a pure function of (seed, position, distribution), hence
    # bit-exactly replayable across batch composition, preemption,
    # failover re-prefill, and speculative verification.

    def configure_sampling(self, uid: int, params) -> None:
        """Attach per-request ``SamplingParams`` to ``uid``. Live uids
        update in place; unknown uids are registered pending and picked up
        when their first prefill chunk creates the descriptor. ``None``
        restores the greedy/no-EOS default."""
        desc = self._seqs.get(uid)
        if desc is not None:
            desc.sampling = params
        elif params is None:
            self._pending_sampling.pop(uid, None)
        else:
            self._pending_sampling[uid] = params

    def configure_adapter(self, uid: int, adapter_id: Optional[str]) -> None:
        """Bind ``adapter_id`` to ``uid`` — the ``configure_sampling``
        shape (ISSUE 18). Unknown uids register a PENDING binding consumed
        when admission creates the descriptor (that is where the pool slot
        is pinned, under the tick's atomic admission); live uids rebind in
        place, acquiring the new adapter before releasing the old so a
        failed acquire changes nothing. ``None`` restores the base model
        (null slot 0)."""
        desc = self._seqs.get(uid)
        if desc is None:
            if adapter_id is None:
                self._pending_adapter.pop(uid, None)
                return
            if self.adapters is None:
                raise RuntimeError(
                    "configure_adapter: adapters are disabled (set "
                    "adapters.enabled in the inference config)")
            if not self.adapters.registered(adapter_id):
                raise KeyError(
                    f"configure_adapter: {adapter_id!r} is not registered "
                    f"— publish it first")
            self._pending_adapter[uid] = adapter_id
            return
        if adapter_id == desc.adapter_id:
            return
        if adapter_id is not None:
            if self.adapters is None:
                raise RuntimeError(
                    "configure_adapter: adapters are disabled (set "
                    "adapters.enabled in the inference config)")
            slot = self.adapters.acquire(adapter_id)
        else:
            slot = 0
        if desc.adapter_id is not None:
            self.adapters.release(desc.adapter_id)
        desc.adapter_id, desc.adapter_slot = adapter_id, slot

    def _sampling_operands(self, descs, B: int):
        """Per-row traced sampling operands, padded to the binned batch:
        (seeds u32, temperature f32, top_k i32 0=off, top_p f32,
        eos i32 -1=off). Padding rows are greedy with EOS off, so they
        sample nothing and can never flag done."""
        seeds = np.zeros((B,), np.uint32)
        temps = np.zeros((B,), np.float32)
        topks = np.zeros((B,), np.int32)
        topps = np.ones((B,), np.float32)
        eos = np.full((B,), -1, np.int32)
        for i, d in enumerate(descs):
            sp = d.sampling
            if sp is None:
                continue
            seeds[i] = np.uint32(sp.seed)
            temps[i] = sp.temperature
            topks[i] = sp.top_k
            topps[i] = sp.top_p
            eos[i] = sp.eos_token_id
        return seeds, temps, topks, topps, eos

    def _lane_masks(self, descs, tails, B: int):
        """Constrained-decoding plane for one lane: [B, V] bool (True =
        allowed), or None when no row constrains. Each masked row's
        ``logit_mask(history)`` callable sees the FULL consumed history —
        the descriptor's written tokens plus this tick's new tokens
        (``tails[i]``: the pending decode token, or the prefill chunk) —
        and must allow at least one token."""
        if not any(d.sampling is not None and d.sampling.logit_mask is not None
                   for d in descs):
            return None
        V = self._mcfg.vocab_size
        m = np.ones((B, V), bool)
        for i, (d, tail) in enumerate(zip(descs, tails)):
            sp = d.sampling
            if sp is None or sp.logit_mask is None:
                continue
            row = np.asarray(sp.logit_mask(list(d.tokens) + list(tail)),
                             dtype=bool)
            if row.shape != (V,):
                raise ValueError(
                    f"logit_mask for uid {d.uid} returned shape {row.shape}, "
                    f"want ({V},)")
            if not row.any():
                raise ValueError(
                    f"logit_mask for uid {d.uid} allows no tokens — a "
                    "constrained row must keep at least one candidate")
            m[i] = row
        return m

    def _sampled_fn(self, key, impl):
        fn = self._mixed_cache.get(key)
        if fn is not None:
            return fn
        import jax

        fn = jax.jit(impl, donate_argnums=_donate_cache())
        self._mixed_cache[key] = fn
        return fn

    def _assert_on_device_sampling(self, key, outs) -> None:
        """The no-logits-to-host proof: every leaf a sampled dispatch
        returns must be token/flag-shaped — nothing with a vocab-sized
        trailing dim may cross to host. Records the avals per program key
        so tests can audit the full set."""
        import jax

        V = self._mcfg.vocab_size
        shapes = tuple(tuple(int(s) for s in x.shape)
                       for x in jax.tree_util.tree_leaves(outs))
        for s in shapes:
            assert not (s and s[-1] == V), (
                f"sampled step {key} ships a vocab-shaped output {s} to "
                "host — sampling must stay in-dispatch")
        self.sampled_output_shapes[key] = shapes

    def _mixed_sampled_impl(self, params, cache: PagedKVCache, dtok, dpos,
                            dtables, dsp, dmask, pids, pstart, pnnew,
                            ptables, psp, pmask, apool=None, daslots=None,
                            paslots=None):
        """The mixed step with the sampler fused at the head: identical
        trunk to ``_mixed_step_impl`` (same layer scan, same gather-last
        head projections), then ``seeded_tokens`` per lane. Returns
        (cache, decode_tokens [Bd], decode_eos [Bd], prefill_tokens [Bp],
        prefill_eos [Bp]) — int32/bool only, never [*, V]."""
        from .sampling import seeded_tokens

        out = self._mixed_step_impl(
            params, cache, dtok, dpos, dtables, pids, pstart, pnnew, ptables,
            apool=apool, daslots=daslots, paslots=paslots)
        cache, dlogits, plogits = out[:3]
        dseeds, dtemp, dtk, dtp, deos = dsp
        pseeds, ptemp, ptk, ptp, peos = psp
        # decode row emits the token at absolute index dpos+1 (dpos is the
        # slot the input token writes); a finished prefill's first
        # generated token sits at pstart+pnnew
        dtoks = seeded_tokens(dlogits, dseeds, dpos + 1, dtemp, dtk, dtp,
                              mask=dmask)
        ptoks = seeded_tokens(plogits, pseeds, pstart + pnnew, ptemp, ptk,
                              ptp, mask=pmask)
        ddone = (dtoks == deos) & (deos >= 0)
        pdone = (ptoks == peos) & (peos >= 0)
        return (cache, dtoks, ddone, ptoks, pdone) + out[3:]

    def _decode_sampled_impl(self, params, cache: PagedKVCache, dtok, dpos,
                             dtables, dsp, dmask, apool=None, daslots=None):
        from .sampling import seeded_tokens

        out = self._paged_decode_impl(params, cache, dtok, dpos,
                                      dtables, apool=apool,
                                      aslots=daslots)
        cache, dlogits = out[:2]
        dseeds, dtemp, dtk, dtp, deos = dsp
        dtoks = seeded_tokens(dlogits, dseeds, dpos + 1, dtemp, dtk, dtp,
                              mask=dmask)
        ddone = (dtoks == deos) & (deos >= 0)
        return (cache, dtoks, ddone) + out[2:]

    def _extend_sampled_impl(self, params, cache: PagedKVCache, pids, pstart,
                             pnnew, ptables, psp, pmask, apool=None,
                             paslots=None):
        from .sampling import seeded_tokens

        out = self._extend_impl(params, cache, pids, pstart,
                                pnnew, ptables, apool=apool,
                                aslots=paslots)
        cache, plogits = out[:2]
        pseeds, ptemp, ptk, ptp, peos = psp
        ptoks = seeded_tokens(plogits, pseeds, pstart + pnnew, ptemp, ptk,
                              ptp, mask=pmask)
        pdone = (ptoks == peos) & (peos >= 0)
        return (cache, ptoks, pdone) + out[2:]

    def _spec_sampled_impl(self, params, cache: PagedKVCache, dops, pops,
                           sops, dsp, psp, ssp, dmask, pmask, apool=None):
        """The speculative mixed step generalized to TRUE speculative
        sampling: the verify lane evaluates the seeded sampling chain
        ``st[j] = seeded_tokens(logits_after_j, seed, sstart+j+1)`` at
        EVERY chunk position and accepts the longest draft prefix that
        MATCHES the chain. Our drafters are deterministic (point-mass
        proposals), for which Gumbel-coupled chain-matching IS the
        Leviathan accept/residual-resample rule: a draft is accepted iff
        the target chain would have emitted it, and the first rejected
        slot's chain token is exactly the residual resample. The emitted
        tokens are therefore the seeded chain itself — bit-identical with
        speculation on or off, at any k, greedy or sampled. Returns
        (cache, (dtoks, ddone) | None, (ptoks, pdone) | None,
        (chain [Bs, Cs] i32, accepted [Bs])) — the [Bs, Cs, V] verify
        logits never leave the device (the greedy path ships last_logits
        [Bs, V]; this path ships nothing vocab-shaped at all)."""
        import jax
        import jax.numpy as jnp

        from .sampling import seeded_tokens

        dops, pops, sops = tuple(dops), tuple(pops), tuple(sops)
        dslots = pslots = sslots = None
        xd = xp = xs = None
        cos = sin = None
        if dops:
            if apool is not None:
                dtok, dpos, dtables, dslots = dops
            else:
                dtok, dpos, dtables = dops
            xd, (cos, sin), _ = self._embed_at(params, dtok[:, None], dpos)
        if pops:
            if apool is not None:
                pids, pstart, pnnew, ptables, pslots = pops
            else:
                pids, pstart, pnnew, ptables = pops
            xp, (cos, sin), ppos = self._embed_at(params, pids, pstart)
        if apool is not None:
            sids, sstart, snnew, stables, sslots = sops
        else:
            sids, sstart, snnew, stables = sops
        xs, (cos, sin), spos = self._embed_at(params, sids, sstart)

        def layer_fn(carry, layer_and_cache):
            hd, hp, hs = carry
            lw, ck, cv = layer_and_cache[:3]
            ap = None if apool is None else layer_and_cache[3]
            tap = self._moe_arm()
            if hd is not None:
                hd, (ck, cv) = self._decode_layer(
                    lw, hd, ck, cv, cos, sin, dpos, dtables,
                    lora=None if ap is None else (ap, dslots))
            if hp is not None:
                hp, (ck, cv) = self._extend_layer(
                    lw, hp, ck, cv, cos, sin, ppos, pstart, pnnew, ptables,
                    lora=None if ap is None else (ap, pslots))
            hs, (ck, cv) = self._extend_layer(
                lw, hs, ck, cv, cos, sin, spos, sstart, snnew, stables,
                lora=None if ap is None else (ap, sslots))
            return (hd, hp, hs), (ck, cv) + self._moe_ys(tap)

        (xd, xp, xs), ys = jax.lax.scan(
            layer_fn, (xd, xp, xs), (params["layers"],) + self._kv_xs(cache)
            + self._apool_xs(apool))
        kp, vp = ys[0], ys[1]
        dres = pres = None
        if dops:
            dlogits = self.model.head(params, xd)[:, 0]
            dseeds, dtemp, dtk, dtp, deos = dsp
            dtoks = seeded_tokens(dlogits, dseeds, dpos + 1, dtemp, dtk,
                                  dtp, mask=dmask)
            dres = (dtoks, (dtoks == deos) & (deos >= 0))
        if pops:
            x_last = jnp.take_along_axis(
                xp, (pnnew - 1)[:, None, None].astype(jnp.int32), axis=1)
            plogits = self.model.head(params, x_last)[:, 0]
            pseeds, ptemp, ptk, ptp, peos = psp
            ptoks = seeded_tokens(plogits, pseeds, pstart + pnnew, ptemp,
                                  ptk, ptp, mask=pmask)
            pres = (ptoks, (ptoks == peos) & (peos >= 0))
        slog = self.model.head(params, xs)          # [Bs, Cs, V], on device
        Bs, Cs = sids.shape
        sseeds, stemp, stk, stp, _ = ssp
        spositions = sstart[:, None] + jnp.arange(Cs)[None, :] + 1
        bc = lambda a: jnp.broadcast_to(a[:, None], (Bs, Cs))  # noqa: E731
        chain = seeded_tokens(slog, bc(sseeds), spositions, bc(stemp),
                              bc(stk), bc(stp))
        nxt = jnp.concatenate(
            [sids[:, 1:], jnp.zeros((Bs, 1), sids.dtype)], axis=1)
        j = jnp.arange(Cs)[None, :]
        m = jnp.where(j < (snnew - 1)[:, None], chain == nxt, False)
        accepted = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1),
                           axis=1)                   # [Bs] in [0, k]
        return (self._cache_of(kp, vp), dres, pres,
                (chain, accepted)) + tuple(ys[2:])

    @atomic_on_reject
    def step_sampled(self, decode_uids: Sequence[int],
                     decode_tokens: Sequence[int],
                     prefills: Sequence[Tuple[int, Sequence[int]]] = (),
                     speculative: Sequence[Tuple[int, Sequence[int]]] = ()):
        """step() with sampling fused into the dispatch: same lanes, same
        admission, same shape-bin ladder — but the return is tokens and
        EOS flags, never logits. Per-uid behavior comes off the
        descriptor's ``SamplingParams`` (``configure_sampling``); uids
        without one run greedy with EOS off, bit-identical to step()'s
        argmax chain.

        Returns ``(decode_tokens [nd], decode_eos [nd], prefill_tokens
        [np], prefill_eos [np])`` int32/bool — prefill entries are only
        meaningful on a sequence's FINAL chunk (mid-prompt chunks sample a
        position the prompt will overwrite; callers ignore them, exactly
        as they ignored mid-chunk logits). With ``speculative`` rows a
        5-tuple appends ``spec_results[i] = (accepted, emitted_tokens)``
        where every emitted token is the row's seeded chain (EOS inside
        the emitted list is the caller's host-side cut — the flags here
        cover the single-token lanes). Commits set ``last_logits = None``:
        a sampled sequence has no host logits by design, and anything that
        silently assumed them fails loudly instead of reading stale rows.

        Constrained rows (``SamplingParams.logit_mask``) dispatch masked
        program variants (distinct ``*_m`` program keys) and are rejected
        from the speculative lane — the mask changes the target chain
        mid-flight, which drafters can't see."""
        prefills, speculative, ddescs, pdescs, sdescs = self._admit_step(
            decode_uids, decode_tokens, prefills, speculative,
            "step_sampled()")
        for d in sdescs:
            if d.sampling is not None and d.sampling.logit_mask is not None:
                raise ValueError(
                    f"speculative uid {d.uid} carries a logit_mask — "
                    "constrained sequences must decode one token at a time "
                    "(schedule it in decode_uids)")
        if sdescs:
            return self._speculative_sampled_dispatch(
                decode_tokens, ddescs, prefills, pdescs, speculative, sdescs)

        nd, npre = len(ddescs), len(pdescs)
        dtoks = np.zeros((0,), np.int32)
        ddone = np.zeros((0,), bool)
        ptoks = np.zeros((0,), np.int32)
        pdone = np.zeros((0,), bool)
        if ddescs and pdescs:
            with trace.span("serve/pack"):
                Bd, Wd, tok, pos, dtables = self._pack_decode(ddescs,
                                                              decode_tokens)
                chunks = [(d, c) for d, (_, c) in zip(pdescs, prefills)]
                cmax = max(len(c) for _, c in chunks)
                Bp, C, Wp, ids, start, nnew, ptables = self._pack_chunks(
                    chunks, pad_chunk=self.config.serving.bin_chunk(cmax))
                dsp = self._sampling_operands(ddescs, Bd)
                psp = self._sampling_operands(pdescs, Bp)
                dmask = self._lane_masks(ddescs, [[t] for t in decode_tokens], Bd)
                pmask = self._lane_masks(pdescs, [c for _, c in prefills], Bp)
                masked = dmask is not None or pmask is not None
                key = (("mixed_m" if masked else "mixed"), Bd, Wd, Bp, C, Wp)
                fn = self._sampled_fn(("s",) + key, self._mixed_sampled_impl)
                ax = ()
                if self.adapters is not None:
                    ax = (self.adapters.device_operands(),
                          self._aslots(ddescs, Bd), self._aslots(pdescs, Bp))
            with trace.span("serve/launch", program=_program_name(("s",) + key)):
                self.cache, dt, dd, pt, pd = self._pop_moe(fn(
                    self.params, self.cache, tok, pos, dtables, dsp, dmask,
                    ids, start, nnew, ptables, psp, pmask, *ax))
            self._assert_on_device_sampling(key, (dt, dd, pt, pd))
            self._program_keys.add(key)
            with trace.span("serve/readback"):
                dtoks, ddone = np.asarray(dt), np.asarray(dd)
                ptoks, pdone = np.asarray(pt), np.asarray(pd)
        elif ddescs:
            with trace.span("serve/pack"):
                Bd, Wd, tok, pos, dtables = self._pack_decode(ddescs,
                                                              decode_tokens)
                dsp = self._sampling_operands(ddescs, Bd)
                dmask = self._lane_masks(ddescs, [[t] for t in decode_tokens], Bd)
                key = (("decode_m" if dmask is not None else "decode"), Bd, Wd)
                fn = self._sampled_fn(("s",) + key, self._decode_sampled_impl)
                ax = self._aargs(ddescs, Bd)
            with trace.span("serve/launch", program=_program_name(("s",) + key)):
                self.cache, dt, dd = self._pop_moe(
                    fn(self.params, self.cache, tok, pos, dtables, dsp, dmask,
                       *ax))
            self._assert_on_device_sampling(key, (dt, dd))
            self._program_keys.add(key)
            with trace.span("serve/readback"):
                dtoks, ddone = np.asarray(dt), np.asarray(dd)
        elif pdescs:
            with trace.span("serve/pack"):
                chunks = [(d, c) for d, (_, c) in zip(pdescs, prefills)]
                cmax = max(len(c) for _, c in chunks)
                Bp, C, Wp, ids, start, nnew, ptables = self._pack_chunks(
                    chunks, pad_chunk=self.config.serving.bin_chunk(cmax))
                psp = self._sampling_operands(pdescs, Bp)
                pmask = self._lane_masks(pdescs, [c for _, c in prefills], Bp)
                key = (("extend_m" if pmask is not None else "extend"), Bp, C, Wp)
                fn = self._sampled_fn(("s",) + key, self._extend_sampled_impl)
                ax = self._aargs(pdescs, Bp)
            with trace.span("serve/launch", program=_program_name(("s",) + key)):
                self.cache, pt, pd = self._pop_moe(
                    fn(self.params, self.cache, ids, start,
                       nnew, ptables, psp, pmask, *ax))
            self._assert_on_device_sampling(key, (pt, pd))
            self._program_keys.add(key)
            with trace.span("serve/readback"):
                ptoks, pdone = np.asarray(pt), np.asarray(pd)
        else:
            return dtoks, ddone, ptoks, pdone
        self.dispatch_count += 1

        for i, d in enumerate(ddescs):
            d.seen_tokens += 1
            d.tokens.append(int(decode_tokens[i]))
            d.last_logits = None
            self._commit(d)
        for i, (d, (_, chunk)) in enumerate(zip(pdescs, prefills)):
            d.seen_tokens += len(chunk)
            d.tokens.extend(chunk)
            d.last_logits = None
            self._commit(d)
        return dtoks[:nd], ddone[:nd], ptoks[:npre], pdone[:npre]

    def _speculative_sampled_dispatch(self, decode_tokens, ddescs, prefills,
                                      pdescs, speculative, sdescs):
        """The spec-lane tail of step_sampled(): pack all three lanes plus
        their sampling operands, run ONE ``_spec_sampled_impl`` dispatch,
        apply chain-match acceptance, rewind rejected draft KV, and emit
        the seeded chain per row."""
        sv = self.config.serving
        lora = self.adapters is not None
        dops = pops = ()
        dsp = psp = ()
        dmask = pmask = None
        Bd = Wd = Bp = C = Wp = 0
        with trace.span("serve/pack"):
            if ddescs:
                Bd, Wd, tok, pos, dtables = self._pack_decode(ddescs,
                                                              decode_tokens)
                dops = (tok, pos, dtables)
                if lora:
                    dops += (self._aslots(ddescs, Bd),)
                dsp = self._sampling_operands(ddescs, Bd)
                dmask = self._lane_masks(ddescs, [[t] for t in decode_tokens], Bd)
            if pdescs:
                chunks = [(d, c) for d, (_, c) in zip(pdescs, prefills)]
                cmax = max(len(c) for _, c in chunks)
                Bp, C, Wp, ids, start, nnew, ptables = self._pack_chunks(
                    chunks, pad_chunk=sv.bin_chunk(cmax))
                pops = (ids, start, nnew, ptables)
                if lora:
                    pops += (self._aslots(pdescs, Bp),)
                psp = self._sampling_operands(pdescs, Bp)
                pmask = self._lane_masks(pdescs, [c for _, c in prefills], Bp)
            schunks = [(d, c) for d, (_, c) in zip(sdescs, speculative)]
            kmax = max(len(c) for _, c in schunks) - 1
            Bs, Cs, Ws, sids, sstart, snnew, stables = self._pack_chunks(
                schunks, pad_chunk=sv.speculative.bin_k(kmax) + 1)
            sops = (sids, sstart, snnew, stables)
            if lora:
                sops += (self._aslots(sdescs, Bs),)
            ssp = self._sampling_operands(sdescs, Bs)

            masked = dmask is not None or pmask is not None
            key = (("spec_m" if masked else "spec"),
                   Bd, Wd, Bp, C, Wp, Bs, Cs, Ws)
            fn = self._sampled_fn(("s",) + key, self._spec_sampled_impl)
        with trace.span("serve/launch", program=_program_name(("s",) + key)):
            self.cache, dres, pres, sres = self._pop_moe(fn(
                self.params, self.cache, dops, pops, sops, dsp, psp, ssp,
                dmask, pmask,
                *((self.adapters.device_operands(),) if lora else ())))
        self.dispatch_count += 1
        self._assert_on_device_sampling(key, (dres, pres, sres))
        self._program_keys.add(key)
        with trace.span("serve/readback"):
            if dres is not None:
                dtoks, ddone = np.asarray(dres[0]), np.asarray(dres[1])
            else:
                dtoks, ddone = np.zeros((0,), np.int32), np.zeros((0,), bool)
            if pres is not None:
                ptoks, pdone = np.asarray(pres[0]), np.asarray(pres[1])
            else:
                ptoks, pdone = np.zeros((0,), np.int32), np.zeros((0,), bool)
            chain, accepted = (np.asarray(x) for x in sres)

        for i, d in enumerate(ddescs):
            d.seen_tokens += 1
            d.tokens.append(int(decode_tokens[i]))
            d.last_logits = None
            self._commit(d)
        for i, (d, (_, chunk)) in enumerate(zip(pdescs, prefills)):
            d.seen_tokens += len(chunk)
            d.tokens.extend(chunk)
            d.last_logits = None
            self._commit(d)
        spec_results = []
        for i, (d, chunk) in enumerate(schunks):
            n, a = len(chunk), int(accepted[i])
            d.seen_tokens += n
            d.tokens.extend(chunk)
            if a < n - 1:
                self._rewind(d, d.seen_tokens - (n - 1 - a))
            d.last_logits = None
            self._commit(d)
            spec_results.append((a, chunk[1:1 + a] + [int(chain[i, a])]))
        return (dtoks[:len(ddescs)], ddone[:len(ddescs)],
                ptoks[:len(pdescs)], pdone[:len(pdescs)], spec_results)

    # -- fused multi-token decode --------------------------------------

    def _decode_loop_fn(self, key):
        fn = self._loop_cache.get(key) if hasattr(self, "_loop_cache") else None
        if fn is not None:
            return fn
        if not hasattr(self, "_loop_cache"):
            self._loop_cache = {}
        import jax

        B, n_steps = key

        def impl(params, cache, tok, pos, btables, apool=None, aslots=None):
            import jax.numpy as jnp

            def step(carry, _):
                cache, tok, pos, _ = carry
                out = self._paged_decode_impl(params, cache, tok,
                                              pos, btables,
                                              apool=apool,
                                              aslots=aslots)
                cache, logits = out[:2]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (cache, nxt, pos + 1, logits), (nxt,) + out[2:]

            logits0 = jnp.zeros((B, self._mcfg.vocab_size), jnp.float32)
            (cache, _, _, logits), ys = jax.lax.scan(
                step, (cache, tok, pos, logits0), None, length=n_steps)
            # ys[0] [n_steps, B] tokens; a trailing MoE-counts element
            # (stacked [n_steps, L, E]) rides when MoE serving is on
            return (cache, ys[0].T, logits) + tuple(ys[1:])

        fn = jax.jit(impl, donate_argnums=_donate_cache())
        self._loop_cache[key] = fn
        return fn

    @atomic_on_reject
    def decode_loop(self, uids: Sequence[int], tokens: Sequence[int],
                    n_steps: int) -> np.ndarray:
        """Greedy-decode ``n_steps`` tokens for known uids in ONE device
        program (a ``lax.scan`` over the paged decode step with on-device
        argmax feedback). The host sees a single dispatch, so per-token
        latency is the ENGINE's, not the host round trip — the
        serving-latency isolation the per-``put`` API number can't give
        (each put() is a host RTT). Returns the generated tokens
        [len(uids), n_steps]; descriptors advance as if put() had run
        n_steps times.

        The reference's FastGen equivalent is host-looped puts
        (inference/v2/engine_v2.py:107) — on TPU the fused loop is the
        shape a serving process should prefer for long generations."""
        self._require_resident(uids, "decode_loop()")
        descs = [self._seqs[u] for u in uids]
        # Admission control BEFORE any mutation (same contract as put():
        # a rejected call leaves allocator + descriptors untouched), via
        # _admission_detail so the copy-on-write surcharge for shared
        # write-span blocks (forked tails) is budgeted too — a bare
        # blocks_needed count would admit, then fail mid-COW with earlier
        # descriptors already cloned. The length cap matters doubly here —
        # in-jit btable indexing clamps instead of erroring, so an overrun
        # would silently write another sequence's KV blocks.
        ok, _, why = self._admission_detail(uids, [n_steps] * len(uids))
        if not ok:
            raise RuntimeError(f"cannot schedule decode_loop: {why}")
        for d in descs:
            self._ensure_blocks(d, d.seen_tokens + n_steps)
        # binned table width (round 9): the decode kernels stream every
        # table entry's block, so a max_seq_len-wide table reads ~3x the
        # live KV at typical fills — width covers exactly the blocks this
        # loop can touch, rounded up a power of two to bound compiles
        W = self._binned_width(max(len(d.blocks) for d in descs))
        btables = np.stack([self._table(d, W) for d in descs]).astype(np.int32)
        pos = np.asarray([d.seen_tokens for d in descs], np.int32)
        tok0 = np.asarray(tokens, np.int32)
        fn = self._decode_loop_fn((len(uids), int(n_steps)))
        self.cache, toks, last_logits = self._pop_moe(
            fn(self.params, self.cache, tok0, pos, btables,
               *self._aargs(descs, len(uids))))
        self.dispatch_count += 1
        self._program_keys.add(("decode_loop", len(uids), int(n_steps), W))
        last_logits = np.asarray(last_logits)
        toks = np.asarray(toks)
        for i, d in enumerate(descs):
            d.seen_tokens += n_steps
            # written KV slots: the seed token plus every generated token
            # except the last (which has logits but no KV entry yet)
            d.tokens.append(int(tok0[i]))
            d.tokens.extend(int(t) for t in toks[i, :-1])
            d.last_logits = last_logits[i]
            self._commit(d)
        return toks

    # -- disaggregated prefill/decode: block export / import -----------
    # (ISSUE 7: the PagedKVCache block IS the wire format — a prefill
    # worker exports a finished sequence's blocks, the transfer substrate
    # moves the bytes (serving/disagg.py stages them through the AIO
    # pinned-buffer pool), and a decode worker imports them under an
    # admission handshake: blocks are acquired BEFORE any payload bytes
    # move, atomic-on-reject with _admission_detail-named errors.)

    def export_kv_blocks(self, uid: int) -> "KVBlockPayload":
        """Snapshot ``uid``'s written KV blocks + host state for a
        disaggregated transfer. The payload arrays are the pool's OWN
        storage layout ([L, nb, KV, block, Dh] data, [L, nb, KV, block]
        scale planes for quantized pools) pulled to host — bf16 pools
        round-trip bit-exactly, quantized pools byte-exactly (payload and
        scales are copied, never re-quantized). The source sequence stays
        live; the caller flushes it when the handoff is done."""
        desc = self._seqs.get(uid)
        if desc is None:
            raise ValueError(f"unknown uid {uid}")
        bs = self.cache.block_size
        nb = blocks_needed(desc.seen_tokens, bs)
        assert len(desc.blocks) >= nb, (uid, len(desc.blocks), nb)
        spilled = sorted(i for i in desc.spilled if i < nb)
        if not spilled:
            idx = np.asarray(desc.blocks[:nb], np.int32)
            planes = [np.asarray(p[:, idx]) for p in self._pool_planes()]
        else:
            # tiered compose (ISSUE 15): a parked sequence's payload is
            # assembled from BOTH tiers — resident positions gather pool
            # storage, spilled positions read the host tier's byte-exact
            # copy — so a failover KV-migration of a spilled sequence
            # ships the same bytes a fully-resident export would (no
            # fetch, no re-prefill, no re-quantization)
            resident = [i for i in range(nb) if i not in desc.spilled]
            idx = np.asarray([desc.blocks[i] for i in resident], np.int32)
            pool_planes = [np.asarray(p[:, idx])
                           for p in self._pool_planes()]
            tidx, tplanes = self.tier.load(uid, count=False)
            planes = []
            for pp, tp in zip(pool_planes, tplanes):
                full = np.empty((pp.shape[0], nb) + pp.shape[2:], pp.dtype)
                for j, i in enumerate(resident):
                    full[:, i] = pp[:, j]
                for j, i in enumerate(tidx):
                    if i < nb:
                        full[:, i] = tp[:, j]
                planes.append(full)
        quantized = self.cache.quantized
        return KVBlockPayload(
            uid=uid,
            tokens=list(desc.tokens),
            seen_tokens=desc.seen_tokens,
            last_logits=None if desc.last_logits is None
            else np.asarray(desc.last_logits),
            k=planes[0],
            v=planes[1],
            k_scale=planes[2] if quantized else None,
            v_scale=planes[3] if quantized else None,
            kv_cache_dtype=self.config.kv_cache_dtype,
            block_size=bs,
            weight_version=self.weight_version,
        )

    @atomic_on_reject
    def begin_import(self, uid: int, n_tokens: int) -> "ImportReservation":
        """The admission half of the disagg handshake: acquire the KV
        blocks a ``n_tokens``-token import needs BEFORE any payload bytes
        move. Atomic-on-reject — a refused reservation mutates nothing,
        and the error names needed-vs-free blocks via the same
        ``_admission_detail`` discipline as put()/step(). The transfer
        then either ``commit_import``s the payload into the reserved
        blocks or ``abort_import``s to release them."""
        if uid in self._seqs:
            raise ValueError(f"uid {uid} is already live")
        if n_tokens < 1:
            raise ValueError(f"import of {n_tokens} tokens")
        ok, _, why = self._admission_detail([uid], [n_tokens])
        if not ok:
            raise RuntimeError(f"cannot reserve KV import for uid {uid}: "
                               f"{why}")
        blocks = self.allocator.allocate(
            blocks_needed(n_tokens, self.cache.block_size))
        return ImportReservation(uid=uid, blocks=blocks,
                                 n_tokens=int(n_tokens))

    def abort_import(self, resv: "ImportReservation") -> None:
        """Release a reservation's blocks (transfer failed or was vetoed).
        Idempotent via the ``done`` flag so cleanup paths can call it
        unconditionally."""
        if not resv.done:
            resv.done = True
            self.allocator.free(resv.blocks)

    def _import_fn(self, nb: int, quantized: bool):
        key = ("import", nb, quantized)
        fn = self._mixed_cache.get(key)
        if fn is not None:
            return fn
        import jax

        from ..utils.placement import cache_safe_donate_argnums

        if quantized:
            def impl(cache, idx, k, v, ks, vs):
                return PagedKVCache(cache.k.at[:, idx].set(k),
                                    cache.v.at[:, idx].set(v),
                                    cache.k_scale.at[:, idx].set(ks),
                                    cache.v_scale.at[:, idx].set(vs))
        else:
            def impl(cache, idx, k, v):
                return PagedKVCache(cache.k.at[:, idx].set(k),
                                    cache.v.at[:, idx].set(v))
        # the pool is argument 0 here (no params operand), unlike the
        # layer-scan programs where it rides at 1
        fn = jax.jit(impl, donate_argnums=cache_safe_donate_argnums((0,)))
        self._mixed_cache[key] = fn
        return fn

    def commit_import(self, resv: "ImportReservation",
                      payload: "KVBlockPayload") -> None:
        """Write a transferred payload into the reserved blocks and bring
        the sequence live. Validates the wire format against THIS pool
        (block size, kv_cache_dtype, per-block shape) before touching
        device state — a mismatch raises with both sides named and the
        reservation still held, so the caller's cleanup path aborts it.
        The imported descriptor commits its full blocks to the prefix
        registry like any locally-prefilled sequence would (disagg
        requires identical weights fleet-wide for token parity, which is
        exactly the prefix cache's validity condition)."""
        if resv.done:
            raise RuntimeError(f"reservation for uid {resv.uid} already "
                               f"committed or aborted")
        if resv.uid in self._seqs:
            raise ValueError(f"uid {resv.uid} is already live")
        if payload.block_size != self.cache.block_size:
            raise ValueError(
                f"wire-format mismatch: payload blocks are "
                f"{payload.block_size} tokens, this pool's are "
                f"{self.cache.block_size}")
        if payload.kv_cache_dtype != self.config.kv_cache_dtype:
            raise ValueError(
                f"wire-format mismatch: payload kv_cache_dtype "
                f"{payload.kv_cache_dtype!r}, this pool stores "
                f"{self.config.kv_cache_dtype!r}")
        if (payload.weight_version is not None
                and payload.weight_version != self.weight_version):
            raise ValueError(
                f"weight-version mismatch: payload KV was computed under "
                f"version {payload.weight_version} but this engine serves "
                f"version {self.weight_version} — KV bytes are only valid "
                f"against the weights that wrote them (re-prefill instead)")
        if payload.seen_tokens != resv.n_tokens:
            raise ValueError(
                f"payload carries {payload.seen_tokens} tokens but the "
                f"reservation was for {resv.n_tokens}")
        nb = len(resv.blocks)
        want = (self.cache.k.shape[0], nb) + self.cache.k.shape[2:]
        if tuple(payload.k.shape) != want:
            raise ValueError(
                f"wire-format mismatch: payload k is "
                f"{tuple(payload.k.shape)}, this pool expects {want}")
        idx = np.asarray(resv.blocks, np.int32)
        quantized = self.cache.quantized
        fn = self._import_fn(nb, quantized)
        if quantized:
            self.cache = fn(self.cache, idx,
                            payload.k.astype(self.cache.k.dtype),
                            payload.v.astype(self.cache.v.dtype),
                            payload.k_scale, payload.v_scale)
        else:
            self.cache = fn(self.cache, idx,
                            payload.k.astype(self.cache.k.dtype),
                            payload.v.astype(self.cache.v.dtype))
        resv.done = True
        desc = SequenceDescriptor(
            uid=resv.uid, seen_tokens=payload.seen_tokens,
            blocks=list(resv.blocks),
            last_logits=None if payload.last_logits is None
            else np.asarray(payload.last_logits),
            tokens=list(payload.tokens))
        self._seqs[resv.uid] = desc
        self._commit(desc)

    # -- versioned weight swap (ISSUE 11: the RLHF train->serve flip) ---
    # The serving programs are weight-agnostic jitted functions, so a
    # weight swap is a pytree pointer flip: paged KV pools, block
    # allocator, and every compiled program survive it untouched (zero
    # recompiles across flips — tests/test_rlhf.py pins it). What a swap
    # MUST do is invalidate the prefix-cache content registry (keys hash
    # token history, not weights) and bar live mixed-weight sequences
    # from committing their blocks. Delivery is two-phase so a fleet
    # publish can crash between replicas and leave every one of them
    # serving the OLD weights (serving/router.py publish_weights).

    @atomic_on_reject(check="validate")
    def stage_weights(self, params, version: Optional[int] = None,
                      prepared: bool = False) -> None:
        """Phase 1 of the train->serve flip: cast/quantize/place the new
        tree into the staging slot without touching serving state. The
        prepare is the half that can fail (casts, device transfer,
        quantization); after it returns, ``commit_staged_weights`` is a
        host pointer swap. Validates the prepared tree's structure against
        the live one BEFORE staging, so a later commit cannot discover a
        mismatch mid-flip. ``prepared=True`` takes ``params`` as already
        run through ``_prepare_params`` — the router prepares ONCE per
        serving-transform key and hands the same placed tree to every
        replica (sharing the device buffers; the serving programs never
        donate the params operand)."""
        import jax

        placed = params if prepared else self._prepare_params(params)
        new_td = jax.tree_util.tree_structure(placed)
        old_td = jax.tree_util.tree_structure(self.params)
        if new_td != old_td:
            raise ValueError(
                "stage_weights: published tree structure does not match the "
                f"serving tree ({new_td} vs {old_td}) — publish the "
                "model-structured weights (engine.module_weights())")
        self._staged_weights = (placed,
                                None if version is None else int(version))

    def discard_staged_weights(self) -> None:
        """Drop an uncommitted staging slot (fleet-publish rollback path).
        Safe to call when nothing is staged."""
        self._staged_weights = None

    def commit_staged_weights(self, force: bool = False,
                              defer: bool = False) -> bool:
        """Phase 2 of the flip: move serving onto the staged tree.

        Live sequences hold KV computed under the OLD weights, so a commit
        under them would silently mix weights into their continuations.
        The guard ladder:

        - no live sequences: install immediately (the staged slot empties);
        - live + ``defer=True``: the staged tree becomes PENDING and is
          installed at the next tick boundary (``apply_pending_weights``,
          which the scheduler calls at tick entry after the in-flight tick
          has fully drained) — the router's delivery mode, safe to call
          while another thread is mid-tick;
        - live + ``force=True``: install NOW (the PR 2 hard-swap for
          callers that accept mid-episode approximation);
        - live + neither: refuse, keep the staged tree for a retry, and
          return False."""
        if self._staged_weights is None:
            raise RuntimeError("commit_staged_weights: nothing staged "
                               "(stage_weights first)")
        if self._seqs and not (force or defer):
            logger.warning(
                f"commit_staged_weights: {len(self._seqs)} live sequences "
                "hold KV from the current weights; refusing the swap (drain "
                "or flush() them, or pass force=True / defer=True)")
            return False
        if self._seqs and defer and not force:
            self._pending_weights = self._staged_weights
            self._staged_weights = None
            return True
        staged, self._staged_weights = self._staged_weights, None
        self._install_weights(*staged)
        return True

    def _install_weights(self, placed, version: Optional[int]) -> None:
        """The actual swap: flip the params pointer, stamp the version,
        and invalidate everything that silently assumed weight identity —
        the content index points at KV computed under the OLD weights
        (keys are pure functions of token history, so a post-swap
        admission hashing the same system prompt would reuse stale KV),
        and live sequences carry mixed-weight KV that must never enter
        the registry."""
        self.params = placed
        self.weight_version = (self.weight_version + 1 if version is None
                               else int(version))
        self.allocator.invalidate_registry()
        for d in self._seqs.values():
            d.no_commit = True

    @property
    def has_pending_weights(self) -> bool:
        return self._pending_weights is not None

    def apply_pending_weights(self) -> bool:
        """Install a deferred weight commit — the tick-boundary half of
        ``commit_staged_weights(defer=True)``. The scheduler calls this at
        tick entry (the previous tick's dispatch has fully drained, the
        next has not started), which is the only point a swap can land
        without interleaving a half-executed tick; direct ``step()``
        drivers own their tick boundary and call it themselves. Returns
        True when a swap was applied."""
        if self._pending_weights is None:
            return False
        pending, self._pending_weights = self._pending_weights, None
        self._install_weights(*pending)
        return True

    def publish_weights(self, params, version: Optional[int] = None,
                        force: bool = False, defer: bool = False) -> bool:
        """In-memory weight delivery (the RLHF train->serve flip): stage +
        commit in one call. ``rlhf.WeightPublisher`` hands the gathered
        training tree here; the fleet path goes through
        ``serving/router.py publish_weights`` instead so the stage phase
        completes on EVERY replica before any replica flips."""
        self.stage_weights(params, version=version)
        return self.commit_staged_weights(force=force, defer=defer)

    def reload_weights(self, ckpt_dir: str, tag: Optional[str] = None,
                       force: bool = False, defer: bool = False) -> bool:
        """Hot-swap serving weights from a training checkpoint (see the base
        engine), with a continuous-batching guard: live sequences hold KV
        entries computed under the OLD weights, so swapping under them would
        silently corrupt their continuations. With live sequences the swap
        is refused (returns False, keeps serving) unless the caller opts
        in: ``defer=True`` applies the swap at the next tick boundary (the
        scheduler drains the in-flight tick first — the footgun-free mode
        the router uses), ``force=True`` hard-swaps immediately (RLHF
        rollouts mid-episode that accept the approximation). Load failures
        — mid-save, torn ``latest``, corrupted shards — keep serving the
        current weights and return False either way."""
        if self._seqs and not (force or defer):
            logger.warning(
                f"reload_weights: {len(self._seqs)} live sequences hold KV "
                "from the current weights; refusing the hot-swap (drain or "
                "flush() them, or pass force=True / defer=True)")
            return False
        params = self._try_load_serving_weights(ckpt_dir, tag=tag)
        if params is None:
            return False
        self.stage_weights(params)
        return self.commit_staged_weights(force=force, defer=defer)

    def flush(self, uids: Sequence[int], early_stop: bool = False) -> None:
        """Free all state for finished sequences (engine_v2.py:242).
        Spilled blocks (ISSUE 15) have no pool slot to free — their host
        tier entry is dropped instead. ``early_stop=True`` marks an
        EOS/stop-sequence termination (ISSUE 16): the freed pool slots are
        tallied in ``early_stop_freed_blocks`` so the scheduler's
        sampling/* counters can report the KV the stop returned ahead of
        the request's budgeted lifetime."""
        for uid in uids:
            desc = self._seqs.pop(uid, None)
            if desc is None:
                raise ValueError(f"unknown uid {uid}")
            self._pending_sampling.pop(uid, None)
            self._pending_adapter.pop(uid, None)
            if desc.adapter_id is not None and self.adapters is not None:
                # unpin the slot; the adapter stays resident (warm) until
                # LRU eviction needs it
                self.adapters.release(desc.adapter_id)
            if early_stop:
                self.early_stop_freed_blocks += sum(
                    1 for b in desc.blocks if b >= 0)
            if desc.spilled:
                self.allocator.free([b for b in desc.blocks if b >= 0])
                self.tier.drop(uid)
            else:
                self.allocator.free(desc.blocks)
