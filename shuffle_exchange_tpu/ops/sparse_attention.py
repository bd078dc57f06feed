"""Blocksparse attention: fixed / longformer / bigbird / variable layouts.

Capability parity with the reference's sparse-attention stack
(``ops/sparse_attention/{matmul,softmax}.py`` triton blocksparse kernels +
the SparsityConfig family — Dense, Fixed, BSLongformer, BigBird, Variable —
SURVEY.md §2.13 "blocksparse attention"). The configs build a block-level
layout [T/bs, S/bs] of which key blocks each query block attends to; the
attention then masks at block granularity.

TPU-native shape: the layout lowers to a block mask applied inside the
fp32-softmax attention. On TPU the MXU runs dense blocks at full rate, so
(unlike the reference's triton kernels, which exist to skip CUDA tiles)
the win is algorithmic — O(T·w) attended positions — and memory-bound
cases route through ``chunked_attention`` with the mask folded in. A
Pallas splash-attention kernel is the drop-in upgrade path for skipping
masked blocks entirely.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .flash_attention import _repeat_kv


@dataclasses.dataclass
class SparsityConfig:
    """Base block-layout config (reference sparsity_config.py)."""

    block: int = 16

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError

    def _n(self, seq_len: int) -> int:
        if seq_len % self.block:
            raise ValueError(f"seq_len {seq_len} not divisible by block {self.block}")
        return seq_len // self.block


@dataclasses.dataclass
class DenseSparsityConfig(SparsityConfig):
    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        return np.ones((n, n), bool)


@dataclasses.dataclass
class FixedSparsityConfig(SparsityConfig):
    """Local blocks + periodic global columns (reference 'fixed' mode:
    every query attends its local stride window plus the last
    ``num_global_blocks`` of each stride)."""

    num_local_blocks: int = 4
    num_global_blocks: int = 1

    def __post_init__(self):
        if self.num_global_blocks > self.num_local_blocks:
            raise ValueError(
                f"FixedSparsityConfig: num_global_blocks ({self.num_global_blocks}) must be "
                f"<= num_local_blocks ({self.num_local_blocks}) — globals are each stride's "
                "trailing blocks")

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        layout = np.zeros((n, n), bool)
        stride = self.num_local_blocks
        for qi in range(n):
            start = (qi // stride) * stride
            layout[qi, start:start + stride] = True        # local window
            # global summary blocks: the trailing blocks of every previous stride
            for s in range(0, start, stride):
                layout[qi, s + stride - self.num_global_blocks:s + stride] = True
        return layout


@dataclasses.dataclass
class BSLongformerSparsityConfig(SparsityConfig):
    """Sliding window + designated global blocks (reference BSLongformer)."""

    num_sliding_window_blocks: int = 3
    global_block_indices: tuple = (0,)

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        layout = np.zeros((n, n), bool)
        w = self.num_sliding_window_blocks // 2
        for qi in range(n):
            layout[qi, max(0, qi - w):min(n, qi + w + 1)] = True
        for g in self.global_block_indices:
            if g < n:
                layout[:, g] = True                        # everyone sees global
                layout[g, :] = True                        # global sees everyone
        return layout


@dataclasses.dataclass
class BigBirdSparsityConfig(SparsityConfig):
    """Window + global + random blocks (reference BigBird)."""

    num_random_blocks: int = 1
    num_sliding_window_blocks: int = 3
    num_global_blocks: int = 1
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        layout = np.zeros((n, n), bool)
        w = self.num_sliding_window_blocks // 2
        for qi in range(n):
            layout[qi, max(0, qi - w):min(n, qi + w + 1)] = True
        g = min(self.num_global_blocks, n)
        layout[:, :g] = True
        layout[:g, :] = True
        rng = np.random.default_rng(self.seed)
        for qi in range(n):
            picks = rng.choice(n, size=min(self.num_random_blocks, n), replace=False)
            layout[qi, picks] = True
        return layout


@dataclasses.dataclass
class VariableSparsityConfig(SparsityConfig):
    """Per-row local windows + explicit global indices (reference Variable)."""

    num_local_blocks: int = 4
    global_block_indices: tuple = (0,)

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        layout = np.zeros((n, n), bool)
        for qi in range(n):
            layout[qi, max(0, qi - self.num_local_blocks + 1):qi + 1] = True
        for g in self.global_block_indices:
            if g < n:
                layout[:, g] = True
                layout[g, :] = True
        return layout


def sparse_attention(q, k, v, config: Optional[SparsityConfig] = None, causal: bool = True,
                     layout: Optional[np.ndarray] = None, impl: str = "auto"):
    """Blocksparse attention. q [B,T,H,D], k/v [B,S,Hkv,D] -> [B,T,H,D].

    ``config`` builds the layout from T (or pass a precomputed block
    ``layout`` [T/bs, S/bs] bool with its block size in ``config.block``).
    On TPU the layout routes through the splash kernel as a NumpyMask —
    fully-masked blocks are SKIPPED (the reference's triton blocksparse
    win), not just masked; elsewhere the dense fp32-softmax fallback.
    """
    import jax
    import jax.numpy as jnp

    config = config or FixedSparsityConfig()
    B, T, H, D = q.shape
    S = k.shape[1]
    if layout is None:
        if T != S:
            raise ValueError("sparse_attention with auto layout expects T == S")
        layout = config.make_layout(T)
    bs = config.block

    # Block layout -> element mask (numpy: splash masks are host-built),
    # + causal inside allowed blocks.
    elem_np = np.kron(np.asarray(layout, bool), np.ones((bs, bs), bool))[:T, :S]
    if causal:
        elem_np = elem_np & np.tril(np.ones((T, S), bool), k=S - T)

    if impl in ("auto", "splash"):
        from .dispatch import pallas_enabled
        from .flash_attention import splash_attention_gqa

        eligible = (D % 64 == 0 and T % 128 == 0 and S % 128 == 0
                    and elem_np.any(axis=1).all())
        if impl == "splash" and not eligible:
            raise ValueError(
                f"impl='splash' needs D%64==0, T/S%128==0 and no fully-masked "
                f"query row (got T={T}, S={S}, D={D})")
        if eligible and (impl == "splash" or pallas_enabled()):
            # selected means it runs or raises (no dense-mask rescue)
            return splash_attention_gqa(q, k, v, causal=False,
                                        mask_np=elem_np,
                                        interpret=impl == "splash" and not pallas_enabled())

    n_rep = H // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    mask = jnp.asarray(elem_np)

    scale = D ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale, k.astype(jnp.float32))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with no allowed block (can't happen with causal diag layouts) stay 0
    probs = jnp.where(mask[None, None], probs, 0.0)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
