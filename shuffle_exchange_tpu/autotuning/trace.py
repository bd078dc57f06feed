"""Seeded, paired Poisson request traces for serving measurement.

Every autotuner trial (``scripts/autotune_serving.py``) scores a scheduler
against a Poisson arrival trace. Candidate comparisons are only meaningful
when the candidates face the SAME trace: same prompts, same arrival offsets,
same per-request token budgets. This module makes that pairing explicit:
a :class:`PoissonTrace` is generated from one RNG seed, carries its seed
in every serialization, and every derived view (``head`` screening
subsets, ``with_load`` arrival calibration) is a pure function of the
parent — so two processes holding the same seed measure against
bit-identical workloads (the variance-control half of the ISSUE 14
successive-halving design).

Arrival offsets are ``np.cumsum(rng.exponential(span / n, size=n))`` — a
Poisson process whose EXPECTED span offers ``load``× the measured capacity
(:func:`poisson_arrivals`).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["PoissonTrace", "poisson_arrivals"]


def poisson_arrivals(rng: np.random.Generator, n: int, span: float) -> List[float]:
    """Cumulative Poisson-process arrival offsets: ``n`` exponential
    interarrivals with mean ``span / n`` (expected total span ``span``)."""
    if n < 1:
        raise ValueError(f"need n >= 1 arrivals, got {n}")
    if span < 0:
        raise ValueError(f"span must be >= 0, got {span}")
    return np.cumsum(rng.exponential(span / n, size=n)).tolist()


@dataclasses.dataclass(frozen=True)
class PoissonTrace:
    """One reproducible serving workload: prompts + per-request max_new
    (+ arrival offsets once calibrated). Frozen: every mutation-shaped
    operation returns a new trace, so a trace object handed to N
    candidate trials cannot drift between them."""

    seed: int
    prompts: tuple                      # tuple of tuple[int] token prompts
    max_new: int
    arrivals: Optional[tuple] = None    # seconds from t0; None = all-at-once
    #: offered-load multiple the arrivals were calibrated at (with_load)
    load: Optional[float] = None
    #: capacity (tokens/s) the calibration measured — recorded so a trial
    #: log can state the absolute rate the candidates were offered
    capacity_tokens_per_sec: Optional[float] = None

    @classmethod
    def generate(cls, seed: int, *, vocab: int, n_requests: int,
                 prompt_lo: int, prompt_hi: int, max_new: int,
                 period: Optional[int] = None) -> "PoissonTrace":
        """Random-token prompts with lengths uniform in [prompt_lo,
        prompt_hi]. ``period`` makes the prompts cycle every ``period``
        tokens — the repetitive-suffix regime prompt-lookup speculation
        drafts in."""
        if not 1 <= prompt_lo <= prompt_hi:
            raise ValueError(
                f"need 1 <= prompt_lo <= prompt_hi, got [{prompt_lo}, {prompt_hi}]")
        rng = np.random.default_rng(seed)
        prompts = []
        for n in rng.integers(prompt_lo, prompt_hi + 1, size=n_requests):
            if period:
                cyc = rng.integers(1, vocab, size=period).tolist()
                prompts.append(tuple((cyc * (int(n) // period + 1))[:int(n)]))
            else:
                prompts.append(tuple(rng.integers(1, vocab, size=int(n)).tolist()))
        return cls(seed=int(seed), prompts=tuple(prompts), max_new=int(max_new))

    # -- derived views (pure; pairing-preserving) -----------------------

    def with_load(self, capacity_tokens_per_sec: float,
                  load: float) -> "PoissonTrace":
        """Calibrate arrivals: a Poisson process offering ``load``× the
        measured ``capacity_tokens_per_sec``. Drawn from a fresh RNG at
        this trace's seed, so the SAME (seed, capacity, load) triple
        always yields the same offsets — the pairing contract."""
        if capacity_tokens_per_sec <= 0:
            raise ValueError(
                f"capacity must be > 0, got {capacity_tokens_per_sec}")
        n = len(self.prompts)
        span = n * self.max_new / capacity_tokens_per_sec / load
        rng = np.random.default_rng(self.seed)
        return dataclasses.replace(
            self, arrivals=tuple(poisson_arrivals(rng, n, span)),
            load=float(load),
            capacity_tokens_per_sec=float(capacity_tokens_per_sec))

    def head(self, n: int) -> "PoissonTrace":
        """The first ``n`` requests (and their arrival offsets): the
        screening-fidelity view. A prefix, never a resample — a candidate
        promoted from a screening round was measured on a strict subset
        of the workload its final sees."""
        n = max(1, min(int(n), len(self.prompts)))
        return dataclasses.replace(
            self, prompts=self.prompts[:n],
            arrivals=self.arrivals[:n] if self.arrivals is not None else None)

    # -- consumption ----------------------------------------------------

    def __len__(self) -> int:
        return len(self.prompts)

    @property
    def total_new_tokens(self) -> int:
        return len(self.prompts) * self.max_new

    def request_tokens_hi(self) -> int:
        """Longest request footprint (prompt + generation) in tokens —
        the number admission constraints size against."""
        return max(len(p) for p in self.prompts) + self.max_new

    def prompt_lists(self) -> List[List[int]]:
        return [list(p) for p in self.prompts]

    def arrival_list(self) -> Optional[List[float]]:
        return list(self.arrivals) if self.arrivals is not None else None

    def describe(self) -> dict:
        """Machine-readable trace record for trial logs — enough to
        reproduce the exact workload (seed + shape) and to audit the
        offsets actually offered."""
        return {
            "seed": self.seed,
            "n_requests": len(self.prompts),
            "prompt_lens": [len(p) for p in self.prompts],
            "max_new_tokens": self.max_new,
            "offered_load_x": self.load,
            "capacity_tokens_per_sec": (
                round(self.capacity_tokens_per_sec, 1)
                if self.capacity_tokens_per_sec is not None else None),
            "arrivals_s": ([round(a, 6) for a in self.arrivals]
                           if self.arrivals is not None else None),
        }
