"""The generate->score->train driver: rollout records, replay log, losses.

The workload class HybridEngine v2 exists for: RLHF-style
loops where one process alternates between fleet-served rollout generation
and ZeRO training steps on the same weights. Two concrete trainers ride
the EXISTING jitted train step (the engine's ``train_batch`` machinery is
reused verbatim — only the loss function differs, passed to
``sxt.initialize(model=..., loss_fn=...)``):

- :func:`pg_loss_fn` — reward-weighted policy gradient: maximize the
  log-probability of sampled rollout tokens weighted by their
  (advantage-normalized) reward. Online distillation is this loss with
  the teacher's preference as the reward — including distilling the
  draft models the speculative decoder wants.
- :func:`dpo_loss_fn` — Direct Preference Optimization over
  (chosen, rejected) pairs, with the frozen reference policy's sequence
  log-probs precomputed OUTSIDE the step (the reference policy never
  trains, so its term is data, not graph).

Replay discipline: every rollout is a :class:`RolloutRecord`
``(prompt, sampled tokens, weight_version, sampling)`` in a
:class:`ReplayLog`. Greedy fleet scheduling is deterministic, and
sampled scheduling is seeded (ISSUE 16's per-request Gumbel chain is a
pure function of ``(seed, position, distribution)``), so any record can
be replayed bit-exactly at its recorded weight version
(``HybridEngineV2.replay`` / ``ReplayLog.verify``) — the same
token-identical contract the serving drain/requeue path keeps, applied
to RLHF debugging ("which weights sampled this token, and can I
reproduce it?").
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class RolloutRecord:
    """One rollout: the prompt, what the policy sampled, and the exact
    weight version it sampled under. ``reward`` is filled by the scorer;
    ``uid`` is the fleet uid that served it (debugging breadcrumb).
    ``sampling`` is the request's ``SamplingParams.to_wire()`` dict
    (None = greedy) — together with ``weight_version`` it is everything
    replay needs to reproduce a SAMPLED chain bit-exactly, because the
    seed rides in the wire dict and the engine's per-token Gumbel noise
    is keyed only on ``(seed, absolute position)``."""

    prompt: List[int]
    tokens: List[int]
    weight_version: int
    reward: Optional[float] = None
    uid: Optional[int] = None
    sampling: Optional[dict] = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "RolloutRecord":
        return cls(**{k: d.get(k) for k in
                      ("prompt", "tokens", "weight_version", "reward",
                       "uid", "sampling")})


class ReplayLog:
    """Append-only token-identical replay log (JSONL-serializable).

    ``verify(hybrid)`` replays every record at the fleet's CURRENT weight
    version and asserts bit-exact token equality — sampled records
    replay under their recorded ``sampling`` wire dict (seed included),
    so stochastic rollouts verify exactly like greedy ones; records from
    other versions are skipped (they need that version's weights), so
    the return value distinguishes verified from unverifiable."""

    def __init__(self, records: Optional[Sequence[RolloutRecord]] = None):
        self.records: List[RolloutRecord] = list(records or [])

    def __len__(self) -> int:
        return len(self.records)

    def __iter__(self):
        return iter(self.records)

    def append(self, rec: RolloutRecord) -> None:
        self.records.append(rec)

    def extend(self, recs: Sequence[RolloutRecord]) -> None:
        self.records.extend(recs)

    def at_version(self, version: int) -> List[RolloutRecord]:
        return [r for r in self.records if r.weight_version == version]

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            for r in self.records:
                f.write(json.dumps(r.to_json()) + "\n")

    @classmethod
    def load(cls, path: str) -> "ReplayLog":
        out = cls()
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(RolloutRecord.from_json(json.loads(line)))
        return out

    def verify(self, hybrid, records: Optional[Sequence[RolloutRecord]] = None
               ) -> Tuple[int, int]:
        """Replay each record through the fleet and require bit-exact
        tokens. Returns ``(verified, skipped)``; raises on the first
        divergence, naming the record."""
        verified = skipped = 0
        for rec in (self.records if records is None else records):
            if rec.weight_version != hybrid.weight_version:
                skipped += 1
                continue
            got = hybrid.replay(rec)
            if got != rec.tokens:
                raise AssertionError(
                    f"replay diverged for uid {rec.uid} at weight version "
                    f"{rec.weight_version}: recorded {rec.tokens}, "
                    f"replayed {got}")
            verified += 1
        return verified, skipped


# -- losses over the existing train step ------------------------------


def pg_loss_fn(model) -> Callable:
    """Reward-weighted policy-gradient loss for ``sxt.initialize(model=m,
    loss_fn=pg_loss_fn(m))``.

    Batch: ``{"input_ids": [B, T] int32 (prompt + rollout, right-padded),
    "weights": [B, T] float32}`` — ``weights[b, j]`` is the (normalized)
    advantage for the token at absolute position ``j`` and 0 on prompt /
    pad positions, so the loss scores exactly the sampled tokens:
    ``-(sum_j w_j * log p(ids_j | ids_<j)) / count(w != 0)``."""

    def loss_fn(params, batch, rng=None):
        import jax
        import jax.numpy as jnp

        ids = batch["input_ids"]
        w = batch["weights"].astype(jnp.float32)
        logits = model.apply(params, ids[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = ids[:, 1:]
        lp = jnp.take_along_axis(logp, tgt[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        wt = w[:, 1:]
        denom = jnp.maximum(jnp.sum(wt != 0), 1)
        return -(lp * wt).sum() / denom

    return loss_fn


def dpo_loss_fn(model, beta: float = 0.1) -> Callable:
    """Direct Preference Optimization loss for ``sxt.initialize``.

    Batch: ``{"chosen_ids"/"rejected_ids": [B, T] int32,
    "chosen_mask"/"rejected_mask": [B, T] float32 (1 on completion
    tokens), "ref_chosen_lp"/"ref_rejected_lp": [B] float32}`` — the
    reference policy's sequence log-probs are precomputed data
    (:meth:`RLHFLoop.dpo_batch` computes them with the frozen snapshot),
    so the jitted step only runs the live policy:
    ``-mean log sigmoid(beta * ((lp_c - ref_c) - (lp_r - ref_r)))``."""

    def seq_lp(params, ids, mask):
        import jax
        import jax.numpy as jnp

        logits = model.apply(params, ids[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(logp, ids[:, 1:, None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        return (lp * mask[:, 1:].astype(jnp.float32)).sum(axis=-1)

    def loss_fn(params, batch, rng=None):
        import jax
        import jax.numpy as jnp

        lc = seq_lp(params, batch["chosen_ids"], batch["chosen_mask"])
        lr = seq_lp(params, batch["rejected_ids"], batch["rejected_mask"])
        margin = (lc - batch["ref_chosen_lp"]) - (lr - batch["ref_rejected_lp"])
        return -jnp.mean(jax.nn.log_sigmoid(jnp.float32(beta) * margin))

    return loss_fn


def sequence_logprob(logits: np.ndarray, ids: np.ndarray,
                     mask: np.ndarray) -> np.ndarray:
    """Host-side masked sequence log-prob from full-sequence logits —
    the scoring path (ref policy / reward models), not the train step.
    ``logits`` [B, T, V] for inputs ``ids[:, :-1]`` is the usual shifted
    layout handled here: pass logits for the FULL ids and the first
    position is simply never scored (mask[:, 0] is ignored)."""
    logits = np.asarray(logits, np.float64)
    x = logits[:, :-1]
    x = x - x.max(axis=-1, keepdims=True)
    logp = x - np.log(np.exp(x).sum(axis=-1, keepdims=True))
    tgt = np.asarray(ids)[:, 1:]
    lp = np.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return (lp * np.asarray(mask, np.float64)[:, 1:]).sum(axis=-1)


class RLHFLoop:
    """generate -> score -> train, end to end.

    ``hybrid`` is a :class:`HybridEngineV2` whose training engine was
    built with :func:`pg_loss_fn` (``mode="pg"``) or :func:`dpo_loss_fn`
    (``mode="dpo"``). ``reward_fn(prompt, tokens) -> float`` scores
    rollouts for the PG path. The loop owns the batch construction (token
    layouts the losses expect) and feeds the engine's EXISTING jitted
    train step through ``hybrid.train_batch``; padding is fixed at
    ``seq_len`` so every step hits the same compiled program."""

    def __init__(self, hybrid,
                 reward_fn: Optional[Callable[[List[int], List[int]],
                                              float]] = None,
                 seq_len: Optional[int] = None,
                 normalize_advantages: bool = True):
        self.hybrid = hybrid
        self.reward_fn = reward_fn
        self.seq_len = int(seq_len if seq_len is not None
                           else hybrid.model.config.max_seq_len)
        self.normalize_advantages = normalize_advantages
        self.log = hybrid.replay_log
        self._ref = None     # frozen DPO reference, snapshotted lazily

    # -- generate + score ----------------------------------------------

    def rollout(self, prompts, max_new_tokens: int = 16
                ) -> List[RolloutRecord]:
        """Flip to serve, generate through the fleet, score. The records
        land in the hybrid's replay log with their weight version."""
        self.hybrid.eval()
        records = self.hybrid.rollout(prompts,
                                      max_new_tokens=max_new_tokens)
        if self.reward_fn is not None:
            for r in records:
                r.reward = float(self.reward_fn(r.prompt, r.tokens))
        return records

    # -- PG path --------------------------------------------------------

    def pg_batch(self, records: Sequence[RolloutRecord]) -> Dict[str, np.ndarray]:
        """``{"input_ids", "weights"}`` for :func:`pg_loss_fn`: rollouts
        right-padded to ``seq_len``, advantages = rewards normalized
        across the batch (mean 0, unit variance when it exists), written
        at the sampled tokens' absolute positions."""
        B, T = len(records), self.seq_len
        rewards = np.asarray([r.reward or 0.0 for r in records], np.float64)
        adv = rewards - rewards.mean()
        if self.normalize_advantages and adv.std() > 1e-8:
            adv = adv / adv.std()
        ids = np.zeros((B, T), np.int32)
        w = np.zeros((B, T), np.float32)
        for i, r in enumerate(records):
            seq = (list(r.prompt) + list(r.tokens))[:T]
            ids[i, :len(seq)] = seq
            lo = min(len(r.prompt), T)
            hi = min(len(seq), T)
            w[i, lo:hi] = adv[i]
        return {"input_ids": ids, "weights": w}

    def pg_step(self, records: Sequence[RolloutRecord]) -> float:
        """One reward-weighted policy-gradient optimizer step over
        ``records`` through the engine's jitted train step."""
        self.hybrid.train()
        return float(self.hybrid.train_batch(self.pg_batch(records)))

    # -- DPO path -------------------------------------------------------

    def _ref_logits(self, ids: np.ndarray) -> np.ndarray:
        """Full-sequence logits from the FROZEN reference policy — a
        snapshot of the weights at the loop's first DPO batch (the
        reference never trains; DPO's KL anchor)."""
        if self._ref is None:
            from ..inference.config import InferenceConfig
            from ..inference.engine import InferenceEngine

            self._ref = InferenceEngine(
                self.hybrid.model,
                self.hybrid.engine.module_weights(consensus=True),
                InferenceConfig(dtype="float32", max_seq_len=self.seq_len))
        return np.asarray(self._ref.forward(ids))

    def dpo_batch(self, pairs: Sequence[Tuple[List[int], List[int],
                                              List[int]]]
                  ) -> Dict[str, np.ndarray]:
        """``{"chosen_ids", "rejected_ids", masks, ref log-probs}`` for
        :func:`dpo_loss_fn` from ``(prompt, chosen, rejected)`` token
        triples; the frozen reference's sequence log-probs are computed
        here, outside the jitted step."""
        B, T = len(pairs), self.seq_len

        def pack(prompt, completion):
            seq = (list(prompt) + list(completion))[:T]
            row = np.zeros((T,), np.int32)
            row[:len(seq)] = seq
            m = np.zeros((T,), np.float32)
            m[min(len(prompt), T):min(len(seq), T)] = 1.0
            return row, m

        cids = np.zeros((B, T), np.int32)
        rids = np.zeros((B, T), np.int32)
        cm = np.zeros((B, T), np.float32)
        rm = np.zeros((B, T), np.float32)
        for i, (prompt, chosen, rejected) in enumerate(pairs):
            cids[i], cm[i] = pack(prompt, chosen)
            rids[i], rm[i] = pack(prompt, rejected)
        ref_c = sequence_logprob(self._ref_logits(cids), cids, cm)
        ref_r = sequence_logprob(self._ref_logits(rids), rids, rm)
        return {"chosen_ids": cids, "rejected_ids": rids,
                "chosen_mask": cm, "rejected_mask": rm,
                "ref_chosen_lp": ref_c.astype(np.float32),
                "ref_rejected_lp": ref_r.astype(np.float32)}

    def dpo_step(self, pairs) -> float:
        """One DPO optimizer step over ``(prompt, chosen, rejected)``
        triples through the engine's jitted train step."""
        self.hybrid.train()
        return float(self.hybrid.train_batch(self.dpo_batch(pairs)))

    # -- the driver -----------------------------------------------------

    def run(self, prompt_batches: Sequence[Sequence[Sequence[int]]],
            max_new_tokens: int = 16) -> Dict[str, object]:
        """generate -> score -> train over ``prompt_batches`` (each batch
        sized to the engine's ``train_batch_size``), PG mode. Returns the
        loop summary (losses, reward trajectory, weight versions)."""
        losses, mean_rewards, versions = [], [], []
        for prompts in prompt_batches:
            records = self.rollout(prompts, max_new_tokens=max_new_tokens)
            mean_rewards.append(
                float(np.mean([r.reward or 0.0 for r in records])))
            versions.append(records[0].weight_version)
            losses.append(self.pg_step(records))
        return {"steps": len(losses), "losses": losses,
                "mean_rewards": mean_rewards, "weight_versions": versions,
                "rollouts_logged": len(self.log),
                "latency": self.hybrid.latency_report()}

    def run_overlapped(self, prompt_batches: Sequence[Sequence[Sequence[int]]],
                       max_new_tokens: int = 16) -> Dict[str, object]:
        """Continuous RLHF over the async weight-sync fleet (ISSUE 20):
        rollouts, scoring, and publishes OVERLAP instead of alternating
        behind the eval()/train() flip barrier.

        The shape: batch ``i+1`` is submitted to the started fleet (its
        replica threads decode in the background) BEFORE batch ``i`` is
        scored and trained on; each optimizer step's publish is the
        async retain-and-kick (O(tree bytes) + first gossip hop), so the
        in-flight batch never stalls on a fleet-wide stage/commit —
        deliveries land at tick boundaries via the deferred staged swap.
        Records are stamped with the weight version that ACTUALLY served
        them (a replica mid-gossip answers from its previous committed
        version — stale-but-honest, bounded by the staleness window), so
        ``weight_versions`` here is a per-batch ``{version: count}``
        census rather than the serial loop's single stamp. Requires
        ``router.sync.enabled``; the serial :meth:`run` drives barrier
        fleets."""
        import time as _time

        hybrid = self.hybrid
        hybrid.eval()
        router = hybrid.router
        if getattr(router, "_async_sync", None) is None:
            raise RuntimeError(
                "run_overlapped needs the async weight-sync fleet "
                "(router.sync.enabled); use run() for barrier publishes")
        batches = [list(b) for b in prompt_batches]
        if not batches:
            return {"steps": 0, "losses": [], "mean_rewards": [],
                    "weight_versions": [], "rollouts_logged": len(self.log),
                    "latency": hybrid.latency_report()}
        # the lazy fleet build above already gathered CURRENT training
        # weights onto every replica (first build IS the publish), so the
        # first batch needs no barrier — decoding starts immediately
        router.start()

        def _submit(prompts):
            return [(list(p), router.submit(list(p),
                                            max_new_tokens=max_new_tokens))
                    for p in prompts]

        def _collect(submitted):
            uids = [u for _, u in submitted]
            while not all(router.requests[u].state in ("finished", "failed")
                          for u in uids):
                _time.sleep(0.002)
            records = []
            for p, u in submitted:
                r = router.requests[u]
                wv = (r.weight_version if r.weight_version is not None
                      else (hybrid.weight_version or 0))
                rec = RolloutRecord(prompt=p, tokens=list(r.generated),
                                    weight_version=int(wv), uid=u)
                if self.reward_fn is not None:
                    rec.reward = float(self.reward_fn(rec.prompt, rec.tokens))
                records.append(rec)
            self.log.extend(records)
            return records

        losses, mean_rewards, versions = [], [], []
        try:
            submitted = _submit(batches[0])
            for nxt in batches[1:] + [None]:
                records = _collect(submitted)
                # the NEXT batch starts decoding now — scoring, the
                # train step, and the publish below all overlap with it
                submitted = _submit(nxt) if nxt is not None else None
                mean_rewards.append(
                    float(np.mean([r.reward or 0.0 for r in records])))
                census: Dict[int, int] = {}
                for r in records:
                    census[r.weight_version] = \
                        census.get(r.weight_version, 0) + 1
                versions.append(census)
                losses.append(float(hybrid.train_batch(
                    self.pg_batch(records))))
                hybrid.publish_weights()
        finally:
            router.stop()
        return {"steps": len(losses), "losses": losses,
                "mean_rewards": mean_rewards, "weight_versions": versions,
                "rollouts_logged": len(self.log),
                "staleness": router._async_sync.staleness(),
                "latency": hybrid.latency_report()}
