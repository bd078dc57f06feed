"""Continuous-batching serving scheduler — Dynamic SplitFuse over engine_v2.

Capability analog of the reference FastGen *scheduler* (SURVEY §2.10): the
paged substrate (``ragged/ragged_manager.py:19`` DSStateManager +
``ragged/blocked_allocator.py:11``) is engine_v2's; what this module adds is
the iteration-level scheduling loop on top (the reference serves it from
MII's ``batching/ragged_batching.py`` ``ScheduleRequests``/``__call__``
around ``inference/v2/engine_v2.py:107 put``): a request queue and running
set where every tick packs a fixed per-step **token budget** with

  (a) one decode token for every running sequence, and
  (b) prefill *chunks* from queued / partially-prefilled sequences filling
      the remainder (chunked prefill a la Sarathi / Orca iteration-level
      scheduling — "Dynamic SplitFuse"),

then executes the whole mixed batch as ONE compiled dispatch via
``InferenceEngineV2.step()``. Uniform-size steps keep the chip busy through
phase changes: aggregate throughput rises with load instead of sinking into
host-driven phase-by-phase dispatches (the ROADMAP's "heavy traffic from
millions of users" north star).

KV pressure: admission is block-accounted before every dispatch; when the
allocator runs dry the youngest admitted sequence is preempted — its blocks
freed, the request requeued at the FRONT with its generated continuation
folded into the prefill target. Greedy decoding makes the replay
deterministic, so a preempted request's output is identical to an
uninterrupted run (tests/test_serving_scheduler.py pins this).

Counters (always observable through the in-process monitor, reference
``monitor/monitor.py:13``): ``serving/ttft_s``, ``serving/tpot_s``,
``serving/queue_depth``, ``serving/running``, ``serving/budget_fill``,
``serving/kv_free_blocks``, ``serving/tick_s``, ``serving/preemptions``,
and the prefix-cache group ``prefix_cache/{hit_tokens, miss_tokens,
cow_copies, shared_blocks}`` (ISSUE 6: with ``prefix_caching`` on,
admission reuses committed shared-prefix KV blocks ref-counted — zero new
allocations for the shared span — and prefill starts from the first
non-cached token, shrinking both TTFT and per-tick prefill spend).

Speculative decoding (ISSUE 8, ``serving.speculative``): a running
sequence may submit k draft tokens per tick — from the n-gram
prompt-lookup self-drafter or a small draft model (``speculative.py``) —
verified in the SAME one-dispatch mixed step via the extend path with
greedy acceptance, so each tick emits 1..k+1 tokens per sequence at
exact-token parity with sequential ``decode_loop`` (bf16 KV). The
``speculative/{proposed, accepted, rejected, acceptance_rate,
rollbacks}`` counter group tracks it; rejected drafts rewind paged-KV
state through ``InferenceEngineV2.rewind`` before anything commits.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..monitor import InMemoryMonitor, Monitor
from ..profiling import trace
from ..testing import faults, sanitizer
from ..utils.invariants import atomic_on_reject
from ..utils.logging import logger
from .config import SamplingParams, ServingConfig
from .engine_v2 import InferenceEngineV2
from .paged import blocks_needed

QUEUED, PREFILL, RUNNING, FINISHED = "queued", "prefill", "running", "finished"
FAILED = "failed"
# tiered KV (ISSUE 15): a PARKED request's cold blocks live in the host
# tier — it keeps its engine descriptor and generated tokens, takes no
# budget, and resumes via fetch (no re-prefill) when pressure subsides
PARKED = "parked"


class DeadlineExceededError(RuntimeError):
    """A request outlived its ``deadline_s`` before finishing (ISSUE 12).
    Deterministic and named: the message carries the uid, the deadline vs
    elapsed time, and the replica's state at expiry; the error object is
    retained on ``ServingRequest.error`` for the caller."""

    def __init__(self, uid: int, deadline_s: float, elapsed_s: float,
                 replica_id: int, generated: int, fleet_state: str):
        self.uid = uid
        self.deadline_s = deadline_s
        self.elapsed_s = elapsed_s
        super().__init__(
            f"request {uid} exceeded its {deadline_s:.3f}s deadline "
            f"({elapsed_s:.3f}s elapsed, {generated} tokens generated) on "
            f"replica {replica_id} [{fleet_state}]")


@dataclasses.dataclass
class ServingRequest:
    """One request's lifecycle state (queued -> prefill -> running ->
    finished, with preemption looping running -> queued)."""

    uid: int
    prompt: List[int]
    max_new_tokens: int
    state: str = QUEUED
    prefill_done: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    # when the request was DUE on the scheduler's clock: an open-loop client
    # passes ``t0 + arrival`` (``submit(due_at=)``), so the wait a stalled
    # submitter imposed counts; defaults to ``submitted_at``.
    # ``first_scheduled_at``: the first tick that packed any of its tokens
    due_at: Optional[float] = None
    first_scheduled_at: Optional[float] = None
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    tpot_s: List[float] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    # ticks this request spent in the decode/verify lane (ISSUE 8): with
    # speculation on, decode_ticks / len(generated) is the per-sequence
    # steps-per-emitted-token — the lever speculative decoding pulls
    decode_ticks: int = 0
    # request-level robustness (ISSUE 12): ``deadline_s`` caps wall time
    # from submission (expired requests FAIL with a typed error at the
    # next tick boundary); ``not_before`` is the failover backoff gate —
    # a re-placed request yields its packing slot until the clock passes
    # it; ``retries`` counts failover re-placements and
    # ``replica_deaths`` the replica deaths it was mid-execution for
    # (the poison-quarantine signal). ``error`` retains the typed error
    # a FAILED request died with.
    deadline_s: Optional[float] = None
    not_before: float = 0.0
    retries: int = 0
    replica_deaths: int = 0
    error: Optional[BaseException] = None
    # tiered KV (ISSUE 15): the state a PARKED request resumes into
    # (PREFILL mid-prompt, RUNNING mid-decode) — recorded at park time
    # because ``prefill_target`` keeps growing with generated tokens
    parked_state: str = ""
    # one-dispatch sampling (ISSUE 16): per-request SamplingParams (None =
    # greedy, no EOS — the historical scheduler contract). The params ride
    # every export/inject/failover snapshot, so a re-placed request's
    # seeded chain replays bit-exactly on the survivor. ``stopped`` marks
    # EOS/stop-sequence early termination — the request finished before
    # its token budget, returning its KV blocks and running slot early.
    sampling: Optional[SamplingParams] = None
    stopped: bool = False
    # multi-tenant LoRA (ISSUE 18): the adapter this request decodes
    # under (None = base model, the reserved null slot 0). The id rides
    # every export/inject/failover snapshot so a re-placed request
    # re-binds the SAME adapter on the survivor. ``adapter_waiting``
    # marks a queued request parked on pool residency: it keeps its
    # FIFO seat but yields its packing slot until a slot frees — park,
    # never preempt, so adapter pressure costs queue time, not
    # re-prefill compute.
    adapter_id: Optional[str] = None
    adapter_waiting: bool = False
    # async weight sync (ISSUE 20): the serving weight version this
    # request's LAST token sampled under, stamped at finish — the
    # per-request staleness audit trail (bounded-window property tests
    # and honest RolloutRecord stamping read it, instead of assuming
    # every replica already serves the newest publish)
    weight_version: Optional[int] = None
    # expert-parallel MoE serving (ISSUE 19): a queued request parked on
    # expert-capacity pressure — the previous tick's routing saturated
    # some expert's buffer, so NEW sequences hold at their FIFO seat
    # until running ticks drain the pressure. Park, never preempt:
    # expert overload costs queue time, never a running sequence's KV.
    moe_waiting: bool = False

    @property
    def prefill_target(self) -> List[int]:
        """Tokens whose KV must exist before the next decode: the prompt
        plus everything generated so far. A preempted request re-enters
        prefill with its continuation folded in, so the replay resumes
        exactly where it left off."""
        return self.prompt + self.generated

    @property
    def done(self) -> bool:
        return self.stopped or len(self.generated) >= self.max_new_tokens

    @property
    def due(self) -> float:
        """When the request was due: ``due_at``, else its submission."""
        return self.submitted_at if self.due_at is None else self.due_at


class ContinuousBatchingScheduler:
    """Queue + running set + per-tick token-budget packing over an
    :class:`InferenceEngineV2`. Decoding is greedy (the engine-parity
    reference semantics of ``decode_loop``); hook ``on_token`` for
    streaming output."""

    def __init__(self, engine: InferenceEngineV2,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 monitor: Optional[Monitor] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 replica_id: int = 0,
                 drafter=None):
        if not isinstance(engine, InferenceEngineV2):
            raise TypeError("ContinuousBatchingScheduler needs the paged "
                            f"InferenceEngineV2, got {type(engine).__name__}")
        self.engine = engine
        # machine-readable replica identity (ISSUE 7): the serving router
        # runs N of these side by side and aggregates their stats() —
        # every summary and admission error can then name which replica
        # it talks about
        self.replica_id = int(replica_id)
        # a draining replica (SIGTERM'd, or scaled away) admits nothing
        # new; its unfinished requests are exported for requeue elsewhere
        self.draining = False
        # a FENCED replica was declared dead by the health layer while a
        # tick might still be in flight (hang): the zombie tick must emit
        # nothing when it finally returns — its requests were already
        # snapshotted and re-placed on survivors, so a late emission would
        # duplicate tokens. A bare bool write (no lock): the failover path
        # cannot take this replica's lock, the hung tick holds it.
        self.fenced = False
        self.cfg: ServingConfig = engine.config.serving
        self.queue: Deque[ServingRequest] = deque()  # FIFO; preempted at front
        self.active: List[ServingRequest] = []       # admission order
        # tiered KV (ISSUE 15): requests parked host-ward under pressure,
        # park order (oldest first — the unpark order); the engine's tier
        # is None unless the config enables kv_tier
        self.parked: List[ServingRequest] = []
        self.tier = getattr(engine, "tier", None)
        self.parks = 0
        self.unparks = 0
        # spillable_blocks() walks every live descriptor's block list —
        # too hot AND too racy for the router's load() polls (they run on
        # router threads while the tick thread mutates eng._seqs under
        # the replica lock), so ONLY the tick thread ever walks: the tick
        # tail (and the force-unpark early return) refresh this cache and
        # load() reads the plain int. Early-return ticks that free blocks
        # (deadline expiry on a backoff-gated tick) can leave it one tick
        # stale — acceptable for a placement-pressure heuristic.
        self._spillable_cache: int = 0
        self.requests: Dict[int, ServingRequest] = {}
        self.on_token = on_token
        self.clock = clock
        # always-on in-process sink (resilience-counter discipline): tests
        # and post-mortems read scheduler.memory_monitor.events even when
        # no external monitor backend is configured
        self.memory_monitor = InMemoryMonitor(maxlen=4096)
        self._sinks: List[Monitor] = [monitor] if monitor is not None else []
        self.ticks = 0
        self.preemptions = 0
        self.deadline_expired = 0
        self._next_uid = 0
        # speculative decoding (ISSUE 8): k drafts per running sequence
        # per tick, verified in the same one-dispatch mixed step. The
        # drafter comes from the config (ngram self-speculation needs no
        # weights; drafter="model" loads serving.speculative.draft_model
        # via models/hf) unless an instance is passed in — the router
        # hands each replica its engine's own serving config unchanged,
        # so per-replica speculation follows the replica's engine.
        self.spec = self.cfg.speculative
        self.drafter = drafter
        if self.spec.enabled and self.drafter is None:
            from .speculative import make_drafter

            self.drafter = make_drafter(self.spec, like=engine.config)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        # one-dispatch sampling (ISSUE 16): counters for the sampling/*
        # monitor group. ``sampling_seen`` latches once any request
        # carries SamplingParams — greedy-only serving never switches off
        # the step() path, so its dispatch behavior (and program-key
        # ladder) is bit-identical to pre-sampling builds.
        self.sampling_seen = False
        self.early_stops = 0
        self.dead_tokens_saved = 0
        self.sampling_resamples = 0
        # multi-tenant LoRA (ISSUE 18): the engine's AdapterPool (None
        # unless config.adapters.enabled), the residency-park counters,
        # and the per-adapter emitted-token tally the adapter/* monitor
        # group and per-tenant billing read
        self.apool = getattr(engine, "adapters", None)
        self.adapter_parks = 0
        self.adapter_unparks = 0
        self.adapter_tokens: Dict[str, int] = {}
        # expert-parallel MoE serving (ISSUE 19): expert-capacity park
        # counters for the moe/* monitor group (the engine owns the
        # routing-count tallies; the scheduler owns the admission parks)
        self.moe_capacity_parks = 0
        self.moe_unparks = 0

    # -- request intake ------------------------------------------------

    @atomic_on_reject(check="validate")
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               uid: Optional[int] = None,
               deadline_s: Optional[float] = None,
               sampling: Optional[SamplingParams] = None,
               adapter_id: Optional[str] = None,
               due_at: Optional[float] = None) -> int:
        """Queue one request; returns its uid. Validates against the
        engine's hard caps up front so impossible requests fail at submit
        time with named numbers, not mid-serve. ``deadline_s`` caps the
        request's wall time from submission (ISSUE 12): a request still
        unfinished past it FAILS with a typed ``DeadlineExceededError``
        at the next tick boundary instead of holding budget forever.
        ``sampling`` (ISSUE 16) attaches per-request SamplingParams —
        temperature/top-k/top-p + seed sample in-dispatch off the seeded
        Gumbel chain, EOS/stop sequences end the request at the tick the
        stop hits. None inherits the engine config's ``sampling`` section
        (whose own default is exactly the historical greedy contract).
        ``due_at``: when the request was due on the scheduler's clock (an
        open-loop client's ``t0 + arrival``); ``stats()`` measures time to
        first token and queue wait from it. Default: now."""
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if sampling is not None and not isinstance(sampling, SamplingParams):
            raise TypeError(
                f"sampling must be a SamplingParams, got "
                f"{type(sampling).__name__}")
        if sampling is None:
            base = self.engine.config.sampling
            if base != SamplingParams():
                sampling = base
        # multi-tenant LoRA (ISSUE 18): an unregistered adapter fails at
        # submit time with named numbers, never mid-serve — residency is
        # NOT checked here (a non-resident registered adapter pages in
        # at admission, or parks the request until a slot frees)
        if adapter_id is not None:
            if self.apool is None:
                raise ValueError(
                    f"replica {self.replica_id}: request names adapter "
                    f"{adapter_id!r} but the adapter pool is disabled "
                    f"(enable config.adapters)")
            if not self.apool.registered(adapter_id):
                raise ValueError(
                    f"replica {self.replica_id}: adapter {adapter_id!r} "
                    f"is not registered; publish_adapter it first")
        if self.draining:
            raise RuntimeError(
                f"replica {self.replica_id} is draining and admits no new "
                f"requests (route to a surviving replica)")
        prompt = list(map(int, prompt))
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        eng = self.engine
        total = len(prompt) + max_new_tokens
        if total > eng.config.max_seq_len:
            raise ValueError(
                f"replica {self.replica_id}: prompt {len(prompt)} + "
                f"max_new_tokens {max_new_tokens} = "
                f"{total} exceeds max_seq_len {eng.config.max_seq_len}")
        usable = eng.allocator.num_blocks - 1  # block 0 is scratch
        need_max = blocks_needed(total, eng.cache.block_size)
        if need_max > usable:
            # named numbers per replica (ISSUE 7 satellite): the router
            # aggregates these verbatim when NO replica can ever take the
            # request, so the fleet-level error still says which replica
            # wanted how many blocks against how many it has
            raise ValueError(
                f"replica {self.replica_id}: request needs up to {need_max} "
                f"KV blocks but the pool has "
                f"{usable} usable (num_kv_blocks={eng.allocator.num_blocks} "
                f"minus scratch); raise num_kv_blocks or shorten the request")
        if uid is None:
            while self._next_uid in self.requests or self._next_uid in eng._seqs:
                self._next_uid += 1
            uid = self._next_uid
            self._next_uid += 1
        elif uid in self.requests or uid in eng._seqs:
            raise ValueError(f"uid {uid} is already live")
        now = self.clock()
        r = ServingRequest(uid=uid, prompt=prompt,
                           max_new_tokens=int(max_new_tokens),
                           submitted_at=now,
                           due_at=now if due_at is None else float(due_at),
                           deadline_s=deadline_s,
                           sampling=sampling,
                           adapter_id=adapter_id)
        if sampling is not None:
            self.sampling_seen = True
        self.requests[uid] = r
        self.queue.append(r)
        return uid

    # -- bookkeeping helpers -------------------------------------------

    def _seen(self, r: ServingRequest) -> int:
        d = self.engine._seqs.get(r.uid)
        return d.seen_tokens if d else 0

    def _have_blocks(self, r: ServingRequest) -> int:
        d = self.engine._seqs.get(r.uid)
        return len(d.blocks) if d else 0

    def _preempt(self, r: ServingRequest) -> None:
        """Free a sequence's KV and requeue it at the front; its prefill
        target now includes the generated continuation (deterministic
        replay under greedy decoding)."""
        if r.uid in self.engine._seqs:
            self.engine.flush([r.uid])
        if self.drafter is not None:
            self.drafter.forget(r.uid)
        self.active.remove(r)
        r.state = QUEUED
        r.prefill_done = 0
        r.preemptions += 1
        self.preemptions += 1
        self.queue.appendleft(r)
        logger.info(
            f"serving: preempted uid {r.uid} ({len(r.generated)} tokens "
            f"generated) — KV pool pressure; requeued at front")

    def _park(self, r: ServingRequest) -> bool:
        """Park an admitted sequence host-ward instead of preempting it
        (ISSUE 15): its cold exclusive blocks spill to the tier (byte-
        exact), the descriptor and generated tokens stay, and a later
        tick fetches the bytes back — zero re-prefill compute, token-
        identical resume. Returns False when nothing was spillable (all
        blocks shared/hot) so the caller can fall back to preemption."""
        reclaimed = self.engine.spill_sequence(r.uid)
        if reclaimed <= 0:
            return False
        self.active.remove(r)
        r.parked_state = r.state
        r.state = PARKED
        self.parked.append(r)
        self.parks += 1
        logger.info(
            f"serving: parked uid {r.uid} ({reclaimed} KV blocks spilled "
            f"host-ward, {len(r.generated)} tokens kept) — KV pool "
            f"pressure; resumes via fetch, no re-prefill")
        return True

    def _unpark(self, r: ServingRequest) -> None:
        """Fetch a parked request's spilled blocks back into fresh pool
        slots and return it to the admitted set in its pre-park state."""
        self.engine.fetch_spilled(r.uid)
        self.parked.remove(r)
        r.state = r.parked_state or RUNNING
        r.parked_state = ""
        # re-enter at the request's ADMISSION-ORDER position, not the
        # tail: the park/preempt victim scans pick reversed(active) as
        # "youngest", so a tail append would re-victimize the unparked
        # request over genuinely younger ones, tick after tick
        idx = next((i for i, a in enumerate(self.active)
                    if a.submitted_at > r.submitted_at), len(self.active))
        self.active.insert(idx, r)
        self.unparks += 1

    def _finish(self, r: ServingRequest, now: float) -> None:
        r.state = FINISHED
        r.finished_at = now
        r.weight_version = self.engine.weight_version
        if r.uid in self.engine._seqs:
            # an early-stopped flush (ISSUE 16) tallies the KV blocks the
            # stop returned ahead of the request's budgeted lifetime
            self.engine.flush([r.uid], early_stop=r.stopped)
        if self.drafter is not None:
            self.drafter.forget(r.uid)
        if r in self.active:
            self.active.remove(r)
        if r in self.parked:
            self.parked.remove(r)

    def fail(self, r: ServingRequest, err: BaseException, now: float) -> None:
        """Terminally fail a request (deadline expiry, poison quarantine,
        retries exhausted): frees its KV, records the typed error on the
        request, and removes it from the queue/running set. Partial
        ``generated`` tokens stay readable on the request."""
        r.state = FAILED
        r.error = err
        r.finished_at = now
        if r.uid in self.engine._seqs:
            self.engine.flush([r.uid])
        if self.drafter is not None:
            self.drafter.forget(r.uid)
        if r in self.active:
            self.active.remove(r)
        if r in self.parked:
            self.parked.remove(r)
        if r in self.queue:
            self.queue.remove(r)
        logger.warning(f"serving: replica {self.replica_id} failed uid "
                       f"{r.uid}: {err}")

    def _expire_deadlines(self, now: float, events: list) -> None:
        """Fail every live request past its deadline (ISSUE 12). Runs at
        tick entry — the dispatch boundary — so an expiry never interleaves
        a half-executed tick, and the freed budget/KV goes to requests that
        can still meet theirs."""
        for r in [a for a in self.active] + list(self.parked) + list(self.queue):
            if r.deadline_s is None:
                continue
            elapsed = now - r.submitted_at
            if elapsed <= r.deadline_s:
                continue
            state = (f"state={r.state} queue_depth={len(self.queue)} "
                     f"running={len(self.active)} draining={self.draining}")
            err = DeadlineExceededError(r.uid, r.deadline_s, elapsed,
                                        self.replica_id, len(r.generated),
                                        state)
            self.fail(r, err, now)
            self.deadline_expired += 1
            events.append(("serving/deadline_expired",
                           self.deadline_expired, self.ticks))

    def _stop_hit(self, r: ServingRequest) -> bool:
        """Host-side stop-sequence check (ISSUE 16): does the generated
        stream now end with one of the request's stop sequences? EOS is
        the on-device flag; multi-token stop sequences are a suffix match
        on the small emitted list — the only per-token host work."""
        sp = r.sampling
        if sp is None or not sp.stop:
            return False
        g = r.generated
        return any(len(g) >= len(s) and tuple(g[-len(s):]) == s
                   for s in sp.stop)

    def _emit(self, r: ServingRequest, tok: int, now: float, events: list,
              eos: bool = False) -> None:
        r.generated.append(tok)
        if r.first_token_at is None:
            r.first_token_at = now
            events.append(("serving/ttft_s", now - r.submitted_at, self.ticks))
        elif r.last_token_at is not None:
            r.tpot_s.append(now - r.last_token_at)
            events.append(("serving/tpot_s", r.tpot_s[-1], self.ticks))
        r.last_token_at = now
        if r.adapter_id is not None:
            self.adapter_tokens[r.adapter_id] = \
                self.adapter_tokens.get(r.adapter_id, 0) + 1
        if self.on_token is not None:
            self.on_token(r.uid, tok)
        # EOS (the on-device flag) / stop sequence (host suffix match)
        # terminate the request at THIS tick: the stop token is kept in
        # ``generated``, the dead remainder of the budget never decodes,
        # and _finish returns the KV blocks and the running slot now
        if (eos or self._stop_hit(r)) and \
                len(r.generated) < r.max_new_tokens:
            r.stopped = True
            self.early_stops += 1
            self.dead_tokens_saved += r.max_new_tokens - len(r.generated)
        if r.done:
            self._finish(r, now)

    def _write_events(self, events: list) -> None:
        self.memory_monitor.write_events(events)
        for sink in self._sinks:
            sink.write_events(events)

    # -- the scheduling loop -------------------------------------------

    def tick(self) -> bool:
        """Pack one token-budget step and execute it as ONE dispatch.
        Returns True while admitted or queued work remains.

        Traced as one ``step("serve", n)`` that three spans cover end to
        end: ``serve/admit`` (everything before the dispatch),
        ``serve/dispatch`` (the engine call) and ``serve/emit`` (results to
        monitor events)."""
        with trace.step("serve", self.ticks):
            self._phase_span = trace.span("serve/admit")
            self._phase_span.__enter__()
            try:
                return self._tick()
            finally:
                self._phase_span.__exit__(None, None, None)

    def _next_phase(self, name: str) -> None:
        """Close the tick's open span and open the next: the phases abut."""
        self._phase_span.__exit__(None, None, None)
        self._phase_span = trace.span(name)
        self._phase_span.__enter__()

    def _tick(self) -> bool:
        eng, cfg = self.engine, self.cfg
        bs = eng.cache.block_size

        # -1.5) concurrency sanitizer (ISSUE 13): a tick can park
        # indefinitely (cold compile, wedged dispatch, the replica_hang
        # drill) — dispatching one while the calling thread holds any
        # instrumented lock beyond this replica's own guard is the PR 11
        # deadlock shape, reported with both stacks. Disarmed: one bool.
        sanitizer.check_blocking("scheduler.tick", allow=("Replica.lock",))

        # -1) fault sites (ISSUE 12, armed per replica id): all three land
        # HERE, at tick entry — the dispatch boundary, where a real
        # preemption becomes observable — so a tripped fault never leaves
        # a half-executed tick. A hang parks until the failover path
        # fences this scheduler (or the drill releases it); the fence
        # check right after makes the woken zombie emit nothing.
        if faults.ACTIVE:
            faults.maybe_hang("replica_hang", self.replica_id,
                              wake=lambda: self.fenced)
            faults.maybe_crash("replica_crash", self.replica_id,
                               exc=faults.ReplicaCrashed)
            faults.maybe_crash("tick_exception", self.replica_id)
        if self.fenced:
            return False

        # 0) tick boundary (ISSUE 11): a deferred weight commit
        # (reload_weights/publish_weights with defer=True) lands HERE —
        # the previous tick's dispatch has fully drained and the next has
        # not packed yet, so the swap can never interleave a half-executed
        # tick. KV pools, allocator, and compiled programs all survive;
        # live sequences continue (mixed-weight, no_commit) exactly as a
        # force swap would leave them, but at a defined boundary.
        if eng.has_pending_weights and eng.apply_pending_weights():
            logger.info(
                f"serving: replica {self.replica_id} applied deferred "
                f"weight swap at tick boundary (now version "
                f"{eng.weight_version})")

        # 0.5) request deadlines (ISSUE 12): expire before packing, so an
        # expired request's budget and KV blocks fund live ones this tick
        now0 = self.clock()
        pre_events: list = []
        self._expire_deadlines(now0, pre_events)
        if pre_events:
            self._write_events(pre_events)

        # 0.7) tiered KV (ISSUE 15): un-park in park order while the pool
        # can fund the fetch plus headroom (one block per running sequence
        # and one for the un-parked sequence's own next decode write) —
        # the conservative gate that keeps park/unpark from thrashing
        if self.tier is not None and self.parked:
            while self.parked and len(self.active) < cfg.max_running:
                r = self.parked[0]
                desc = eng._seqs.get(r.uid)
                need = len(desc.spilled) if desc is not None else 0
                headroom = 1 + sum(1 for a in self.active
                                   if a.state == RUNNING)
                if need + headroom > eng.free_blocks:
                    break
                self._unpark(r)

        # 1) decode set: every running sequence takes one budget slot — or
        # 1+k slots when its drafter proposes k tokens this tick (ISSUE 8:
        # the pending token plus the drafts are one verify row through the
        # same dispatch). Draft+verify tokens are accounted — budget AND
        # KV blocks — BEFORE any state mutation; if the pool can't hold
        # them, preempt the youngest admitted sequence until it can.
        spec_rows: Dict[int, List[int]] = {}
        if self.spec.enabled and self.drafter is not None:
            reqs = []
            for r in self.active:
                if r.state != RUNNING:
                    continue
                # constrained rows (ISSUE 16): a logit_mask changes the
                # target chain per step, which drafters can't see — masked
                # requests decode one token at a time
                if r.sampling is not None and r.sampling.logit_mask is not None:
                    continue
                # cap the draft width so an accepted run can never emit
                # past max_new_tokens or write past max_seq_len
                cap = min(self.spec.k,
                          r.max_new_tokens - len(r.generated) - 1,
                          eng.config.max_seq_len - self._seen(r) - 1)
                if cap >= 1:
                    reqs.append((r, r.prompt + r.generated, cap))
            if reqs:
                # batch-shaped drafters (the draft-model one) propose the
                # whole tick's rows in one pass — one sync put + one
                # decode_loop dispatch per k, not one dispatch per row
                many = getattr(self.drafter, "propose_many", None)
                if many is not None:
                    got = many([(r.uid, h, c) for r, h, c in reqs])
                else:
                    got = {r.uid: self.drafter.propose(r.uid, h, c)
                           for r, h, c in reqs}
                for r, _, cap in reqs:
                    drafts = got.get(r.uid) or []
                    if drafts:
                        spec_rows[r.uid] = ([r.generated[-1]]
                                            + [int(t) for t in drafts[:cap]])

        def row_cost(r):
            return len(spec_rows.get(r.uid, ())) or 1

        def decode_need(rs):
            return sum(max(0, blocks_needed(self._seen(r) + row_cost(r), bs)
                           - self._have_blocks(r)) for r in rs)

        while True:
            decodes = [r for r in self.active if r.state == RUNNING]
            if decode_need(decodes) <= eng.free_blocks or not self.active:
                break
            # draft widths are OPTIONAL work: before preempting anyone,
            # demote the youngest verify row to a plain decode token and
            # recheck — dropping a proposal costs nothing (the drafter
            # resyncs off the emitted history next tick), where a preempt
            # flushes KV and replays the whole prefill
            victim = next((r for r in reversed(self.active)
                           if r.uid in spec_rows), None)
            if victim is not None:
                spec_rows.pop(victim.uid)
                continue
            # tiered KV (ISSUE 15): spillable blocks are reclaimable-not-
            # free — park the youngest admitted sequence host-ward
            # (byte-exact spill, no lost work) before ever preempting one
            # (flush + full re-prefill replay). Preemption remains the
            # fallback when nothing is spillable (all blocks shared).
            if self.tier is not None:
                # youngest-first, but keep probing older actives when the
                # youngest has nothing spillable (all blocks shared via
                # the prefix cache, or all hot): preemption is the
                # fallback only when NOTHING on the replica can spill
                pv = next((r for r in reversed(self.active)
                           if r.uid in eng._seqs and self._park(r)), None)
                if pv is not None:
                    spec_rows.pop(pv.uid, None)
                    continue
            self._preempt(self.active[-1])

        decode_cost = sum(row_cost(r) for r in decodes)
        budget_left = cfg.token_budget - decode_cost
        free_left = eng.free_blocks - decode_need(decodes)

        # 2) fill the remainder with prefill chunks: partially-prefilled
        # actives first (admission order), then FIFO admission from the
        # queue while the running-set cap and KV pressure allow. Strict
        # head-of-line order — a request never overtakes an earlier one
        # into the prefill lane, so admission is starvation-free.
        prefills: List[Tuple[ServingRequest, List[int]]] = []
        admitted: List[Tuple[ServingRequest, int]] = []
        for r in [a for a in self.active if a.state == PREFILL] + list(self.queue):
            if budget_left <= 0:
                break
            from_queue = r.state == QUEUED
            if from_queue and r.not_before > now0:
                # failover backoff (ISSUE 12): a re-placed request yields
                # its packing slot until its backoff window passes — the
                # one sanctioned exception to strict FIFO, since holding
                # the head would stall every request behind it for the
                # whole backoff
                continue
            if from_queue and r.adapter_id is not None and \
                    self.apool is not None:
                # multi-tenant LoRA (ISSUE 18): can the pool seat this
                # request's adapter ALONGSIDE everything already planned
                # this tick (batch-aware — a plan may not evict its own
                # hits)? If not, park in place: the request keeps its
                # FIFO seat, younger base-model or resident-adapter work
                # may pass it, and NO running sequence is ever preempted
                # for an adapter slot. The actual acquire happens at the
                # admission commit below, so a loop that breaks early
                # mutates nothing.
                want = [a.adapter_id for a, _ in admitted] + [r.adapter_id]
                if not self.apool.can_acquire_all(want)[0]:
                    if not r.adapter_waiting:
                        r.adapter_waiting = True
                        self.adapter_parks += 1
                    continue
            if from_queue and getattr(eng, "_moe_serving", False) and \
                    self.cfg.moe.overload_policy == "park" and \
                    (self.active or admitted) and \
                    eng.moe_pressure() > self.cfg.moe.overload_threshold:
                # expert capacity is the next admission resource after KV
                # blocks, tier residency, and adapter slots (ISSUE 19):
                # the previous tick's routing counts say some expert ran
                # past its buffer, so hold NEW sequences at their FIFO
                # seat — running ticks keep decoding (their routing is
                # what drains the pressure) and no sequence is ever
                # preempted for expert load. The ``active or admitted``
                # guard keeps a stale reading with nothing running from
                # parking the whole queue forever. Policy "drop" admits
                # anyway and lets the capacity impl drop overload tokens
                # on device (counted in moe/dropped).
                if not r.moe_waiting:
                    r.moe_waiting = True
                    self.moe_capacity_parks += 1
                continue
            if from_queue and self.parked and \
                    self.parked[0].submitted_at <= r.submitted_at:
                # tiered KV (ISSUE 15): freed blocks must fund the oldest
                # parked fetch before any YOUNGER arrival may consume
                # them — otherwise sustained arrivals absorb every freed
                # block chunk-by-chunk and the parked head starves
                # against the all-at-once unpark gate. Seniority is by
                # submission time, not queue-vs-parked lane: a preempted
                # request re-queued at the front can be OLDER than every
                # parked sequence and then packs ahead of them. Stop the
                # queue lane at the first younger request; in-flight
                # prefills above still pack (finishing them is what
                # frees blocks).
                break
            if from_queue and len(self.active) + len(admitted) >= cfg.max_running:
                break
            target = r.prefill_target
            if from_queue:
                # prefix cache: plan the admission from the first
                # NON-CACHED token — a LIVE shared block costs zero free
                # slots, a parked one only its revival slot (the engine
                # acquisition happens at the admission commit below, so a
                # packing loop that breaks early mutates nothing)
                hit, live, _parked = eng.prefix_peek(target)
                pd, free_have = hit, live
            else:
                pd, free_have = r.prefill_done, self._have_blocks(r)
            remaining = len(target) - pd
            chunk = min(budget_left, remaining)
            # a leftover-budget sliver that does not finish the prompt is
            # not worth a dispatch slot — wait for a fuller tick
            if chunk < remaining and chunk < cfg.chunk_min:
                break
            fit = (free_left + free_have) * bs - pd
            chunk = min(chunk, fit)
            if chunk <= 0 or (chunk < remaining and chunk < cfg.chunk_min):
                break
            free_left -= max(0, blocks_needed(pd + chunk, bs) - free_have)
            budget_left -= chunk
            prefills.append((r, target[pd:pd + chunk]))
            if from_queue:
                admitted.append((r, pd))
                if r.adapter_waiting:
                    r.adapter_waiting = False
                    self.adapter_unparks += 1
                if r.moe_waiting:
                    r.moe_waiting = False
                    self.moe_unparks += 1
        for r, hit in admitted:
            self.queue.remove(r)
            self.active.append(r)
            r.state = PREFILL
            # multi-tenant LoRA (ISSUE 18): stage the adapter binding
            # BEFORE the engine admission — acquire_prefix consumes the
            # pending binding and pins the pool slot, so the descriptor
            # is born adapter-bound and this very tick's chunk already
            # runs under the adapter's slot row
            if r.adapter_id is not None:
                eng.configure_adapter(r.uid, r.adapter_id)
            # admit in the engine NOW so shared prefix blocks are
            # ref-counted before the dispatch: the descriptor starts at
            # the cached boundary and this tick's chunk prefills only the
            # suffix (acquire_prefix is a cold admission when
            # prefix_caching is off — hit is 0 either way then)
            got = eng.acquire_prefix(r.uid, r.prefill_target)
            assert got == hit, (r.uid, got, hit)
            r.prefill_done = hit
            # one-dispatch sampling (ISSUE 16): the descriptor exists now
            # — attach the request's SamplingParams so the sampled step's
            # per-row operands pick them up from the first chunk onward
            if r.sampling is not None:
                eng.configure_sampling(r.uid, r.sampling)

        # 3) nothing packable?
        if not decodes and not prefills:
            if not (self.active or self.queue or self.parked):
                return False
            if self.parked and not self.active:
                # tiered KV (ISSUE 15): everything admitted is parked —
                # force-unpark the oldest past the headroom gate (nothing
                # else will free blocks) so progress resumes next tick.
                # The fetch must ALSO fund the sequence's own next decode
                # write when it sits on a block boundary: an equality
                # admit there leaves free_blocks == 0, the next tick
                # parks it right back, and the park/unpark pair livelocks
                # serve() without ever reaching the loud error below.
                r = self.parked[0]
                desc = eng._seqs.get(r.uid)
                need = len(desc.spilled) if desc is not None else 0
                if desc is not None and desc.seen_tokens % \
                        eng.config.kv_block_size == 0:
                    need += 1
                if desc is not None and need > eng.free_blocks:
                    # the OTHER parked sequences' hot tails
                    # (hot_block_fraction keeps them resident through
                    # _park) are reclaimable — spill them fully before
                    # declaring a stall the pool could still serve
                    for other in self.parked[1:]:
                        if eng.free_blocks >= need:
                            break
                        if other.uid in eng._seqs:
                            eng.spill_sequence(other.uid, keep_hot=0)
                if desc is not None and need <= eng.free_blocks:
                    self._unpark(r)
                    # this early return skips the tick-tail cache
                    # refresh, and the fetch just moved block state
                    self._spillable_cache = eng.spillable_blocks()
                    return True
                raise RuntimeError(
                    f"serving stalled: parked uid {r.uid} needs "
                    f"{need} KV blocks (spilled fetch + next decode "
                    f"write) but only {eng.free_blocks} of "
                    f"{eng.allocator.num_blocks} are free and nothing is "
                    f"running to release more; raise num_kv_blocks")
            if any(r.not_before > now0 or r.adapter_waiting
                   or r.moe_waiting for r in self.queue):
                # everything eligible is in its failover backoff window
                # or parked on adapter-pool residency — work remains, it
                # just may not pack yet (running/parked sequences release
                # slots as they finish)
                return True
            head = next((r for r in self.active if r.state == PREFILL),
                        self.queue[0] if self.queue else None)
            if head is None:     # running set exists; it will free budget
                return True
            raise RuntimeError(
                f"serving stalled: uid {head.uid} needs "
                f"{blocks_needed(len(head.prefill_target), bs)} KV blocks "
                f"for its prefill but only {eng.free_blocks} of "
                f"{eng.allocator.num_blocks} are free and nothing is "
                f"running to release more; raise num_kv_blocks or lower "
                f"max_running/concurrency")

        # 4) ONE mixed dispatch for the whole tick: plain decode rows,
        # prefill chunk rows, and speculative verify rows all ride it
        self.ticks += 1
        packed = decode_cost + sum(len(c) for _, c in prefills)
        spec_batch = [(r, spec_rows[r.uid]) for r in decodes
                      if r.uid in spec_rows]
        plain = [r for r in decodes if r.uid not in spec_rows]
        # one-dispatch sampling (ISSUE 16): any participant carrying
        # SamplingParams flips the WHOLE tick onto step_sampled — greedy
        # rows inside it are bit-identical to step()'s argmax chain, and
        # logits never ship to host. A tick with no sampled participant
        # keeps the historical step() path byte-for-byte.
        sampled = any(r.sampling is not None
                      for r in decodes) or any(r.sampling is not None
                                               for r, _ in prefills)
        t0 = self.clock()
        for r, _ in prefills:
            if r.first_scheduled_at is None:
                r.first_scheduled_at = t0
                # a mark in the trace: how long this request queued
                with trace.span("serve/first_schedule",
                                wait_ms=1e3 * (t0 - r.due)):
                    pass
        self._next_phase("serve/dispatch")
        dtoks = ddone = ptoks = pdone = None
        if sampled:
            out = eng.step_sampled(
                [r.uid for r in plain], [r.generated[-1] for r in plain],
                [(r.uid, c) for r, c in prefills],
                speculative=[(r.uid, c) for r, c in spec_batch])
            dtoks, ddone, ptoks, pdone = out[:4]
            sres = out[4] if spec_batch else []
        elif spec_batch:
            dlogits, plogits, sres = eng.step(
                [r.uid for r in plain], [r.generated[-1] for r in plain],
                [(r.uid, c) for r, c in prefills],
                speculative=[(r.uid, c) for r, c in spec_batch])
        else:
            dlogits, plogits = eng.step(
                [r.uid for r in plain], [r.generated[-1] for r in plain],
                [(r.uid, c) for r, c in prefills])
            sres = []
        tick_s = self.clock() - t0
        self._next_phase("serve/emit")
        if self.fenced:
            # the health layer declared this replica dead while the
            # dispatch was in flight: its requests were snapshotted and
            # re-placed on survivors — emitting now would duplicate tokens
            return False

        # 5) results: decode tokens stream immediately; a verify row
        # streams its accepted drafts plus the verifier's correction/bonus
        # token (every one the exact greedy/seeded chain); a finished
        # prefill yields the sequence's next token (its FIRST for fresh
        # requests)
        now = self.clock()
        events: list = []
        for i, r in enumerate(plain):
            r.decode_ticks += 1
            if sampled:
                self._emit(r, int(dtoks[i]), now, events,
                           eos=bool(ddone[i]))
            else:
                self._emit(r, int(np.argmax(dlogits[i])), now, events)
        for (r, chunk), (a, emitted) in zip(spec_batch, sres):
            j = len(chunk) - 1
            r.decode_ticks += 1
            self.spec_proposed += j
            self.spec_accepted += a
            self.spec_rejected += j - a
            sp = r.sampling
            if sp is not None and sp.temperature > 0 and a < j:
                # the residual-resample event (Leviathan): the chain
                # replaced the first rejected draft with its own token
                self.sampling_resamples += 1
            eos_id = sp.eos_token_id if sp is not None else -1
            for t in emitted:
                self._emit(r, int(t), now, events,
                           eos=(eos_id >= 0 and int(t) == eos_id))
                if r.done:
                    # EOS/stop inside the accepted run: the tokens after
                    # it are dead — never emitted, request already flushed
                    break
        for i, (r, chunk) in enumerate(prefills):
            r.prefill_done += len(chunk)
            if r.prefill_done == len(r.prefill_target):
                r.state = RUNNING
                if sampled:
                    self._emit(r, int(ptoks[i]), now, events,
                               eos=bool(pdone[i]))
                else:
                    self._emit(r, int(np.argmax(plogits[i])), now, events)
        events += [
            ("serving/queue_depth", len(self.queue), self.ticks),
            ("serving/running", len(decodes), self.ticks),
            ("serving/budget_fill", packed / cfg.token_budget, self.ticks),
            ("serving/kv_free_blocks", eng.free_blocks, self.ticks),
            ("serving/tick_s", tick_s, self.ticks),
            ("serving/preemptions", self.preemptions, self.ticks),
            # prefix-cache group (cumulative engine counters; ISSUE 6):
            # hit/miss tokens say how much prefill the cache absorbed,
            # cow_copies counts divergence clones, shared_blocks is the
            # CURRENT cross-sequence sharing in the pool
            ("prefix_cache/hit_tokens", eng.prefix_hit_tokens, self.ticks),
            ("prefix_cache/miss_tokens", eng.prefix_miss_tokens, self.ticks),
            ("prefix_cache/cow_copies", eng.cow_copies, self.ticks),
            ("prefix_cache/shared_blocks", eng.allocator.shared_blocks,
             self.ticks),
            # weight-version watermark (ISSUE 11): every tick records the
            # serving weight version its tokens were sampled under, so a
            # post-mortem can line the event stream up against the RLHF
            # replay log's per-rollout versions
            ("weights/version", eng.weight_version, self.ticks),
        ]
        if self.spec.enabled:
            # speculative group (cumulative; ISSUE 8): proposed/accepted/
            # rejected count draft tokens, acceptance_rate is their ratio,
            # rollbacks counts the engine's rejected-draft KV rewinds
            events += [
                ("speculative/proposed", self.spec_proposed, self.ticks),
                ("speculative/accepted", self.spec_accepted, self.ticks),
                ("speculative/rejected", self.spec_rejected, self.ticks),
                ("speculative/acceptance_rate",
                 self.spec_accepted / max(1, self.spec_proposed), self.ticks),
                ("speculative/rollbacks", eng.spec_rollbacks, self.ticks),
            ]
        if self.sampling_seen:
            # sampling group (cumulative; ISSUE 16): early_stops counts
            # EOS/stop-sequence terminations, dead_tokens_saved the budget
            # tokens they never decoded (the goodput lever), resamples the
            # speculative residual-resample events at temperature>0, and
            # early_stop_freed_blocks the KV the stops returned early
            events += [
                ("sampling/early_stops", self.early_stops, self.ticks),
                ("sampling/dead_tokens_saved", self.dead_tokens_saved,
                 self.ticks),
                ("sampling/resamples", self.sampling_resamples, self.ticks),
                ("sampling/early_stop_freed_blocks",
                 eng.early_stop_freed_blocks, self.ticks),
            ]
        if self.tier is not None:
            # tiered-KV group (ISSUE 15): spill/fetch traffic, prefetch
            # effectiveness, and the current host-tier footprint
            ts = self.tier.stats()
            events += [
                ("kv_tier/spills", ts["spills"], self.ticks),
                ("kv_tier/fetches", ts["fetches"], self.ticks),
                ("kv_tier/hit_rate",
                 ts["hit_rate"] if ts["hit_rate"] is not None else 0.0,
                 self.ticks),
                ("kv_tier/prefetch_misses", ts["prefetch_misses"],
                 self.ticks),
                ("kv_tier/spilled_blocks", ts["spilled_blocks"], self.ticks),
                ("kv_tier/host_bytes", ts["host_bytes"], self.ticks),
                ("kv_tier/parked", len(self.parked), self.ticks),
                ("kv_tier/parks", self.parks, self.ticks),
                ("kv_tier/unparks", self.unparks, self.ticks),
            ]
            # double-buffered prefetch (ISSUE 15): stage the next
            # ``prefetch_depth`` parked sequences' host bytes into pinned
            # buffers NOW — one tick ahead of the decode window they
            # rejoin — so their fetch is only the device scatter
            depth = max(0, eng.config.kv_tier.prefetch_depth)
            for r in self.parked[:depth]:
                self.tier.prefetch(r.uid)
        if self.apool is not None:
            # multi-tenant LoRA group (ISSUE 18): pool traffic plus the
            # scheduler's residency parks — a park is a FIFO-seat yield,
            # never a preemption, so adapter pressure shows up here as
            # queue time, not re-prefill compute
            ast = self.apool.stats()
            events += [
                ("adapter/hits", ast["hits"], self.ticks),
                ("adapter/misses", ast["misses"], self.ticks),
                ("adapter/evictions", ast["evictions"], self.ticks),
                ("adapter/parks", self.adapter_parks, self.ticks),
                ("adapter/unparks", self.adapter_unparks, self.ticks),
                ("adapter/active_adapters", ast["resident"], self.ticks),
            ]
            for aid in sorted(self.adapter_tokens):
                events.append((f"adapter/tokens/{aid}",
                               self.adapter_tokens[aid], self.ticks))
            # double-buffered adapter prefetch (the kv_tier discipline):
            # stage the next waiting adapters' padded factor planes into
            # pinned buffers one tick ahead of the admission that will
            # install them, so the acquire-miss copy is pinned-host ->
            # device only
            depth = max(0, eng.config.adapters.prefetch_depth)
            staged = 0
            seen: set = set()
            for r in self.queue:
                if staged >= depth:
                    break
                aid = r.adapter_id
                if aid is None or aid in seen or \
                        self.apool.slot_of(aid) is not None:
                    continue
                self.apool.prefetch(aid)
                seen.add(aid)
                staged += 1
        if getattr(eng, "_moe_serving", False):
            # expert-parallel MoE group (ISSUE 19): routing traffic from
            # the engine's per-tick counts (dispatched assignments, drops
            # at expert capacity, peak per-(layer, expert) load) plus the
            # scheduler's capacity parks — like adapter parks, a park is
            # a FIFO-seat yield under expert pressure, never a preemption
            events += [
                ("moe/dispatched", eng.moe_dispatched, self.ticks),
                ("moe/dropped", eng.moe_dropped, self.ticks),
                ("moe/capacity_parks", self.moe_capacity_parks, self.ticks),
                ("moe/expert_load_max", eng.moe_expert_load_max, self.ticks),
            ]
        # block state settled for this tick — refresh the placement-
        # pressure cache HERE, on the tick thread, where the _seqs walk
        # is safe (see __init__); load() only ever reads the int
        if self.tier is not None:
            self._spillable_cache = eng.spillable_blocks()
        self._write_events(events)
        return bool(self.active or self.queue or self.parked)

    # -- elastic drain / requeue (ISSUE 7) ------------------------------

    def export_requests(self) -> List[ServingRequest]:
        """Stop admitting, preempt every admitted sequence, and hand back
        ALL unfinished requests as requeue-able descriptors, oldest first.

        The elastic-drain half of the scheduler contract: a SIGTERM'd (or
        scaled-away) replica frees its whole KV pool here and the router
        front-requeues the returned requests on surviving replicas — each
        carries its generated continuation, so the replay elsewhere is
        token-identical under greedy decoding (the same discipline as
        ``_preempt``, applied fleet-wide). After this call the scheduler
        refuses new submits (``draining``) and holds no requests: nothing
        can be lost or served twice."""
        self.draining = True
        # active is admission order (oldest first); preempting frees KV and
        # folds the continuation into each request's prefill target.
        # Parked requests (ISSUE 15) drain the same way — flush drops both
        # their resident blocks and their host-tier entry, and the replay
        # elsewhere re-prefills prompt + generated token-identically.
        exported: List[ServingRequest] = []
        for r in list(self.active) + list(self.parked):
            if r.uid in self.engine._seqs:
                self.engine.flush([r.uid])
            if self.drafter is not None:
                self.drafter.forget(r.uid)
            r.state = QUEUED
            r.prefill_done = 0
            r.parked_state = ""
            r.preemptions += 1
            self.preemptions += 1
            exported.append(r)
        exported.extend(self.queue)
        self.active.clear()
        self.parked.clear()
        self.queue.clear()
        self._spillable_cache = 0
        for r in exported:
            # residency parks are THIS pool's state — a re-placed request
            # re-evaluates against the destination replica's pool
            r.adapter_waiting = False
            r.moe_waiting = False
            self.requests.pop(r.uid, None)
        self._write_events([
            ("serving/drained_requests", len(exported), self.ticks),
            ("serving/queue_depth", 0, self.ticks),
        ])
        if exported:
            logger.info(
                f"serving: replica {self.replica_id} drained — "
                f"{len(exported)} unfinished requests exported for requeue")
        return exported

    @atomic_on_reject(check="validate")
    def inject(self, r: ServingRequest, front: bool = True) -> None:
        """Adopt a request exported from another replica, by default at the
        FRONT of the queue (a drained request is older than anything queued
        here — front placement preserves fleet-wide FIFO fairness). The
        request's generated continuation rides along in its prefill target,
        so serving resumes token-identically."""
        if self.draining:
            raise RuntimeError(
                f"replica {self.replica_id} is draining and admits no new "
                f"requests (route to a surviving replica)")
        if r.uid in self.requests or r.uid in self.engine._seqs:
            raise ValueError(f"uid {r.uid} is already live on replica "
                             f"{self.replica_id}")
        eng = self.engine
        total = len(r.prompt) + r.max_new_tokens
        if total > eng.config.max_seq_len:
            raise ValueError(
                f"replica {self.replica_id}: request {r.uid} needs "
                f"{total} tokens but max_seq_len is "
                f"{eng.config.max_seq_len}; route it to a bigger replica")
        usable = eng.allocator.num_blocks - 1
        need_max = blocks_needed(total, eng.cache.block_size)
        if need_max > usable:
            raise ValueError(
                f"replica {self.replica_id}: request needs up to {need_max} "
                f"KV blocks but the pool has {usable} usable; route it to a "
                f"bigger replica")
        if r.adapter_id is not None and (
                self.apool is None or not self.apool.registered(r.adapter_id)):
            raise ValueError(
                f"replica {self.replica_id}: request {r.uid} needs adapter "
                f"{r.adapter_id!r} which is not registered here; "
                f"publish_adapter to this replica first")
        r.state = QUEUED
        r.prefill_done = 0
        r.adapter_waiting = False
        r.moe_waiting = False
        if r.sampling is not None:
            # the seed rides the request (ISSUE 16): its re-prefill replay
            # resumes the SAME seeded chain at the same absolute positions
            self.sampling_seen = True
        self.requests[r.uid] = r
        if front:
            self.queue.appendleft(r)
        else:
            self.queue.append(r)

    @atomic_on_reject(check="validate")
    def adopt_running(self, r: ServingRequest) -> None:
        """Adopt a request whose KV was MIGRATED into this replica's
        engine (hung-replica failover, ISSUE 12): the sequence is already
        live engine-side (``commit_import``), so it enters the running
        set directly and its next tick is a plain decode token — zero
        re-prefill tokens. Everything is validated before any mutation; a
        refusal leaves both scheduler and engine untouched, and the
        caller falls back to ``inject()`` (drain-replay re-prefill)."""
        if self.draining:
            raise RuntimeError(
                f"replica {self.replica_id} is draining and admits no new "
                f"requests (route to a surviving replica)")
        if r.uid in self.requests:
            raise ValueError(f"uid {r.uid} is already live on replica "
                             f"{self.replica_id}")
        if not r.generated:
            raise ValueError(
                f"uid {r.uid} has no generated tokens — a migrated "
                f"sequence must be mid-decode; inject() fresh requests")
        desc = self.engine._seqs.get(r.uid)
        if desc is None:
            raise ValueError(
                f"uid {r.uid} has no imported KV on replica "
                f"{self.replica_id} — commit_import first, or inject() "
                f"for re-prefill")
        want = len(r.prompt) + len(r.generated) - 1
        if desc.seen_tokens != want:
            raise ValueError(
                f"uid {r.uid}: imported KV covers {desc.seen_tokens} "
                f"tokens but the request's history needs {want} (prompt "
                f"{len(r.prompt)} + generated {len(r.generated)} - 1 "
                f"pending); the migrated pool state is torn")
        total = len(r.prompt) + r.max_new_tokens
        if total > self.engine.config.max_seq_len:
            raise ValueError(
                f"replica {self.replica_id}: request {r.uid} needs {total} "
                f"tokens but max_seq_len is "
                f"{self.engine.config.max_seq_len}")
        if len(self.active) >= self.cfg.max_running:
            raise RuntimeError(
                f"replica {self.replica_id}: running set is at max_running"
                f"={self.cfg.max_running}; requeue uid {r.uid} instead")
        if r.adapter_id is not None and (
                self.apool is None or not self.apool.registered(r.adapter_id)):
            raise ValueError(
                f"replica {self.replica_id}: request {r.uid} needs adapter "
                f"{r.adapter_id!r} which is not registered here; "
                f"publish_adapter to this replica first")
        if r.adapter_id is not None:
            # the migrated descriptor is live but adapter-unbound (slot
            # indices are replica-local); rebind so the next decode tick
            # runs under this pool's slot for the same adapter. May page
            # the adapter in — a refusal (pool fully pinned) lands before
            # any scheduler mutation, so the caller falls back to
            # inject() like any other adoption refusal.
            self.engine.configure_adapter(r.uid, r.adapter_id)
        r.state = RUNNING
        r.prefill_done = len(r.prompt) + len(r.generated)
        r.adapter_waiting = False
        r.moe_waiting = False
        if r.sampling is not None:
            self.sampling_seen = True
            self.engine.configure_sampling(r.uid, r.sampling)
        self.requests[r.uid] = r
        self.active.append(r)

    def knobs(self) -> Dict[str, object]:
        """The effective tunable-knob point this replica serves at
        (ISSUE 14 introspection): the serving families the autotuner
        searches — packing shape, derived chunk/k ladders, speculation —
        plus the engine's storage/kernel modes. Autotuner trial logs
        record this dict verbatim, so a winner's provenance names the
        exact knobs it was measured with, and a fleet post-mortem can
        diff what each replica actually ran."""
        ecfg = self.engine.config
        out = dict(self.cfg.knob_values())
        out.update({
            "decode_kernel": getattr(self.engine, "_decode_kernel",
                                     ecfg.decode_kernel),
            "kv_cache_dtype": ecfg.kv_cache_dtype,
            "prefix_caching": ecfg.prefix_caching,
            "kv_block_size": ecfg.kv_block_size,
            "num_kv_blocks": ecfg.num_kv_blocks,
            "spill_enabled": ecfg.kv_tier.enabled,
            "hot_block_fraction": ecfg.kv_tier.hot_block_fraction,
            "prefetch_depth": ecfg.kv_tier.prefetch_depth,
            "adapter_slots": (ecfg.adapters.slots
                              if ecfg.adapters.enabled else 0),
            "adapter_prefetch_depth": (ecfg.adapters.prefetch_depth
                                       if ecfg.adapters.enabled else 0),
        })
        return out

    def load(self) -> Dict[str, object]:
        """Cheap placement snapshot for the router: queue depth, running
        set, and KV-pool pressure, every tick-independent number the
        placement score needs."""
        eng = self.engine
        usable = max(1, eng.allocator.num_blocks - 1)
        # tier-aware pressure (ISSUE 15): spillable blocks are reclaimable
        # — a replica that could spill its way to room is less pressured
        # than its raw free count says, so the router's placement sees
        # free + spillable over usable. A plain int read: load() runs on
        # router threads, so it must never walk eng._seqs itself (the
        # tick thread refreshes the cache; see __init__)
        spillable = self._spillable_cache if self.tier is not None else 0
        return {
            "replica_id": self.replica_id,
            "queue_depth": len(self.queue),
            "running": len(self.active),
            "parked": len(self.parked),
            "free_blocks": eng.free_blocks,
            "spillable_blocks": spillable,
            "kv_pressure": max(
                0.0, 1.0 - (eng.free_blocks + spillable) / usable),
            "draining": self.draining,
            # multi-tenant LoRA (ISSUE 18): the placement-affinity signal
            # — a request routes toward a replica whose pool already
            # holds its adapter. The pool takes its own lock, so this is
            # safe from router threads like the rest of load().
            "resident_adapters": ([] if self.apool is None
                                  else self.apool.resident_ids()),
        }

    # -- drivers --------------------------------------------------------

    def drain(self) -> None:
        """Tick until every admitted and queued request finishes."""
        while self.tick():
            pass

    def serve(self, requests: Sequence[Union[Sequence[int], Tuple[Sequence[int], int]]],
              max_new_tokens: int = 32,
              arrivals: Optional[Sequence[float]] = None,
              deadline_s: Optional[float] = None,
              sampling: Optional[Union[SamplingParams,
                                       Sequence[Optional[SamplingParams]]]]
              = None,
              adapter_ids: Optional[Sequence[Optional[str]]] = None
              ) -> Dict[int, List[int]]:
        """Serve a batch of requests to completion, continuous-batching
        style. ``requests``: prompts, or ``(prompt, max_new)`` pairs.
        ``arrivals``: optional arrival offsets in seconds (e.g. a Poisson
        trace) — request i is submitted once ``clock() - t0 >=
        arrivals[i]``; None submits everything up front. ``deadline_s``
        applies one per-request deadline to every submission (an expired
        request FAILS with its partial tokens retained). ``sampling``
        (ISSUE 16): one SamplingParams for every request, or a per-request
        sequence (None entries run greedy). ``adapter_ids`` (ISSUE 18):
        per-request adapter names (None entries serve the base model) —
        a mixed trace exercises the multi-tenant pool. Returns ``{uid:
        generated tokens}`` in submission order."""
        items = []
        for req in requests:
            if (isinstance(req, tuple) and len(req) == 2
                    and not isinstance(req[1], (list, np.ndarray))):
                items.append((list(req[0]), int(req[1])))
            else:
                items.append((list(req), int(max_new_tokens)))
        if arrivals is not None and len(arrivals) != len(items):
            raise ValueError("arrivals must align with requests")
        if isinstance(sampling, SamplingParams) or sampling is None:
            samplings: List[Optional[SamplingParams]] = [sampling] * len(items)
        else:
            samplings = list(sampling)
            if len(samplings) != len(items):
                raise ValueError("sampling must align with requests")
        if adapter_ids is None:
            aids: List[Optional[str]] = [None] * len(items)
        else:
            aids = list(adapter_ids)
            if len(aids) != len(items):
                raise ValueError("adapter_ids must align with requests")
        pending = deque(enumerate(items))
        t0 = self.clock()
        uids: List[int] = []
        while pending or self.active or self.queue or self.parked:
            while pending and (arrivals is None
                               or self.clock() - t0 >= arrivals[pending[0][0]]):
                i, (prompt, mn) = pending.popleft()
                uids.append(self.submit(
                    prompt, max_new_tokens=mn, deadline_s=deadline_s,
                    sampling=samplings[i], adapter_id=aids[i],
                    due_at=None if arrivals is None else t0 + arrivals[i]))
            if not self.tick() and pending and arrivals is not None:
                # idle: sleep until the next arrival is due (clock() may be
                # a test fake, so never pass a negative to sleep)
                wait = arrivals[pending[0][0]] - (self.clock() - t0)
                if wait > 0:
                    time.sleep(wait)
        return {uid: self.requests[uid].generated for uid in uids}

    # -- observability --------------------------------------------------

    def stats(self) -> Dict[str, object]:
        """Serving-quality summary over finished requests: sustained
        tokens/s (wall span from first submit to last finish), TTFT/TPOT
        p50/p95/p99 (tail latency is what a production SLO binds on, not
        the median), prefix-cache effectiveness, preemption and tick
        counts."""

        def pct(xs, q):
            return float(np.percentile(xs, q)) if len(xs) else None

        done = [r for r in self.requests.values() if r.state == FINISHED]
        # from the time the request was due: what an open-loop client waits
        ttft = [r.first_token_at - r.due for r in done
                if r.first_token_at is not None]
        wait = [r.first_scheduled_at - r.due for r in done
                if r.first_scheduled_at is not None]
        tpot = [t for r in done for t in r.tpot_s]
        total = sum(len(r.generated) for r in done)
        span = (max(r.finished_at for r in done)
                - min(r.submitted_at for r in done)) if done else 0.0
        eng = self.engine
        hit, miss = eng.prefix_hit_tokens, eng.prefix_miss_tokens
        return {
            "replica_id": self.replica_id,
            "queue_depth": len(self.queue),
            "running": len(self.active),
            "draining": self.draining,
            "requests": len(done),
            "generated_tokens": total,
            "sustained_tokens_per_sec": (total / span) if span > 0 else None,
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p95_s": pct(ttft, 95),
            "ttft_p99_s": pct(ttft, 99),
            "queue_wait_p50_s": pct(wait, 50),
            "queue_wait_p95_s": pct(wait, 95),
            "tpot_p50_s": pct(tpot, 50),
            "tpot_p95_s": pct(tpot, 95),
            "tpot_p99_s": pct(tpot, 99),
            "ticks": self.ticks,
            "preemptions": self.preemptions,
            # tiered KV (ISSUE 15): None when kv_tier is off; with it on,
            # the host-tier traffic + park/unpark counts — parks that did
            # NOT become preemptions are re-prefill compute saved
            "kv_tier": (None if self.tier is None else {
                **self.tier.stats(),
                "parks": self.parks,
                "unparks": self.unparks,
                "parked": len(self.parked),
            }),
            # request-level robustness (ISSUE 12): terminally-failed
            # requests by cause — deadline expiries counted here, poison
            # quarantines / exhausted retries land via router fail()s
            "failed": sum(1 for r in self.requests.values()
                          if r.state == FAILED),
            "deadline_expired": self.deadline_expired,
            "compiled_programs": len(self.engine.program_shapes),
            "weight_version": eng.weight_version,
            "prefix_cache": {
                "hit_tokens": hit,
                "miss_tokens": miss,
                "hit_rate": (hit / (hit + miss)) if (hit + miss) else None,
                "cow_copies": eng.cow_copies,
                "shared_blocks": eng.allocator.shared_blocks,
            },
            # ISSUE 8: the steps-per-token lever — with speculation on,
            # ticks per emitted token falls below 1 as acceptance rises
            # (the target is < 0.67 at k=4 on repetitive suffixes)
            "speculative": {
                "enabled": self.spec.enabled,
                "k": self.spec.k if self.spec.enabled else 0,
                "drafter": (type(self.drafter).__name__
                            if self.drafter is not None else None),
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "rejected": self.spec_rejected,
                "acceptance_rate": (self.spec_accepted / self.spec_proposed
                                    if self.spec_proposed else None),
                "rollbacks": eng.spec_rollbacks,
                "rolled_back_tokens": eng.spec_rolled_tokens,
                # per-sequence decode ticks per emitted token (the first
                # token of each request comes from prefill, so a k=0 run
                # measures (n-1)/n, and acceptance pushes it toward
                # 1/(k+1)); batching does NOT deflate this the way
                # ticks/total would
                "steps_per_emitted_token": (
                    sum(r.decode_ticks for r in done) / total if total
                    else None),
            },
            # one-dispatch sampling (ISSUE 16): early-stop effectiveness —
            # dead_tokens_saved is decode budget EOS/stop returned to the
            # pool, early_stop_freed_blocks the KV it released early
            "sampling": {
                "seen": self.sampling_seen,
                "early_stops": self.early_stops,
                "dead_tokens_saved": self.dead_tokens_saved,
                "resamples": self.sampling_resamples,
                "early_stop_freed_blocks": eng.early_stop_freed_blocks,
            },
            # multi-tenant LoRA (ISSUE 18): None when the pool is off;
            # with it on, pool traffic + the scheduler's residency parks
            # (FIFO-seat yields, never preemptions) and the per-adapter
            # emitted-token tally per-tenant billing reads
            "adapters": (None if self.apool is None else {
                **self.apool.stats(),
                "parks": self.adapter_parks,
                "unparks": self.adapter_unparks,
                "waiting": sum(1 for r in self.queue if r.adapter_waiting),
                "tokens_by_adapter": dict(self.adapter_tokens),
            }),
            # expert-parallel MoE serving (ISSUE 19): None on dense models;
            # with experts live, the routed-token traffic plus the
            # scheduler's capacity parks (FIFO-seat holds under expert
            # overload — never preemptions) and last tick's pressure
            "moe": (None if not getattr(eng, "_moe_serving", False) else {
                "dispatched": eng.moe_dispatched,
                "dropped": eng.moe_dropped,
                "expert_load_max": eng.moe_expert_load_max,
                "pressure": eng.moe_pressure(),
                "capacity_parks": self.moe_capacity_parks,
                "unparks": self.moe_unparks,
                "waiting": sum(1 for r in self.queue if r.moe_waiting),
            }),
        }
