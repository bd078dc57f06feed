"""Replica router: N engine+scheduler replicas behind one serving front.

One engine on one mesh cannot serve the north star's "heavy traffic from
millions of users": the reference runs N ranks behind the launcher's
hostfile fan-out (SURVEY §1) and scales workers against load with its
ElasticAgent (§5.3). This module is that fleet layer for the paged serving
stack — each replica is an ``InferenceEngineV2`` +
``ContinuousBatchingScheduler`` pair, and the router:

  - **places** every incoming request by per-replica KV-block pressure and
    queue depth, prefix-cache-aware: with ``prefix_caching`` on, the
    replica whose content registry already holds the prompt's block-key
    chain (``engine.prefix_peek``) wins the tiebreak, so shared system
    prompts keep landing where their KV lives;
  - **pins sticky sessions**: a ``session_id``'s later turns return to the
    replica already holding that conversation's blocks (the multi-turn
    prefix-cache win), until that replica drains;
  - **keeps the scheduler's front**: ``serve(requests, arrivals=...)`` is
    the same Poisson-trace front the single-engine scheduler exposes, so
    1-replica and N-replica fleets can be compared on identical traces;
  - **drains elastically**: ``drain(replica_id)`` stops admission on one
    replica, preempts its running sequences, and front-requeues every
    unfinished request on the surviving replicas — token-identical replay
    is the scheduler's existing preemption contract, applied fleet-wide
    (``serving/lifecycle.py`` wires this to SIGTERM and the autoscaler).

On the driver box replicas are in-process (cooperative ticking, or one
thread each via ``start()``/``stop()``); a real multi-host fleet launches
one serving worker per host through the launcher's hostfile machinery
(``fleet_commands`` below reuses ``launcher/runner.py`` parsing — SURVEY
§1's ``deepspeed`` runner shape).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..inference.config import RouterConfig
from ..inference.engine_v2 import InferenceEngineV2
from ..inference.scheduler import (FAILED, FINISHED, PREFILL, RUNNING,
                                   ContinuousBatchingScheduler,
                                   ServingRequest)
from ..monitor.monitor import FleetMonitor, Monitor
from ..testing import faults, sanitizer
from ..utils.invariants import atomic_on_reject, locked_by, requires_lock
from ..utils.logging import logger
from .health import H_DEAD, HealthMonitor

ACTIVE, DRAINING, STOPPED = "active", "draining", "stopped"


class NoActiveReplicaError(RuntimeError):
    """Every replica is drained, stopped or dead — the fleet cannot take
    (or re-place) a request."""


class LoadShedError(RuntimeError):
    """Admission refused by the load shedder (ISSUE 12): fleet queue depth
    crossed ``router.shed_queue_depth``. Carries the uid the request would
    have gotten plus the fleet state, so callers can log/retry with
    context instead of guessing."""

    def __init__(self, uid: int, queue_depth: int, bound: int,
                 active_replicas: int):
        self.uid = uid
        self.queue_depth = queue_depth
        self.bound = bound
        super().__init__(
            f"admission shed for request {uid}: fleet queue depth "
            f"{queue_depth} >= shed_queue_depth {bound} across "
            f"{active_replicas} active replica(s) — back off and retry")


class PoisonQuarantinedError(RuntimeError):
    """A request's replica died mid-execution ``poison_death_threshold``
    times (ISSUE 12): it is quarantined — never re-placed — so one
    pathological input cannot serially take the whole fleet down."""

    def __init__(self, uid: int, deaths: int):
        self.uid = uid
        self.deaths = deaths
        super().__init__(
            f"request {uid} quarantined as poison: its replica died "
            f"mid-execution {deaths} times — not re-placing it on a "
            f"third replica")


class RetriesExhaustedError(RuntimeError):
    """A request was failover-re-placed more than ``router.max_retries``
    times without finishing (ISSUE 12)."""

    def __init__(self, uid: int, retries: int, max_retries: int):
        self.uid = uid
        self.retries = retries
        super().__init__(
            f"request {uid} failed after {retries} failover re-placements "
            f"(max_retries={max_retries})")


class Replica:
    """One serving replica: engine + scheduler + lifecycle state."""

    def __init__(self, replica_id: int, engine: InferenceEngineV2,
                 scheduler: ContinuousBatchingScheduler):
        self.replica_id = replica_id
        self.engine = engine
        self.scheduler = scheduler
        self.state = ACTIVE
        self.thread: Optional[threading.Thread] = None
        # guards this replica's scheduler (tick vs submit/inject/export):
        # per-replica so N threaded replicas tick CONCURRENTLY — the
        # router-wide lock covers only membership/placement bookkeeping.
        # Rank 10 in utils.invariants.LOCK_ORDER; instrumented under
        # SXT_SANITIZE (testing/sanitizer.py).
        self.lock = sanitizer.wrap(threading.RLock(), "Replica.lock")

    @property
    def active(self) -> bool:
        return self.state == ACTIVE


@locked_by("_lock", "requests", "owner", "sessions", "_session_of",
           "_next_uid", "drains", "requeued", "weight_publishes",
           "published_version", "_published_weights",
           "failovers", "recovered", "migrated_sequences",
           "migrated_blocks", "reprefill_tokens", "quarantined",
           "retries_exhausted", "shed", "_channel",
           "adapter_publishes", "_published_adapters",
           "publish_stage_s", "publish_commit_s", "publish_bytes")
class ReplicaRouter:
    """Place requests across replicas; tick them; aggregate their stats.

    ``engines``: the replica engines (same model+weights — token-identical
    routing requires it). ``engine_factory`` (optional) builds additional
    engines for scale-up. ``monitor``: a downstream sink (e.g.
    ``MonitorMaster``) for the fleet-aggregated ``fleet/*`` events.
    """

    def __init__(self, engines: Sequence[InferenceEngineV2],
                 engine_factory: Optional[Callable[[], InferenceEngineV2]] = None,
                 monitor: Optional[Monitor] = None,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 drafter_factory: Optional[Callable[[int], object]] = None):
        if not engines:
            raise ValueError("ReplicaRouter needs at least one engine")
        self.rcfg: RouterConfig = engines[0].config.router
        self.engine_factory = engine_factory
        # speculative serving (ISSUE 8) rides each replica's OWN engine
        # config unchanged — the scheduler builds its drafter from
        # engine.config.serving.speculative. ``drafter_factory(replica_id)``
        # overrides that per replica (a draft-model fleet shares one
        # loaded (model, params) instead of re-importing the checkpoint
        # N times; drafter STATE is never shared — draft KV is
        # per-replica like every other cache).
        self.drafter_factory = drafter_factory
        self.clock = clock
        self.on_token = on_token
        self.fleet = FleetMonitor(downstream=monitor)
        self.replicas: List[Replica] = []
        self.requests: Dict[int, ServingRequest] = {}   # uid -> live object
        self.owner: Dict[int, int] = {}                 # uid -> replica_id
        self.sessions: Dict[object, int] = {}           # session -> replica_id
        self._session_of: Dict[int, object] = {}        # uid -> session
        self._next_uid = 0
        self._stop = threading.Event()
        # rank 0 — the BOTTOM of the declared lock hierarchy
        # (utils.invariants.LOCK_ORDER): nothing below it may be held
        # when it is taken, and fail_over()'s fence deliberately uses
        # bare bool writes so a hung replica can be released without it
        self._lock = sanitizer.wrap(threading.RLock(), "ReplicaRouter._lock")
        # replica ids whose drain was REQUESTED from a signal handler
        # (serving/lifecycle.py): the handler only records the id — a
        # handler that mutated router state directly could interleave
        # with a half-finished submit()/scale_to() on the same thread
        # through the reentrant lock. Consumed at the next tick().
        self._pending_drains: set = set()
        self.drains = 0
        self.requeued = 0
        # fleet fault tolerance (ISSUE 12): the heartbeat state machine,
        # failover bookkeeping, and the lazy KV-migration channel. The
        # health monitor is consulted inline (tick()) and, for threaded
        # fleets, from the dedicated monitor thread start() spawns — a
        # hung replica cannot check its own pulse.
        self.health = HealthMonitor(self.rcfg, clock=self.clock)
        self.failovers = 0
        self.recovered = 0            # requests re-placed by failover
        self.migrated_sequences = 0   # re-placed WITHOUT re-prefill
        self.migrated_blocks = 0
        self.reprefill_tokens = 0     # prefill tokens replayed by failover
        self.quarantined: Dict[int, int] = {}   # uid -> replica deaths
        self.retries_exhausted = 0
        self.shed = 0
        self._channel = None          # lazy KVTransferChannel
        self._health_thread: Optional[threading.Thread] = None
        self._last_health_check = 0.0
        # fleet-wide weight publication (ISSUE 11): count + last version,
        # plus a reference to the last-published tree so elastic scale-up
        # can catch a factory-built replica up to the fleet's version
        # (without it, a replica added after a publish would serve the
        # factory's construction-time weights — a silently half-published
        # fleet). Replaced on every publish; costs one retained tree.
        self.weight_publishes = 0
        self.published_version: Optional[int] = None
        self._published_weights = None
        # multi-tenant LoRA (ISSUE 18): fleet-published adapters, kept by
        # id so elastic scale-up catches a factory-built replica up to
        # every published adapter (same rationale as _published_weights —
        # without it a replica added after a publish_adapter would refuse
        # that tenant's requests)
        self.adapter_publishes = 0
        self._published_adapters: Dict[str, tuple] = {}
        # async shuffle-exchange weight sync (ISSUE 20): when
        # rcfg.sync.enabled, publishes stage only to the trainer peer's
        # current edge partners and a background loop (or cooperative
        # tick piggyback) spreads the version along the decentralized
        # schedule — built after the replica roster below so the peer
        # count is known. Publish-path meters ride the same roster.
        self._async_sync = None
        self._sync_thread: Optional[threading.Thread] = None
        self.publish_stage_s = 0.0
        self.publish_commit_s = 0.0
        self.publish_bytes = 0
        for eng in engines:
            self._add_replica(eng)
        if self.rcfg.sync.enabled:
            from .async_sync import AsyncWeightSync
            self._async_sync = AsyncWeightSync(
                self.rcfg.sync, n_replicas=len(self.replicas),
                apply_fn=self._sync_apply)

    # -- fleet membership ----------------------------------------------

    def _add_replica(self, engine: InferenceEngineV2) -> Replica:
        rid = len(self.replicas)
        drafter = (self.drafter_factory(rid)
                   if self.drafter_factory is not None else None)
        sched = ContinuousBatchingScheduler(
            engine, on_token=self._emit_token, clock=self.clock,
            monitor=self.fleet.sink(rid), replica_id=rid, drafter=drafter)
        rep = Replica(rid, engine, sched)
        # elastic scale-up after a publish: catch the newcomer up to the
        # fleet's published weights before it takes traffic (a fresh
        # engine has no live KV, so the commit applies immediately)
        if self._published_weights is not None:
            engine.publish_weights(self._published_weights,
                                   version=self.published_version)
        if self._published_adapters and engine.adapters is not None:
            for aid, (factors, alpha, ver) in self._published_adapters.items():
                engine.adapters.register(aid, factors, alpha=alpha,
                                         version=ver)
        self.replicas.append(rep)
        self.health.register(rid)
        # async sync (ISSUE 20): a scale-up replica joins the topology as
        # a fresh peer, already caught up to the published version above
        sync = getattr(self, "_async_sync", None)
        if sync is not None:
            if rid >= sync.n_replicas:
                sync.add_peer()
            sync.reactivate_peer(rid, version=self.published_version or 0)
        return rep

    def _emit_token(self, uid: int, tok: int) -> None:
        if self.on_token is not None:
            self.on_token(uid, tok)

    @property
    def active_replicas(self) -> List[Replica]:
        return [r for r in self.replicas if r.active]

    # -- placement ------------------------------------------------------

    def _score(self, rep: Replica, prompt: Sequence[int],
               adapter_id: Optional[str] = None) -> float:
        """Placement score (higher wins): prefix-cache and adapter-pool
        affinities minus queue-depth and KV-pressure penalties, per the
        router config's weights. Deterministic, so placement decisions
        are testable."""
        cfg = self.rcfg
        load = rep.scheduler.load()
        score = 0.0
        if cfg.prefix_affinity and rep.engine.config.prefix_caching:
            hit, _, _ = rep.engine.prefix_peek(list(prompt))
            score += cfg.prefix_affinity_weight * (hit / max(1, len(prompt)))
        # multi-tenant LoRA (ISSUE 18): a request lands where its adapter
        # already sits in HBM — the paging analog of prefix affinity (a
        # miss costs an install + possibly an eviction somewhere else)
        if cfg.adapter_affinity and adapter_id is not None and \
                adapter_id in load.get("resident_adapters", ()):
            score += cfg.adapter_affinity_weight
        max_running = rep.engine.config.serving.max_running
        score -= cfg.queue_depth_weight * (
            (load["queue_depth"] + load["running"]) / max(1, max_running))
        score -= cfg.kv_pressure_weight * load["kv_pressure"]
        return score

    def place(self, prompt: Sequence[int],
              session_id: Optional[object] = None,
              adapter_id: Optional[str] = None) -> Replica:
        """Pick the replica a request should land on (no mutation).
        Health-aware (ISSUE 12): SUSPECT replicas — missed heartbeats or
        a flagged hang — take no NEW placements while any healthy
        candidate exists (they may be about to die; their existing work
        either recovers with them or fails over)."""
        cfg = self.rcfg
        candidates = self.active_replicas
        if not candidates:
            raise NoActiveReplicaError(
                "no ACTIVE replicas (all drained/stopped/dead)")
        states = self.health.states()
        healthy = [r for r in candidates
                   if states.get(r.replica_id) == "active"]
        if healthy:
            candidates = healthy
        if cfg.sticky_sessions and session_id is not None:
            rid = self.sessions.get(session_id)
            if (rid is not None and self.replicas[rid].active
                    and self.replicas[rid] in candidates):
                return self.replicas[rid]
        # stable max: ties go to the lowest replica id
        return max(candidates,
                   key=lambda r: (self._score(r, prompt,
                                              adapter_id=adapter_id),
                                  -r.replica_id))

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               session_id: Optional[object] = None,
               deadline_s: Optional[float] = None,
               sampling=None,
               adapter_id: Optional[str] = None) -> int:
        """Route one request; returns its fleet-global uid. When NO active
        replica can ever take the request, the error aggregates every
        replica's own needed-vs-free numbers (the ``_admission_detail``
        discipline carried across the fleet boundary). With
        ``router.shed_queue_depth`` set, admission is refused with a
        typed ``LoadShedError`` once the fleet's total queued requests
        cross the bound (ISSUE 12) — a loud early refusal instead of a
        silent deadline miss later. ``deadline_s`` rides to the
        scheduler's per-request deadline; ``sampling`` (ISSUE 16) rides
        per-request :class:`SamplingParams` to whichever replica the
        request lands on — the seed travels WITH the request, so drains
        and failovers replay the same chain on the survivor."""
        with self._lock:
            bound = self.rcfg.shed_queue_depth
            if bound:
                depth = sum(len(r.scheduler.queue)
                            for r in self.active_replicas)
                if depth >= bound:
                    self.shed += 1
                    self.fleet.write_events([
                        ("shed/rejected", self.shed, self.shed),
                        ("shed/queue_depth", depth, self.shed)])
                    raise LoadShedError(self._next_uid, depth, bound,
                                        len(self.active_replicas))
            rep = self.place(prompt, session_id=session_id,
                             adapter_id=adapter_id)
            uid = self._next_uid
            self._next_uid += 1
            try:
                with rep.lock:
                    rep.scheduler.submit(prompt,
                                         max_new_tokens=max_new_tokens,
                                         uid=uid,
                                         deadline_s=deadline_s,
                                         sampling=sampling,
                                         adapter_id=adapter_id)
            # RuntimeError included (ISSUE 12): the placed replica may
            # have been fenced/drained between place() and the lock — a
            # draining refusal is retryable on the survivors
            except (ValueError, RuntimeError) as first_err:
                # the chosen replica can never take it — try the rest and
                # aggregate every refusal with its numbers (satellite:
                # admission errors name the replica considered)
                reasons = [str(first_err)]
                for other in self.active_replicas:
                    if other is rep:
                        continue
                    try:
                        with other.lock:
                            other.scheduler.submit(
                                prompt, max_new_tokens=max_new_tokens,
                                uid=uid, deadline_s=deadline_s,
                                sampling=sampling, adapter_id=adapter_id)
                        rep = other
                        break
                    except (ValueError, RuntimeError) as e:
                        reasons.append(str(e))
                else:
                    raise ValueError(
                        "no replica can admit the request — "
                        + "; ".join(reasons)) from first_err
            self.requests[uid] = rep.scheduler.requests[uid]
            self.owner[uid] = rep.replica_id
            if session_id is not None:
                # delete-then-set keeps the dict in recency order, so the
                # bound below evicts the LEAST-recently-pinned session
                self.sessions.pop(session_id, None)
                self.sessions[session_id] = rep.replica_id
                self._session_of[uid] = session_id
            self._evict_finished()
            return uid

    @requires_lock("_lock")
    def _evict_finished(self) -> None:
        """Long-lived-process bounds (router config): drop the oldest
        FINISHED requests past ``retain_finished`` (their results have
        had the whole window to be picked up; keep the cap above any
        ``serve()`` batch size) and the least-recently-pinned sessions
        past ``max_sessions``. Live requests are never evicted."""
        cap = self.rcfg.retain_finished
        if cap and len(self.requests) > cap:
            excess = len(self.requests) - cap
            done = [u for u, r in self.requests.items()
                    if r.state in (FINISHED, FAILED)][:excess]
            for u in done:
                del self.requests[u]
                self.owner.pop(u, None)
                self._session_of.pop(u, None)
        scap = self.rcfg.max_sessions
        while scap and len(self.sessions) > scap:
            self.sessions.pop(next(iter(self.sessions)))

    # -- ticking --------------------------------------------------------

    def tick(self) -> bool:
        """Tick every non-stopped replica once (round-robin); True while
        any replica holds work. Signal-requested drains (SIGTERM hook)
        are applied here, at a point where no router mutation is half
        done. A tick that RAISES is a health event (ISSUE 12): a
        ``ReplicaCrashed`` is an unclean death — immediate failover with
        the engine treated as lost — while any other exception is a
        strike (SUSPECT, escalating to DEAD after
        ``tick_exception_strikes`` consecutive ones). The failure is
        handled OUTSIDE the replica lock: failover takes the router lock
        and the survivors' locks, and the lock order is router before
        replica, always."""
        self._process_pending_drains()
        self.check_health()
        busy = False
        for rep in list(self.replicas):
            if rep.state == STOPPED:
                continue
            err: Optional[BaseException] = None
            self.health.beat_start(rep.replica_id)
            with rep.lock:
                if rep.state != STOPPED:
                    try:
                        busy = rep.scheduler.tick() or busy
                    except BaseException as e:
                        err = e
            if err is None:
                self.health.beat_end(rep.replica_id)
            else:
                self._on_tick_failure(rep, err)
                busy = True   # failed-over work now lives on survivors
        # cooperative drivers (serve()/direct tick loops) advance the
        # async weight sync here; the threaded driver has its own loop
        if self._async_sync is not None and (
                self._sync_thread is None
                or not self._sync_thread.is_alive()):
            self.sync_step()
        return busy

    def request_drain(self, replica_id: int) -> None:
        """Record a drain request to apply at the next tick. The ONLY
        router entry point that is safe from a signal handler: a handler
        runs on the main thread mid-bytecode, where the reentrant lock
        would let a direct drain() interleave with a half-finished
        submit()/scale_to() frame underneath it."""
        self._pending_drains.add(int(replica_id))

    def _process_pending_drains(self) -> None:
        if not self._pending_drains:
            return
        with self._lock:
            pending, self._pending_drains = self._pending_drains, set()
        for rid in sorted(pending):
            try:
                n = self.drain(rid)
                logger.warning(f"requested drain: replica {rid} drained, "
                               f"{n} requests requeued on survivors")
            except Exception:
                logger.exception(f"requested drain of replica {rid} failed")

    # -- fleet health & unclean failover (ISSUE 12) ---------------------

    def check_health(self, force: bool = False) -> int:
        """One health observation: fold heartbeats/thread-liveness into
        the state machine and fail over every newly-DEAD replica.
        Rate-limited to ``health_check_interval_s`` unless ``force``
        (the dedicated monitor thread forces; inline callers — tick(),
        the supervisor — ride the limiter). Returns the number of
        replicas failed over."""
        now = self.clock()
        if not force and now - self._last_health_check < \
                self.rcfg.health_check_interval_s:
            return 0
        self._last_health_check = now

        def is_alive(rid: int) -> Optional[bool]:
            rep = self.replicas[rid]
            if rep.state == STOPPED:
                return None
            if rep.thread is None:
                return None   # cooperative mode: failures are synchronous
            return rep.thread.is_alive()

        newly_dead = self.health.check(is_alive)
        for rid, reason, reachable in newly_dead:
            try:
                self.fail_over(rid, reason=reason,
                               engine_reachable=reachable)
            except Exception:
                logger.exception(f"failover of replica {rid} failed")
        counts = self.health.state_counts()
        self.fleet.write_events([
            ("fleet/health/active", counts["active"], self.failovers),
            ("fleet/health/suspect", counts["suspect"], self.failovers),
            ("fleet/health/dead", counts["dead"], self.failovers),
            ("fleet/health/hung_ticks", self.health.hung_ticks,
             self.failovers)])
        return len(newly_dead)

    def _on_tick_failure(self, rep: Replica, exc: BaseException) -> None:
        """A replica's tick raised. ``ReplicaCrashed`` (and non-Exception
        BaseExceptions) = unclean death: immediate failover, engine lost.
        Anything else = a strike; ``tick_exception_strikes`` consecutive
        ones escalate to DEAD with the engine still reachable (the tick
        admission discipline is atomic-on-reject, so a raised tick left
        engine state clean). Never called with the replica's lock held."""
        rid = rep.replica_id
        if rep.state == STOPPED:
            return
        if (isinstance(exc, faults.ReplicaCrashed)
                or not isinstance(exc, Exception)):
            logger.error(f"router: replica {rid} tick crashed uncleanly: "
                         f"{type(exc).__name__}: {exc}")
            self.health.mark_dead(rid, f"tick crashed: {exc}",
                                  engine_reachable=False)
            self.fail_over(rid, reason=f"tick crashed: {exc}",
                           engine_reachable=False)
            return
        logger.warning(f"router: replica {rid} tick raised "
                       f"{type(exc).__name__}: {exc}")
        state = self.health.strike(rid, f"{type(exc).__name__}: {exc}")
        if state == H_DEAD:
            self.fail_over(
                rid, reason=f"tick-exception strike budget exhausted "
                            f"(last: {exc})",
                engine_reachable=True)

    def fail_over(self, replica_id: int, reason: str = "operator verdict",
                  engine_reachable: bool = False) -> int:
        """Reclaim a DEAD replica's queue and in-flight requests and
        re-place them on survivors (ISSUE 12 tentpole).

        Unlike ``drain()``, the dead replica is never asked anything: the
        router's own bookkeeping — the shared ``ServingRequest`` objects
        in ``self.requests`` (prompt + emitted tokens per uid, the
        ``export_requests``-shaped state kept router-side) — is the
        source of truth. The scheduler is FENCED first, so a hung tick
        that eventually returns emits nothing (its requests have new
        homes); every re-placed request carries its generated
        continuation, so the replay elsewhere is token-identical under
        greedy decoding (the drain-replay discipline applied to crashes).

        Recovery per request, oldest first:

        - mid-execution deaths count toward poison quarantine
          (``poison_death_threshold``) and bounded retries
          (``max_retries`` with exponential backoff via ``not_before``);
        - a RUNNING sequence on a REACHABLE engine (hang, not crash)
          migrates its committed KV blocks to a survivor over the
          ``KVTransferChannel`` and resumes decoding with ZERO re-prefill
          tokens; everything else front-requeues for drain-replay;
        - sticky sessions re-pin to wherever their requests landed.

        With no surviving replica, a replacement is spawned from
        ``engine_factory`` (caught up to the published weight version by
        ``_add_replica``); without a factory the orphans FAIL with typed
        errors rather than hanging forever. Returns the number of
        recovered (re-placed) requests."""
        rep = self.replicas[replica_id]
        if rep.state == STOPPED:
            return 0
        # fence BEFORE taking the router lock: bare bool writes the
        # zombie tick reads after its dispatch. Never take rep.lock here
        # (a hung tick holds it) — and never require the router lock for
        # the fence itself: a submit() may be holding the router lock
        # while blocked on THIS replica's lock, and the fence is what
        # releases that hung tick (the submit then gets a retryable
        # draining refusal and re-places on a survivor).
        rep.scheduler.fenced = True
        rep.scheduler.draining = True
        with self._lock:
            if rep.state == STOPPED:
                return 0
            rep.state = STOPPED
            self.health.mark_dead(replica_id, reason, engine_reachable)
            if self._async_sync is not None:
                # the dead peer leaves the gossip schedule mid-exchange;
                # its last committed version stays recorded, so a
                # replacement re-enters via _add_replica's reactivation
                self._async_sync.deactivate_peer(replica_id)
            self.failovers += 1
            victims = sorted(
                uid for uid, rid in self.owner.items()
                if rid == replica_id
                and self.requests[uid].state not in (FINISHED, FAILED))
            survivors = [r for r in self.active_replicas if r is not rep]
            if victims and not survivors and self.engine_factory is not None:
                logger.warning(
                    f"router: no survivor for replica {replica_id}'s "
                    f"{len(victims)} requests — spawning a replacement "
                    f"from the engine factory")
                survivors = [self._add_replica(self.engine_factory())]
                if any(r.thread is not None and r.thread.is_alive()
                       for r in self.replicas):
                    self.start()
            now = self.clock()
            recovered = migrated = 0
            # inject newest-first so the OLDEST victim ends up at the very
            # front of its new queue (fleet FIFO, the drain discipline)
            for uid in reversed(victims):
                old = self.requests[uid]
                mid_exec = old.state in (PREFILL, RUNNING)
                # snapshot a FRESH request object: the dead replica's
                # zombie tick may still hold the old one
                snap = ServingRequest(
                    uid=uid, prompt=list(old.prompt),
                    max_new_tokens=old.max_new_tokens,
                    generated=list(old.generated),
                    submitted_at=old.submitted_at,
                    due_at=old.due_at,
                    first_scheduled_at=old.first_scheduled_at,
                    first_token_at=old.first_token_at,
                    last_token_at=old.last_token_at,
                    tpot_s=list(old.tpot_s),
                    preemptions=old.preemptions + (1 if mid_exec else 0),
                    decode_ticks=old.decode_ticks,
                    deadline_s=old.deadline_s,
                    retries=old.retries,
                    replica_deaths=old.replica_deaths,
                    # ISSUE 16: the seed travels with the victim, so the
                    # survivor's replay re-samples the identical chain
                    sampling=old.sampling,
                    stopped=old.stopped,
                    # ISSUE 18: the adapter id travels too — the replay
                    # re-binds the same adapter on the survivor's pool
                    adapter_id=old.adapter_id)
                self.requests[uid] = snap
                if mid_exec:
                    snap.replica_deaths += 1
                    if snap.replica_deaths >= self.rcfg.poison_death_threshold:
                        snap.state = FAILED
                        snap.finished_at = now
                        snap.error = PoisonQuarantinedError(
                            uid, snap.replica_deaths)
                        self.quarantined[uid] = snap.replica_deaths
                        logger.error(str(snap.error))
                        continue
                    snap.retries += 1
                    if snap.retries > self.rcfg.max_retries:
                        snap.state = FAILED
                        snap.finished_at = now
                        snap.error = RetriesExhaustedError(
                            uid, snap.retries, self.rcfg.max_retries)
                        self.retries_exhausted += 1
                        logger.error(str(snap.error))
                        continue
                    snap.not_before = now + (self.rcfg.retry_backoff_s
                                             * 2 ** (snap.retries - 1))
                target = None
                if (engine_reachable and self.rcfg.kv_migration
                        and old.state == RUNNING and old.generated
                        and uid in rep.engine._seqs):
                    target = self._migrate(rep, snap, survivors)
                    if target is not None:
                        migrated += 1
                if target is None:
                    target = self._replace(snap, survivors, replica_id, now)
                    if target is None:
                        continue   # FAILED inside _replace
                recovered += 1
                self.owner[uid] = target.replica_id
                sid = self._session_of.get(uid)
                if sid is not None:
                    self.sessions[sid] = target.replica_id
            for sid, rid in list(self.sessions.items()):
                if rid == replica_id:
                    del self.sessions[sid]
            self.recovered += recovered
            self.migrated_sequences += migrated
            self.fleet.write_events([
                ("failover/deaths", self.failovers, self.failovers),
                ("failover/recovered", self.recovered, self.failovers),
                ("failover/migrated_sequences", self.migrated_sequences,
                 self.failovers),
                ("failover/migrated_blocks", self.migrated_blocks,
                 self.failovers),
                ("failover/reprefill_tokens", self.reprefill_tokens,
                 self.failovers),
                ("failover/quarantined", len(self.quarantined),
                 self.failovers)])
            logger.warning(
                f"router: replica {replica_id} failed over ({reason}): "
                f"{recovered}/{len(victims)} requests re-placed on "
                f"{len(survivors)} survivors ({migrated} via KV "
                f"migration), {len(self.quarantined)} quarantined total")
            return recovered

    @staticmethod
    def _failover_order(adapter_id: Optional[str]):
        """Survivor preference for a victim: adapter-resident replicas
        first (ISSUE 18 — re-placing onto a pool that already holds the
        victim's adapter skips an install and possibly someone else's
        eviction), then least loaded, ties to the lowest id."""
        def key(s):
            ld = s.scheduler.load()
            resident = (adapter_id is not None
                        and adapter_id in ld.get("resident_adapters", ()))
            return (0 if resident else 1,
                    ld["queue_depth"] + ld["running"], s.replica_id)
        return key

    @requires_lock("_lock")
    def _migrate(self, rep: Replica, snap: ServingRequest,
                 survivors: List[Replica]) -> Optional[Replica]:
        """Move a RUNNING sequence's committed KV from a hung (reachable)
        replica to a survivor and adopt it mid-decode — zero re-prefill
        tokens. Any refusal (KV pressure, weight-version mismatch, full
        running set) falls back to drain-replay; a committed import whose
        adoption is then refused is flushed so nothing leaks."""
        from .disagg import KVTransferChannel, TransferAborted

        if self._channel is None:
            self._channel = KVTransferChannel(monitor=self.fleet)

        for target in sorted(survivors,
                             key=self._failover_order(snap.adapter_id)):
            with target.lock:
                if (target.scheduler.draining
                        or len(target.scheduler.active)
                        >= target.scheduler.cfg.max_running):
                    continue
                try:
                    self._channel.transfer(rep.engine, target.engine,
                                           snap.uid, flush_src=False)
                except (ValueError, RuntimeError, TransferAborted) as e:
                    logger.info(
                        f"failover: KV migration of uid {snap.uid} to "
                        f"replica {target.replica_id} refused ({e}); "
                        f"trying the next survivor")
                    continue
                try:
                    target.scheduler.adopt_running(snap)
                except (ValueError, RuntimeError) as e:
                    target.engine.flush([snap.uid])
                    logger.info(
                        f"failover: replica {target.replica_id} refused "
                        f"adoption of migrated uid {snap.uid} ({e})")
                    continue
                # read under the target's lock: its tick thread may
                # finish+flush the adopted sequence the moment we let go
                nblocks = len(target.engine._seqs[snap.uid].blocks)
            self.migrated_blocks += nblocks
            logger.info(
                f"failover: uid {snap.uid} migrated to replica "
                f"{target.replica_id} ({nblocks} KV blocks, zero "
                f"re-prefill tokens)")
            return target
        return None

    @requires_lock("_lock")
    def _replace(self, snap: ServingRequest, survivors: List[Replica],
                 dead_rid: int, now: float) -> Optional[Replica]:
        """Front-requeue a victim on a survivor (drain-replay: the
        generated continuation folds into the prefill target). Marks the
        request FAILED with a typed error when nobody can take it."""
        refusals = []

        for target in sorted(survivors,
                             key=self._failover_order(snap.adapter_id)):
            try:
                with target.lock:
                    target.scheduler.inject(snap, front=True)
            except (ValueError, RuntimeError) as e:
                refusals.append(str(e))
                continue
            self.reprefill_tokens += len(snap.prompt) + len(snap.generated)
            return target
        snap.state = FAILED
        snap.finished_at = now
        snap.error = NoActiveReplicaError(
            f"request {snap.uid}: no surviving replica could adopt it "
            f"from dead replica {dead_rid}"
            + (f" — {'; '.join(refusals)}" if refusals else ""))
        logger.error(str(snap.error))
        return None

    def serve(self, requests: Sequence[Union[Sequence[int],
                                             Tuple[Sequence[int], int]]],
              max_new_tokens: int = 32,
              arrivals: Optional[Sequence[float]] = None,
              session_ids: Optional[Sequence[object]] = None,
              deadline_s: Optional[float] = None,
              sampling=None,
              adapter_ids: Optional[Sequence[Optional[str]]] = None
              ) -> Dict[int, List[int]]:
        """Serve a batch to completion across the fleet — the scheduler's
        Poisson-trace ``serve`` contract, routed. Returns ``{uid: tokens}``
        in submission order (a FAILED request contributes its partial
        tokens; check ``requests[uid].state``/``.error`` for the verdict).
        Results survive mid-serve drains AND failovers: the router tracks
        the live ``ServingRequest`` objects, wherever they run.
        ``sampling`` (ISSUE 16): one ``SamplingParams`` for every request
        or a per-request sequence (None entries = greedy). ``adapter_ids``
        (ISSUE 18): per-request adapter names — affinity routing sends
        each toward a replica whose pool already holds its adapter."""
        items = []
        for req in requests:
            if (isinstance(req, tuple) and len(req) == 2
                    and not isinstance(req[1], (list, np.ndarray))):
                items.append((list(req[0]), int(req[1])))
            else:
                items.append((list(req), int(max_new_tokens)))
        if arrivals is not None and len(arrivals) != len(items):
            raise ValueError("arrivals must align with requests")
        if session_ids is not None and len(session_ids) != len(items):
            raise ValueError("session_ids must align with requests")
        if sampling is None or not isinstance(sampling, (list, tuple)):
            samplings = [sampling] * len(items)
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
        while pending or any(r.scheduler.active or r.scheduler.queue
                             for r in self.replicas if r.state != STOPPED):
            while pending and (arrivals is None
                               or self.clock() - t0 >= arrivals[pending[0][0]]):
                i, (prompt, mn) = pending.popleft()
                sid = session_ids[i] if session_ids is not None else None
                uids.append(self.submit(prompt, max_new_tokens=mn,
                                        session_id=sid,
                                        deadline_s=deadline_s,
                                        sampling=samplings[i],
                                        adapter_id=aids[i]))
            if not self.tick() and pending and arrivals is not None:
                wait = arrivals[pending[0][0]] - (self.clock() - t0)
                if wait > 0:
                    time.sleep(wait)
        return {uid: self.requests[uid].generated for uid in uids}

    # -- threaded drivers ----------------------------------------------

    def start(self) -> None:
        """One worker thread per replica, each ticking its own scheduler
        until ``stop()`` — the in-process analog of one serving process
        per host. Placement/submit stay on the caller's thread (the
        scheduler queue is the handoff point). A dedicated health-monitor
        thread runs the heartbeat checks (ISSUE 12): a hung replica
        cannot check its own pulse, and the submit thread may be asleep
        between arrivals."""
        self._stop.clear()
        for rep in self.replicas:
            if rep.thread is None or not rep.thread.is_alive():
                rep.thread = threading.Thread(
                    target=self._replica_loop, args=(rep,), daemon=True,
                    name=f"serving-replica-{rep.replica_id}")
                rep.thread.start()
        if self._health_thread is None or not self._health_thread.is_alive():
            self._health_thread = threading.Thread(
                target=self._health_loop, daemon=True,
                name="serving-health-monitor")
            self._health_thread.start()
        if self._async_sync is not None and (
                self._sync_thread is None
                or not self._sync_thread.is_alive()):
            self._sync_thread = threading.Thread(
                target=self._sync_loop, daemon=True,
                name="serving-weight-sync")
            self._sync_thread.start()

    def _replica_loop(self, rep: Replica) -> None:
        while not self._stop.is_set() and rep.state != STOPPED:
            self._process_pending_drains()
            err: Optional[BaseException] = None
            busy = False
            self.health.beat_start(rep.replica_id)
            with rep.lock:
                if rep.state != STOPPED:
                    try:
                        busy = rep.scheduler.tick()
                    except BaseException as e:
                        err = e
            if err is not None:
                self._on_tick_failure(rep, err)
                if rep.state == STOPPED:
                    return   # this replica is dead; the loop ends with it
            else:
                self.health.beat_end(rep.replica_id)
            if not busy:
                time.sleep(0.001)

    def _health_loop(self) -> None:
        interval = self.rcfg.health_check_interval_s
        while not self._stop.wait(interval):
            try:
                self.check_health(force=True)
            except Exception:
                logger.exception("health check failed")

    def stop(self) -> None:
        self._stop.set()
        for rep in self.replicas:
            if rep.thread is not None:
                rep.thread.join(timeout=5.0)
                rep.thread = None
        if self._health_thread is not None:
            self._health_thread.join(timeout=5.0)
            self._health_thread = None
        if self._sync_thread is not None:
            self._sync_thread.join(timeout=5.0)
            self._sync_thread = None

    # -- elastic lifecycle ---------------------------------------------

    def drain(self, replica_id: int) -> int:
        """Drain one replica: stop admission, preempt its sequences, and
        front-requeue every unfinished request on surviving replicas
        (oldest first, so fleet FIFO order is preserved). Returns the
        number of requeued requests; zero requests are lost or duplicated
        — the moved ``ServingRequest`` objects keep their uids, generated
        continuations, and router bookkeeping."""
        with self._lock:
            rep = self.replicas[replica_id]
            if rep.state == STOPPED:
                return 0
            # validate BEFORE mutating anything: a refused drain must
            # leave the fleet exactly as it was (requests still live on
            # this replica), never preempt-then-discover-no-home
            survivors = [r for r in self.active_replicas if r is not rep]
            with rep.lock:
                has_work = bool(rep.scheduler.active or rep.scheduler.queue)
                if has_work and not survivors:
                    raise RuntimeError(
                        f"cannot drain replica {replica_id}: it holds "
                        f"unfinished requests and no surviving replica "
                        f"could take them")
                rep.state = DRAINING
                exported = rep.scheduler.export_requests()
            # front-requeue => inject in REVERSE so the oldest exported
            # request ends up at the very front of its new queue
            moved_uids: set = set()
            try:
                for r in reversed(exported):
                    refusals = []
                    for target in sorted(
                            survivors,
                            key=lambda s: (s.scheduler.load()["queue_depth"]
                                           + s.scheduler.load()["running"],
                                           s.replica_id)):
                        try:
                            with target.lock:
                                target.scheduler.inject(r, front=True)
                        except ValueError as e:
                            refusals.append(str(e))
                            continue
                        moved_uids.add(r.uid)
                        self.owner[r.uid] = target.replica_id
                        sid = self._session_of.get(r.uid)
                        if sid is not None:
                            self.sessions[sid] = target.replica_id
                        break
                    else:
                        raise RuntimeError(
                            f"no surviving replica can adopt request "
                            f"{r.uid} from draining replica {replica_id} — "
                            + "; ".join(refusals))
            except BaseException:
                # roll back: everything not yet moved returns to this
                # replica (front, original order) and it stays ACTIVE —
                # already-moved requests are validly queued on survivors,
                # so nothing is lost either way
                unmoved = [r for r in exported if r.uid not in moved_uids]
                with rep.lock:
                    rep.scheduler.draining = False
                    for r in reversed(unmoved):
                        rep.scheduler.inject(r, front=True)
                    rep.state = ACTIVE
                raise
            # stickiness to a drained replica is gone for everyone else
            for sid, rid in list(self.sessions.items()):
                if rid == replica_id:
                    del self.sessions[sid]
            rep.state = STOPPED
            self.health.retire(replica_id)   # clean exit, not a symptom
            if self._async_sync is not None:
                self._async_sync.deactivate_peer(replica_id)
            self.drains += 1
            self.requeued += len(exported)
            self.fleet.write_events([
                ("fleet/drains", self.drains, self.drains),
                ("fleet/requeued", self.requeued, self.drains)])
            logger.info(f"router: replica {replica_id} drained, "
                        f"{len(exported)} requests requeued on "
                        f"{len(survivors)} survivors")
            return len(exported)

    def scale_to(self, n: int) -> int:
        """Grow or shrink the ACTIVE fleet to ``n`` replicas. Growth needs
        ``engine_factory``; shrink drains the LEAST-LOADED active replica
        (queue depth + running set, ties to the newest id) — draining the
        newest regardless of load evicted whichever replica happened to
        join last, including one that had accumulated the hottest prefix
        cache, and moved the most in-flight work when an idle replica was
        standing right there. The verdict is logged per drain. Returns
        the active count after scaling."""
        if n < 1:
            raise ValueError(f"cannot scale to {n} replicas")
        with self._lock:
            while len(self.active_replicas) < n:
                if self.engine_factory is None:
                    raise RuntimeError(
                        "scale-up needs an engine_factory (the router only "
                        "drains without one)")
                rep = self._add_replica(self.engine_factory())
                if any(r.thread is not None and r.thread.is_alive()
                       for r in self.replicas):
                    self.start()   # threaded mode: give the newcomer a loop
                logger.info(f"router: scaled up — replica "
                            f"{rep.replica_id} joined")
            while len(self.active_replicas) > n:
                loads = {}
                for r in self.active_replicas:
                    ld = r.scheduler.load()
                    loads[r.replica_id] = ld["queue_depth"] + ld["running"]
                victim = min(self.active_replicas,
                             key=lambda r: (loads[r.replica_id],
                                            -r.replica_id))
                logger.info(
                    f"router: shrink verdict — draining replica "
                    f"{victim.replica_id} (least loaded: "
                    f"{loads[victim.replica_id]} queued+running, fleet "
                    f"loads {loads})")
                self.drain(victim.replica_id)
            return len(self.active_replicas)

    def autoscale_step(self, policy) -> int:
        """One autoscale observation: feed the policy the mean queue depth
        per active replica (``launcher/elastic_agent.AutoscalePolicy``)
        and apply its verdict. Returns the active count."""
        with self._lock:
            active = self.active_replicas
            depth = (sum(r.scheduler.load()["queue_depth"] for r in active)
                     / max(1, len(active)))
            want = policy.desired(len(active), depth)
            if want != len(active):
                self.scale_to(want)
            return len(self.active_replicas)

    # -- fleet-wide weight publication (ISSUE 11) ----------------------

    @atomic_on_reject(check="validate")
    def publish_weights(self, params, version: Optional[int] = None) -> int:
        """Deliver new serving weights to EVERY live replica — the fleet
        half of the RLHF train->serve flip — without tearing down any
        replica's paged KV pool or compiled programs.

        Two-phase for per-replica atomicity: every replica STAGES the
        prepared tree first (the phase that can fail — casts, device
        placement, quantization; the ``weight_publish`` fault site lands
        here), and only after ALL replicas staged successfully does each
        one commit. A crash mid-stage rolls every staged replica back, so
        the fleet keeps serving the OLD weight version as one unit — a
        half-published fleet (replicas answering from different weights)
        can never exist. Commits use ``defer=True``: a replica with live
        sequences applies the swap at its next tick boundary (its
        scheduler drains the in-flight tick first), an idle replica flips
        immediately.

        ``version`` stamps every replica's ``weight_version`` (default:
        one past the fleet's current max). Returns the published version.

        With ``rcfg.sync.enabled`` (ISSUE 20) the barrier is gone: the
        publish records the version with the async coordinator, stages
        only to the trainer peer's CURRENT edge partners, and returns —
        background sync steps spread it inside the bounded staleness
        window (``_publish_async``).
        """
        from ..testing import faults

        if self._async_sync is not None:
            return self._publish_async(params, version)
        with self._lock:
            reps = [r for r in self.replicas if r.state != STOPPED]
            if not reps:
                raise RuntimeError(
                    "publish_weights: no live replicas (all stopped)")
            if version is None:
                version = max(r.engine.weight_version for r in reps) + 1
            version = int(version)
            # prepare ONCE per serving-transform key (dtype/quantization)
            # and hand every matching replica the same placed tree: the
            # per-replica work under the lock is then a structure check +
            # a staging-slot write, not N cast+place passes of the whole
            # model (replicas share the device buffers; the serving
            # programs never donate the params operand)
            prep_cache: Dict[tuple, object] = {}

            def _prep(eng):
                cfg = eng.config
                key = (cfg.dtype, cfg.quantize_weights, str(cfg.quant_bits),
                       cfg.quant_group_size)
                if key not in prep_cache:
                    prep_cache[key] = eng._prepare_params(params)
                return prep_cache[key]

            staged: List[Replica] = []
            try:
                for i, rep in enumerate(reps):
                    faults.maybe_crash("weight_publish", i)
                    rep.engine.stage_weights(_prep(rep.engine),
                                             version=version, prepared=True)
                    staged.append(rep)
            except BaseException:
                # roll back: no replica has committed yet, so dropping the
                # staged trees leaves the WHOLE fleet on the old version
                for rep in staged:
                    rep.engine.discard_staged_weights()
                raise
            for rep in reps:
                with rep.lock:
                    rep.engine.commit_staged_weights(defer=True)
            self.weight_publishes += 1
            self.published_version = version
            self._published_weights = params
            self.fleet.write_events([
                ("fleet/weight_version", version, self.weight_publishes),
                ("fleet/weight_publishes", self.weight_publishes,
                 self.weight_publishes)])
            logger.info(f"router: published weight version {version} to "
                        f"{len(reps)} replicas")
            return version

    # -- async shuffle-exchange weight sync (ISSUE 20) ------------------

    def _sync_apply(self, rid: int, tree, version: int) -> None:
        """One edge delivery landing on a replica: prepare+stage OUTSIDE
        the replica lock (the expensive cast/quantize/place half), then
        defer-commit under it — a host pointer flip the replica applies
        at its next tick boundary, so a serving tick never stalls on the
        publish. Runs with AsyncWeightSync._mu (rank 5) held; rep.lock
        is rank 10 — ascending, per the declared order."""
        rep = self.replicas[rid]
        if rep.state == STOPPED:
            raise RuntimeError(f"sync apply: replica {rid} is stopped")
        rep.engine.stage_weights(tree, version=version)
        with rep.lock:
            rep.engine.commit_staged_weights(defer=True)

    def _publish_async(self, params, version: Optional[int]) -> int:
        """The barrier-free publish: wire the tree to the coordinator
        (one byte-exact host copy retained), stamp the version, and
        deliver only to the trainer peer's current edge partners —
        O(edge degree), not O(fleet). Everyone else picks it up from
        background :meth:`sync_step` rounds inside the staleness
        window."""
        import jax

        sync = self._async_sync
        t0 = self.clock()
        with self._lock:
            reps = [r for r in self.replicas if r.state != STOPPED]
            if not reps:
                raise RuntimeError(
                    "publish_weights: no live replicas (all stopped)")
            if version is None:
                version = max(sync.newest_version,
                              max(r.engine.weight_version for r in reps)) + 1
            version = int(version)
            retained = sync.publish(params, version)
            stage_dt = self.clock() - t0
            t1 = self.clock()
            kicked = sync.kick(version)
            commit_dt = self.clock() - t1
            self.weight_publishes += 1
            self.published_version = version
            self._published_weights = retained
            self.publish_stage_s += stage_dt
            self.publish_commit_s += commit_dt
            self.publish_bytes += sum(
                np.asarray(leaf).nbytes
                for leaf in jax.tree_util.tree_leaves(retained))
            self.fleet.write_events([
                ("fleet/weight_version", version, self.weight_publishes),
                ("fleet/weight_publishes", self.weight_publishes,
                 self.weight_publishes),
                ("publish/stage_s", stage_dt, self.weight_publishes),
                ("publish/commit_s", commit_dt, self.weight_publishes),
                ("publish/bytes", self.publish_bytes,
                 self.weight_publishes)])
            logger.info(
                f"router: async-published weight version {version} "
                f"(first hop: {kicked} edge partners; fleet converges "
                f"inside staleness window "
                f"{self.rcfg.sync.staleness_window})")
            return version

    def sync_step(self) -> int:
        """One manual edge round of the async coordinator (tests and
        cooperative drivers; the threaded driver runs these from the
        loop ``start()`` spawns). Returns deliveries applied and
        surfaces the staleness counters through the fleet monitor."""
        sync = self._async_sync
        if sync is None:
            return 0
        applied = sync.step()
        st = sync.staleness()
        self.fleet.write_events([
            ("sync/edge_exchanges", st["edge_exchanges"],
             st["sync_steps"]),
            ("sync/staleness_max", st["staleness_max"], st["sync_steps"]),
            ("sync/versions_behind", st["versions_behind"],
             st["sync_steps"]),
            ("sync/forced_catchups", st["forced_catchups"],
             st["sync_steps"])])
        return applied

    def converge(self) -> int:
        """Reduce the fleet to the reference ``synchronization()``
        full-average on demand (SURVEY §2.1): every active peer's tree is
        mixed with the uniform matrix and the SAME averaged tree lands on
        every replica — bit-equal across peers. Returns the version the
        converged weights are stamped with."""
        sync = self._async_sync
        if sync is None:
            raise RuntimeError(
                "converge: async sync is disabled (router.sync.enabled)")
        tree, version = sync.converge()
        with self._lock:
            self.weight_publishes += 1
            self.published_version = version
            self._published_weights = tree
            self.fleet.write_events([
                ("fleet/weight_version", version, self.weight_publishes),
                ("fleet/weight_publishes", self.weight_publishes,
                 self.weight_publishes)])
        logger.info(f"router: fleet converged to full-average at version "
                    f"{version}")
        return version

    def _sync_loop(self) -> None:
        interval = self.rcfg.sync.sync_interval_s
        while not self._stop.wait(interval):
            try:
                self.sync_step()
            except Exception:
                logger.exception("async weight-sync step failed")

    @atomic_on_reject(check="validate")
    def publish_adapter(self, adapter_id: str, factors, alpha=None,
                        version: Optional[int] = None) -> int:
        """Register one LoRA adapter in EVERY live replica's pool
        (ISSUE 18) — factors only, never full weights: a tenant flip
        ships kilobytes per layer, not the model. Host-side registration
        only; residency stays acquire's business, so a publish never
        evicts anything or touches a running batch. Content-keyed like
        the pools themselves — republishing identical bytes is a no-op,
        changed bytes bump the version and rewrite any resident slot in
        place (running sequences pick the new factors up next step, the
        publish_weights semantics at adapter granularity). The factors
        are retained so elastic scale-up catches factory-built replicas
        up to every published adapter. Returns the version stamped."""
        with self._lock:
            reps = [r for r in self.replicas if r.state != STOPPED]
            if not reps:
                raise RuntimeError(
                    "publish_adapter: no live replicas (all stopped)")
            no_pool = [r.replica_id for r in reps
                       if r.engine.adapters is None]
            if no_pool:
                raise ValueError(
                    f"publish_adapter: replicas {no_pool} have no adapter "
                    f"pool (enable config.adapters fleet-wide)")
            if version is None:
                version = max((r.engine.adapters.version(adapter_id) or 0)
                              for r in reps) + 1
            version = int(version)
            # the first register validates shapes/targets; identical
            # model configs mean the rest cannot fail differently, so a
            # bad publish raises before any replica mutates
            for rep in reps:
                rep.engine.adapters.register(adapter_id, factors,
                                             alpha=alpha, version=version)
            self._published_adapters[adapter_id] = (factors, alpha, version)
            self.adapter_publishes += 1
            self.fleet.write_events([
                ("fleet/adapter_publishes", self.adapter_publishes,
                 self.adapter_publishes)])
            logger.info(f"router: published adapter {adapter_id!r} "
                        f"version {version} to {len(reps)} replicas")
            return version

    # -- observability --------------------------------------------------

    def stats(self) -> Dict[str, object]:
        """Fleet summary: aggregated tails over every replica's finished
        requests plus the per-replica breakdown (satellite: fleet p50/p95/
        p99 TTFT/TPOT + per-replica queue depth through the monitor)."""

        def pct(xs, q):
            return float(np.percentile(xs, q)) if len(xs) else None

        done = [r for r in self.requests.values() if r.state == "finished"]
        failed = [r for r in self.requests.values() if r.state == FAILED]
        ttft = [r.first_token_at - r.submitted_at for r in done
                if r.first_token_at is not None]
        tpot = [t for r in done for t in r.tpot_s]
        total = sum(len(r.generated) for r in done)
        span = (max(r.finished_at for r in done)
                - min(r.submitted_at for r in done)) if done else 0.0
        return {
            "replicas": len(self.replicas),
            "active_replicas": len(self.active_replicas),
            "requests": len(done),
            "generated_tokens": total,
            # fleet fault tolerance (ISSUE 12): per-replica health states,
            # failover recovery bookkeeping (incl. the poison-quarantine
            # roster — uid -> replica deaths), and shed/deadline tallies
            "health": self.health.snapshot(),
            "failover": {
                "deaths": self.failovers,
                "recovered_requests": self.recovered,
                "migrated_sequences": self.migrated_sequences,
                "migrated_blocks": self.migrated_blocks,
                "reprefill_tokens": self.reprefill_tokens,
                "quarantined": dict(self.quarantined),
                "retries_exhausted": self.retries_exhausted,
            },
            "shed": {
                "rejected": self.shed,
                "queue_depth_bound": self.rcfg.shed_queue_depth,
            },
            "failed_requests": len(failed),
            "deadline_expired": sum(r.scheduler.deadline_expired
                                    for r in self.replicas),
            "sustained_tokens_per_sec": (total / span) if span > 0 else None,
            "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
            "ttft_p99_s": pct(ttft, 99),
            "tpot_p50_s": pct(tpot, 50), "tpot_p95_s": pct(tpot, 95),
            "tpot_p99_s": pct(tpot, 99),
            "drains": self.drains,
            "requeued": self.requeued,
            # RLHF weight publication (ISSUE 11): the last fleet-published
            # version plus every replica's installed version — a healthy
            # fleet shows them all equal once deferred commits landed
            "weight_publishes": self.weight_publishes,
            "published_version": self.published_version,
            "weight_versions": {r.replica_id: r.engine.weight_version
                                for r in self.replicas},
            # async shuffle-exchange sync (ISSUE 20): publish-path timing
            # plus the coordinator's staleness/propagation counters
            "publish": {
                "stage_s": self.publish_stage_s,
                "commit_s": self.publish_commit_s,
                "bytes": self.publish_bytes,
            },
            "sync": (dict(self._async_sync.staleness(), enabled=True)
                     if self._async_sync is not None
                     else {"enabled": False}),
            # fleet-aggregated speculative group (ISSUE 8): sums over
            # replicas; acceptance_rate re-derived from the sums so it is
            # token-weighted, not an average of per-replica averages
            "speculative": self._spec_aggregate(),
            # one-dispatch sampling (ISSUE 16): fleet-summed early-stop /
            # resample accounting, same sums-not-averages discipline
            "sampling": self._sampling_aggregate(),
            "kv_tier": self._tier_aggregate(),
            # multi-tenant LoRA (ISSUE 18): fleet-summed pool traffic and
            # per-adapter token tallies, same sums-not-averages discipline
            "adapters": self._adapter_aggregate(),
            "per_replica": [dict(r.scheduler.load(), state=r.state,
                                 preemptions=r.scheduler.preemptions)
                            for r in self.replicas],
        }

    def _tier_aggregate(self) -> Dict[str, object]:
        """Fleet-wide tiered-KV traffic (ISSUE 15): the scheduler's
        kv_tier/* counter group summed over replicas whose engine carries
        a tier (enabled stays False on a tier-less fleet)."""
        tiers = [(r, r.scheduler.tier) for r in self.replicas
                 if r.scheduler.tier is not None]
        if not tiers:
            return {"enabled": False}
        ts = [t.stats() for _, t in tiers]
        hits = sum(t["prefetch_hits"] for t in ts)
        misses = sum(t["prefetch_misses"] for t in ts)
        return {
            "enabled": True,
            "spills": sum(t["spills"] for t in ts),
            "fetches": sum(t["fetches"] for t in ts),
            "prefetch_misses": misses,
            "hit_rate": (hits / (hits + misses)) if hits + misses else None,
            "spilled_blocks": sum(t["spilled_blocks"] for t in ts),
            "host_bytes": sum(t["host_bytes"] for t in ts),
            "parks": sum(r.scheduler.parks for r, _ in tiers),
            "unparks": sum(r.scheduler.unparks for r, _ in tiers),
            "parked": sum(len(r.scheduler.parked) for r, _ in tiers),
        }

    def _spec_aggregate(self) -> Dict[str, object]:
        proposed = sum(r.scheduler.spec_proposed for r in self.replicas)
        accepted = sum(r.scheduler.spec_accepted for r in self.replicas)
        return {
            "enabled": any(r.scheduler.spec.enabled for r in self.replicas),
            "proposed": proposed,
            "accepted": accepted,
            "rejected": sum(r.scheduler.spec_rejected for r in self.replicas),
            "acceptance_rate": (accepted / proposed) if proposed else None,
            "rollbacks": sum(r.engine.spec_rollbacks for r in self.replicas),
        }

    def _adapter_aggregate(self) -> Dict[str, object]:
        """Fleet-wide multi-tenant pool traffic (ISSUE 18): pool counters
        summed over adapter-enabled replicas, per-adapter token tallies
        merged across wherever each tenant's requests actually ran."""
        pools = [(r, r.engine.adapters) for r in self.replicas
                 if r.engine.adapters is not None]
        if not pools:
            return {"enabled": False}
        ps = [p.stats() for _, p in pools]
        tokens: Dict[str, int] = {}
        for r, _ in pools:
            for aid, n in r.scheduler.adapter_tokens.items():
                tokens[aid] = tokens.get(aid, 0) + n
        return {
            "enabled": True,
            "publishes": self.adapter_publishes,
            "registered": max(p["registered"] for p in ps),
            "resident": sum(p["resident"] for p in ps),
            "hits": sum(p["hits"] for p in ps),
            "misses": sum(p["misses"] for p in ps),
            "evictions": sum(p["evictions"] for p in ps),
            "installs": sum(p["installs"] for p in ps),
            "parks": sum(r.scheduler.adapter_parks for r, _ in pools),
            "unparks": sum(r.scheduler.adapter_unparks for r, _ in pools),
            "tokens_by_adapter": tokens,
        }

    def _sampling_aggregate(self) -> Dict[str, object]:
        return {
            "seen": any(r.scheduler.sampling_seen for r in self.replicas),
            "early_stops": sum(r.scheduler.early_stops
                               for r in self.replicas),
            "dead_tokens_saved": sum(r.scheduler.dead_tokens_saved
                                     for r in self.replicas),
            "resamples": sum(r.scheduler.sampling_resamples
                             for r in self.replicas),
            "early_stop_freed_blocks": sum(r.engine.early_stop_freed_blocks
                                           for r in self.replicas),
        }

    def publish(self) -> dict:
        """Push the fleet aggregate downstream (``fleet/*`` events)."""
        return self.fleet.publish()


def fleet_commands(hostfile, script: str, script_args: Sequence[str] = (),
                   include: str = "", exclude: str = "",
                   num_replicas: int = -1,
                   extra_env: Optional[Dict[str, str]] = None
                   ) -> List[Tuple[str, List[str]]]:
    """Per-host launch commands for a real multi-host serving fleet — one
    serving worker per hostfile host, through the SAME parsing/filtering
    the training launcher uses (``launcher/runner.py``, SURVEY §1's
    ``deepspeed`` runner). Each worker sees ``SXT_REPLICA_ID`` /
    ``SXT_NUM_REPLICAS`` instead of the trainer's PROCESS_ID pair: serving
    replicas are independent processes behind the router, not one SPMD
    job, so they must NOT join ``jax.distributed``."""
    import shlex
    import sys

    from ..launcher.runner import filter_hosts, parse_hostfile

    hosts = parse_hostfile(hostfile)
    if not hosts:
        hosts = {"localhost": 1}
    hosts = filter_hosts(hosts, include, exclude, num_replicas)
    host_list = list(hosts)
    cmds: List[Tuple[str, List[str]]] = []
    for idx, host in enumerate(host_list):
        env = {"SXT_REPLICA_ID": str(idx),
               "SXT_NUM_REPLICAS": str(len(host_list))}
        env.update(extra_env or {})
        envs = [f"{k}={shlex.quote(v)}" for k, v in env.items()]
        inner = ["env"] + envs + [sys.executable, script] + list(script_args)
        if len(host_list) == 1:
            cmds.append((host, inner))
        else:
            cmds.append((host, ["ssh", host,
                                " ".join(shlex.quote(c) if i > 0 else c
                                         for i, c in enumerate(inner))]))
    return cmds
