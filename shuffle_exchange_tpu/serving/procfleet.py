"""Cross-process replica fleet: the router side of the RPC boundary
(ISSUE 17 tentpole).

``ProcessReplicaRouter`` is the ``ReplicaRouter`` contract re-based onto
real worker processes (``serving/worker.py``) behind the frame transport
(``serving/rpc.py``), selected by ``router.fleet_mode: process``. What
changes at the boundary — and what deliberately does not:

- **Replicas are processes.** One ``python -m
  shuffle_exchange_tpu.serving.worker`` per replica, spawned with the
  §5.3 launcher identity (``SXT_REPLICA_ID``/``SXT_NUM_REPLICAS``) and a
  deterministic engine spec, discovered through a ready-file handshake
  (the worker binds port 0 and publishes the real port).
- **Load is PUSHED.** Every RPC response piggybacks the worker's load
  report (queue depth / running / KV pressure); placement scores the
  cached reports. There is no cross-process ``load()`` call to block on.
- **Router bookkeeping is the sole source of truth.** Every submitted
  request lives in ``self.requests`` as a ServingRequest mirror (prompt
  + generated + sampling seed), refreshed by polls — failover replays
  from the router ALONE, exactly the PR 11 discipline, because a dead
  process answers nothing.
- **RPC outcomes drive the same health machine.** ``RpcTimeout`` (peer
  accepts, never answers — SIGSTOP/hang) -> SUSPECT with the clock-run
  miss budget deciding DEAD; ``RpcConnectionLost`` (refused/reset —
  kill -9) -> immediately DEAD with the engine LOST
  (``HealthMonitor.rpc_ok/rpc_hung/rpc_unreachable``). Process liveness
  (``Popen.poll``) feeds ``check()`` the crash half, as thread liveness
  did in threads mode.
- **Failover semantics carry over.** Poison quarantine after
  ``poison_death_threshold`` mid-execution deaths, bounded
  ``max_retries`` with exponential backoff through ``not_before``, and
  drain-replay re-placement (prompt + generated continuation injected at
  the front of a survivor's queue — token-identical under greedy, seeded
  chains replay bit-exactly). A hung worker's KV cannot be migrated out
  of a frozen process, so process-mode hang failover re-prefills; live
  KV handoff (the disagg prefill->decode path) uses
  :meth:`transfer_kv`, shipping the byte-exact payload planes over the
  socket unchanged.
- **Weight publishes stay two-phase.** ``stage_weights`` ships the
  leaves (``jax.tree_util`` order against the spec-derived treedef) to
  every ACTIVE worker; only when every stage succeeded does commit fan
  out — any stage failure discards every staged slot, leaving the whole
  fleet on the OLD version (the PR 10 atomicity bar, now across
  processes).

Threading: this router is a SINGLE-THREADED control loop by contract
(``utils.invariants.LOCK_ORDER`` notes) — its concurrency lives in the
worker processes, so there is nothing in-process to race and no lock to
rank. ``RpcClient`` is correspondingly single-owner.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..inference.config import InferenceConfig
from ..utils.logging import logger
from .health import H_DEAD, HealthMonitor
from .router import (LoadShedError, NoActiveReplicaError,
                     PoisonQuarantinedError, RetriesExhaustedError)
from .rpc import RpcClient, RpcConnectionLost, RpcError, RpcRemoteError, RpcTimeout
from .worker import request_to_wire, sampling_to_wire

FINISHED, FAILED = "finished", "failed"
_TERMINAL = (FINISHED, FAILED)
ACTIVE, DEAD, STOPPED = "active", "dead", "stopped"


class WorkerHandle:
    """Router-side record of one worker process: the Popen, its RPC
    client, and the latest pushed load report."""

    def __init__(self, replica_id: int, proc: subprocess.Popen,
                 client: RpcClient, port: int, log_path: str):
        self.replica_id = replica_id
        self.proc = proc
        self.client = client
        self.port = port
        self.log_path = log_path
        self.state = ACTIVE
        self.seen_tick_errors = 0

    @property
    def pid(self) -> int:
        return self.proc.pid

    @property
    def load(self) -> dict:
        return self.client.last_load or {}


class ProcessReplicaRouter:
    """N worker processes behind the placement/health/failover policy.

    ``spec`` is the deterministic engine spec every worker builds from
    (``worker.build_engine_from_spec``) — and the parity oracle's recipe.
    Config comes from ``spec["inference"]["router"]`` unless ``config``
    overrides it. ``env`` adds environment entries to every worker;
    ``worker_env`` adds per-replica entries keyed by replica id — the
    chaos seam for arming ``SXT_FAULTS`` plans in a SPECIFIC worker."""

    def __init__(self, spec: dict, n_replicas: Optional[int] = None, *,
                 config: Optional[InferenceConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 env: Optional[Dict[str, str]] = None,
                 worker_env: Optional[Dict[int, Dict[str, str]]] = None,
                 workdir: Optional[str] = None,
                 python: str = sys.executable):
        self.spec = dict(spec)
        cfg = config or InferenceConfig(**spec.get("inference", {}))
        self.rcfg = cfg.router
        self.n_replicas = int(n_replicas or self.rcfg.num_replicas)
        self.clock = clock
        self.python = python
        self.base_env = dict(env or {})
        self.worker_env = {int(k): dict(v)
                           for k, v in (worker_env or {}).items()}
        self.workdir = workdir or tempfile.mkdtemp(prefix="sxt-procfleet-")
        os.makedirs(self.workdir, exist_ok=True)
        self.spec_path = os.path.join(self.workdir, "engine_spec.json")
        with open(self.spec_path, "w") as f:
            json.dump(self.spec, f)
        self.health = HealthMonitor(self.rcfg, clock=clock)
        self.workers: Dict[int, WorkerHandle] = {}
        self._next_rid = 0
        self._next_uid = 0
        # the sole source of truth: ServingRequest mirrors per uid,
        # refreshed by polls — failover replays from these alone
        self.requests: Dict[int, object] = {}
        self.owner: Dict[int, int] = {}
        self._pending: List[int] = []
        # uids whose submit/inject TIMED OUT against a still-live worker:
        # the worker may have admitted the request before the reply was
        # lost, leaving an untracked duplicate holding KV — reaped via a
        # best-effort cancel on the worker's next successful exchange
        self._maybe_resident: Dict[int, set] = {}
        self._last_health_check = 0.0
        # failover/drain bookkeeping (the threaded stats() vocabulary)
        self.failovers = 0
        self.recovered = 0
        self.reprefill_tokens = 0
        self.migrated_sequences = 0
        self.migrated_blocks = 0
        self.quarantined: Dict[int, int] = {}
        self.retries_exhausted = 0
        self.shed = 0
        self.drains = 0
        self.requeued = 0
        self.weight_publishes = 0
        self.published_version: Optional[int] = None
        # multi-tenant LoRA (ISSUE 18): the retained wire payloads of
        # every fleet-published adapter — replayed to newcomers at spawn
        # so an elastic scale-up serves the same tenant set (mirrors the
        # threaded router's _published_adapters catch-up)
        self.adapter_publishes = 0
        self._published_adapters: Dict[str, Tuple[dict,
                                                  List[np.ndarray]]] = {}
        self._metrics_step = 0
        # async shuffle-exchange weight sync (ISSUE 20): built after the
        # spawn loop so the coordinator's peer count matches the fleet.
        # Deaths discovered INSIDE a delivery (_sync_apply -> _call ->
        # _fail_over) are deferred into _sync_dead and drained at the top
        # of sync_step(): deactivate_peer takes the coordinator's _mu,
        # which _deliver already holds at that point — safe because this
        # router is a single-threaded control loop.
        self._async_sync = None
        self._sync_dead: set = set()
        self.publish_stage_s = 0.0
        self.publish_commit_s = 0.0
        self.publish_bytes = 0
        for _ in range(self.n_replicas):
            self.spawn_replica()
        if self.rcfg.sync.enabled:
            from .async_sync import AsyncWeightSync
            self._async_sync = AsyncWeightSync(
                self.rcfg.sync, n_replicas=self._next_rid,
                apply_fn=self._sync_apply)

    # -- membership -----------------------------------------------------

    def spawn_replica(self) -> WorkerHandle:
        """Launch one worker, wait for its ready file, connect, register.
        The spawn is validated end-to-end: an early death or a missed
        handshake raises with the worker's log tail named."""
        rid = self._next_rid
        self._next_rid += 1
        ready = os.path.join(self.workdir, f"ready-{rid}.json")
        if os.path.exists(ready):
            os.remove(ready)
        log_path = os.path.join(self.workdir, f"worker-{rid}.log")
        env = dict(os.environ)
        env.update(self.base_env)
        env.update(self.worker_env.get(rid, {}))
        env["SXT_REPLICA_ID"] = str(rid)
        env["SXT_NUM_REPLICAS"] = str(max(self.n_replicas, rid + 1))
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        log = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                [self.python, "-m", "shuffle_exchange_tpu.serving.worker",
                 "--spec", self.spec_path, "--ready-file", ready],
                env=env, stdout=log, stderr=subprocess.STDOUT,
                cwd=repo_root)
        finally:
            log.close()
        deadline = time.monotonic() + self.rcfg.worker_start_timeout_s
        info = None
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"worker {rid} exited with {proc.returncode} before "
                    f"serving — {self._log_tail(log_path)}")
            if os.path.exists(ready):
                with open(ready) as f:
                    info = json.load(f)
                break
            time.sleep(0.05)
        if info is None:
            proc.kill()
            raise TimeoutError(
                f"worker {rid} did not publish its ready file within "
                f"{self.rcfg.worker_start_timeout_s:.0f}s — "
                f"{self._log_tail(log_path)}")
        client = RpcClient(
            "127.0.0.1", int(info["port"]),
            connect_retries=self.rcfg.rpc_connect_retries,
            connect_backoff_s=self.rcfg.rpc_connect_backoff_s,
            backoff_cap_s=self.rcfg.rpc_backoff_cap_s,
            default_timeout_s=self.rcfg.rpc_call_timeout_s, seed=rid)
        h = WorkerHandle(rid, proc, client, int(info["port"]), log_path)
        try:
            client.call("ping", timeout_s=self.rcfg.rpc_ping_timeout_s)
            # catch a newcomer up to the fleet's published adapter set —
            # a request routed here must never be refused for a tenant
            # every other replica already knows (ISSUE 18; mirrors the
            # threaded router's _add_replica catch-up). Still inside the
            # handshake: a failed catch-up fails THIS spawn cleanly
            # instead of leaking a half-provisioned worker into traffic
            for _aid, (meta, planes) in self._published_adapters.items():
                client.call("publish_adapter", dict(meta), planes,
                            timeout_s=self.rcfg.rpc_call_timeout_s)
        except Exception:
            # the handle is not registered yet, so no failover path will
            # ever reap this process — kill it here or it leaks live
            # outside all router bookkeeping
            client.close()
            proc.kill()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                logger.error(f"procfleet: worker {rid} (pid {proc.pid}) "
                             f"did not reap after a failed handshake ping")
            raise
        self.workers[rid] = h
        self.health.register(rid)
        sync = getattr(self, "_async_sync", None)
        if sync is not None:
            # a replacement/newcomer rejoins the gossip schedule at the
            # spec weights (version 0) — catch_up in scale_to / sync_step
            # brings it forward from the retained newest tree
            if rid >= sync.n_replicas:
                sync.add_peer()
            sync.reactivate_peer(rid, version=0)
            self._sync_dead.discard(rid)
        logger.info(f"procfleet: worker {rid} up (pid {h.pid}, port "
                    f"{h.port})")
        return h

    @staticmethod
    def _log_tail(path: str, n: int = 12) -> str:
        try:
            with open(path, "rb") as f:
                lines = f.read().decode("utf-8", "replace").splitlines()
            return "log tail:\n" + "\n".join(lines[-n:])
        except OSError:
            return f"(no log at {path})"

    @property
    def active_workers(self) -> List[WorkerHandle]:
        return [h for h in self.workers.values() if h.state == ACTIVE]

    def scale_to(self, n: int) -> int:
        """Grow the ACTIVE fleet back to ``n`` workers (the chaos
        drill's revive path); newcomers are caught up to the published
        weight version before taking traffic."""
        grown = 0
        while len(self.active_workers) < n:
            h = self.spawn_replica()
            if self._async_sync is not None:
                # the async coordinator RETAINS the newest published tree
                # (byte-exact wire copy), so the newcomer is caught up
                # here instead of waiting a full gossip propagation — no
                # republish from the caller needed
                caught = self._async_sync.catch_up(h.replica_id)
                if caught:
                    logger.info(
                        f"procfleet: worker {h.replica_id} caught up to "
                        f"version {self._async_sync.newest_version} from "
                        f"the retained publish")
            elif self.published_version is not None:
                # a fresh worker rebuilt version-0 weights from the spec;
                # republishing to IT alone would need the tree — the
                # caller republished through publish_weights, which
                # targets every ACTIVE worker, so just record the gap
                logger.warning(
                    f"procfleet: worker {h.replica_id} starts at the spec "
                    f"weights; republish to catch it up to version "
                    f"{self.published_version}")
            grown += 1
        return grown

    # -- RPC outcome classification -------------------------------------

    def _call(self, h: WorkerHandle, method: str,
              payload: Optional[dict] = None,
              bufs: Sequence[np.ndarray] = (),
              timeout_s: Optional[float] = None) -> Tuple[dict, list]:
        """One exchange + its health consequence. Success is the beat;
        a timeout is the hang shape (SUSPECT, clock escalates); a lost
        connection is the kill shape (DEAD now, engine lost, failover
        runs before the error propagates)."""
        try:
            out = h.client.call(method, payload, bufs, timeout_s=timeout_s)
        except RpcTimeout as e:
            state = self.health.rpc_hung(h.replica_id, str(e))
            if state == H_DEAD:
                self._fail_over(h.replica_id, str(e),
                                engine_reachable=True)
            raise
        except RpcConnectionLost as e:
            self.health.rpc_unreachable(h.replica_id, str(e))
            self._fail_over(h.replica_id, f"connection lost during "
                                          f"{method!r}: {e}",
                            engine_reachable=False)
            raise
        self.health.rpc_ok(h.replica_id)
        self._consume_strikes(h)
        self._reap_maybe_resident(h)
        return out

    def _consume_strikes(self, h: WorkerHandle) -> None:
        """Fold the pushed load report's tick-error counter into the
        strike machinery — a worker whose ticks raise repeatedly
        escalates SUSPECT -> DEAD exactly like a threaded replica."""
        load = h.load
        errs = int(load.get("tick_errors", 0))
        if errs > h.seen_tick_errors:
            reason = str(load.get("last_error", "tick raised"))
            for _ in range(errs - h.seen_tick_errors):
                state = self.health.strike(h.replica_id, reason)
            h.seen_tick_errors = errs
            if state == H_DEAD:
                self._fail_over(h.replica_id,
                                f"consecutive tick exceptions ({reason})",
                                engine_reachable=True)

    def _reap_maybe_resident(self, h: WorkerHandle) -> None:
        """Cancel possible duplicate sequences on a worker that answered
        again after a timed-out submit/inject. The router placed those
        uids elsewhere (or requeued them), so any copy still live here is
        an untracked duplicate decoding into KV it will never release —
        and it would refuse a later legitimate inject of the same uid
        with 'uid already live'. Best-effort by design: a direct client
        call (no health consequence, no recursion into _call); a failed
        reap keeps the set and retries on the next successful exchange."""
        uids = self._maybe_resident.get(h.replica_id)
        if not uids or h.state != ACTIVE:
            return
        doomed = sorted(u for u in uids
                        if self.owner.get(u) != h.replica_id)
        try:
            if doomed:
                h.client.call("cancel", {"uids": doomed},
                              timeout_s=self.rcfg.rpc_call_timeout_s)
        except RpcError:
            return
        self._maybe_resident.pop(h.replica_id, None)

    # -- placement / intake ---------------------------------------------

    def _placement_order(self, handles: List[WorkerHandle],
                         adapter_id: Optional[str] = None
                         ) -> List[WorkerHandle]:
        """Least-loaded first from the PUSHED reports — and health-ACTIVE
        workers strictly before SUSPECT ones: a suspected-hung worker
        costs a full RPC timeout per attempt, so it is only tried when no
        healthy peer remains (it may just be mid-compile). A request
        naming an adapter (ISSUE 18) discounts workers whose pushed
        report lists it resident — landing there skips a host->HBM page
        of the factor pair, the same affinity the threaded router scores."""
        states = self.health.states()
        affine = bool(self.rcfg.adapter_affinity and adapter_id is not None)

        def score(h: WorkerHandle):
            ld = h.load
            cost = (self.rcfg.queue_depth_weight
                    * (ld.get("queue_depth", 0) + ld.get("running", 0))
                    + self.rcfg.kv_pressure_weight
                    * ld.get("kv_pressure", 0.0))
            if affine and adapter_id in (ld.get("resident_adapters") or ()):
                cost -= self.rcfg.adapter_affinity_weight
            return (0 if states.get(h.replica_id) == "active" else 1,
                    cost, h.replica_id)

        return sorted(handles, key=score)

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               deadline_s: Optional[float] = None, sampling=None,
               adapter_id: Optional[str] = None) -> int:
        """Place one request; returns its fleet-wide uid. Raises the
        threaded router's error classes: LoadShedError past the shed bound,
        NoActiveReplicaError with zero survivors, and the aggregated
        per-replica refusals when nobody can take it."""
        from ..inference.scheduler import ServingRequest

        active = self.active_workers
        if not active:
            raise NoActiveReplicaError("no ACTIVE worker in the fleet")
        uid = self._next_uid
        self._next_uid += 1
        if self.rcfg.shed_queue_depth:
            depth = sum(h.load.get("queue_depth", 0) for h in active)
            if depth >= self.rcfg.shed_queue_depth:
                self.shed += 1
                raise LoadShedError(uid, depth, self.rcfg.shed_queue_depth,
                                    len(active))
        wire_sampling = sampling_to_wire(sampling)   # rejects logit_mask
        refusals = []
        for h in self._placement_order(active, adapter_id=adapter_id):
            try:
                self._call(h, "submit",
                           {"prompt": [int(t) for t in prompt],
                            "max_new_tokens": int(max_new_tokens),
                            "uid": uid, "deadline_s": deadline_s,
                            "sampling": wire_sampling,
                            "adapter_id": adapter_id})
            except RpcRemoteError as e:
                refusals.append(f"replica {h.replica_id}: "
                                f"{e.remote_type}: {e.remote_message}")
                continue
            except RpcTimeout as e:
                # a slow-but-alive worker may have ADMITTED the request
                # before the reply was lost; remember the uid so the
                # duplicate gets reaped once the worker answers again
                if h.state == ACTIVE:
                    self._maybe_resident.setdefault(
                        h.replica_id, set()).add(uid)
                refusals.append(f"replica {h.replica_id}: {e}")
                continue
            except RpcError as e:
                refusals.append(f"replica {h.replica_id}: {e}")
                continue
            r = ServingRequest(uid=uid,
                               prompt=[int(t) for t in prompt],
                               max_new_tokens=int(max_new_tokens),
                               deadline_s=deadline_s, sampling=sampling,
                               adapter_id=adapter_id)
            r.submitted_at = self.clock()
            self.requests[uid] = r
            self.owner[uid] = h.replica_id
            return uid
        raise RuntimeError(
            f"no replica could admit the request: {'; '.join(refusals)}")

    # -- the control loop -----------------------------------------------

    def poll(self) -> None:
        """Refresh the router-side mirrors from every ACTIVE worker (the
        streamed-token pickup) — and, for idle workers, ping: every
        exchange doubles as the heartbeat."""
        for h in list(self.active_workers):
            uids = [u for u, rid in self.owner.items()
                    if rid == h.replica_id
                    and self.requests[u].state not in _TERMINAL]
            try:
                if uids:
                    result, _ = self._call(h, "poll", {"uids": uids})
                else:
                    self._call(h, "ping",
                               timeout_s=self.rcfg.rpc_ping_timeout_s)
                    continue
            except RpcError:
                continue   # health consequence already applied by _call
            now = self.clock()
            for uid_s, st in result.get("requests", {}).items():
                r = self.requests.get(int(uid_s))
                if r is None or r.state in _TERMINAL:
                    continue
                r.generated = [int(t) for t in st.get("generated", ())]
                if r.first_token_at is None and r.generated:
                    r.first_token_at = now
                r.stopped = bool(st.get("stopped", False))
                state = st.get("state")
                if state == FINISHED:
                    r.state = FINISHED
                    r.finished_at = now
                elif state == FAILED:
                    r.state = FAILED
                    r.finished_at = now
                    r.error = RuntimeError(st.get("error")
                                           or "remote failure")
                elif state in ("queued", "prefill", "running"):
                    r.state = state

    def check_health(self, force: bool = False) -> int:
        """Clock-throttled health sweep: process liveness feeds the
        crash half (``Popen.poll``), RPC outcomes already fed the
        hang/unreachable half. Newly-DEAD workers fail over here."""
        now = self.clock()
        if not force and (now - self._last_health_check
                          < self.rcfg.health_check_interval_s):
            return 0
        self._last_health_check = now

        def is_alive(rid: int) -> Optional[bool]:
            h = self.workers.get(rid)
            if h is None or h.state != ACTIVE:
                return None
            return h.proc.poll() is None

        newly = self.health.check(is_alive)
        for rid, reason, reachable in newly:
            self._fail_over(rid, reason, engine_reachable=reachable)
        return len(newly)

    def _place_pending(self) -> int:
        """Re-place failed-over requests whose backoff gate has passed
        (oldest first — fleet FIFO)."""
        now = self.clock()
        placed = 0
        # Take the batch and leave self._pending EMPTY while we work: an
        # inject below can trigger _fail_over, whose victims append to
        # self._pending concurrently with this loop — a final overwrite
        # from a pre-loop snapshot would silently drop them (zero-lost
        # invariant), so the unplaced remainder is merged back instead.
        batch, self._pending = self._pending, []
        remaining: List[int] = []
        for uid in sorted(batch):
            r = self.requests[uid]
            if r.state in _TERMINAL:
                continue
            if now < r.not_before:
                remaining.append(uid)
                continue
            target = None
            # failover re-placement honors adapter affinity (ISSUE 18):
            # a victim lands on a survivor whose pool already holds its
            # adapter when one exists, so the replay pays no page-in
            for h in self._placement_order(self.active_workers,
                                           adapter_id=r.adapter_id):
                try:
                    self._call(h, "inject",
                               {"request": request_to_wire(r),
                                "front": True})
                except RpcTimeout:
                    # the worker may have admitted the inject before the
                    # reply was lost — remember the possible duplicate
                    if h.state == ACTIVE:
                        self._maybe_resident.setdefault(
                            h.replica_id, set()).add(uid)
                    continue
                except RpcError:
                    continue
                target = h
                break
            if target is None:
                remaining.append(uid)
                continue
            self._maybe_resident.get(target.replica_id, set()).discard(uid)
            self.owner[uid] = target.replica_id
            self.recovered += 1
            self.reprefill_tokens += len(r.prompt) + len(r.generated)
            placed += 1
        self._pending.extend(remaining)
        return placed

    def fail_orphans(self) -> int:
        """Fail every still-pending request with the typed error when the
        ACTIVE fleet is empty AND the caller will not revive it (serve()
        with no survivors; a chaos drill that revives must NOT call this
        — its pending requests are waiting for the replacement worker)."""
        if self.active_workers or not self._pending:
            return 0
        now = self.clock()
        failed = 0
        for uid in self._pending:
            r = self.requests[uid]
            if r.state not in _TERMINAL:
                r.state = FAILED
                r.finished_at = now
                r.error = NoActiveReplicaError(
                    f"request {uid}: no surviving replica could adopt it")
                failed += 1
        self._pending = []
        return failed

    # -- failover --------------------------------------------------------

    def _requeue_from_mirror(self, uid: int,
                             generated: Optional[Sequence[int]] = None
                             ) -> None:
        """Hand one request back to the pending path from the router's
        own mirror (the transfer_kv failure half: the source has already
        detached the sequence, so the mirror is the only live copy).
        Idempotent against _fail_over's requeue — a connection loss
        inside the same exchange may have beaten us here."""
        r = self.requests.get(uid)
        if r is None or r.state in _TERMINAL:
            return
        if generated is not None:
            r.generated = [int(t) for t in generated]
        r.state = "queued"
        self.owner.pop(uid, None)
        if uid not in self._pending:
            self._pending.append(uid)

    def _fail_over(self, replica_id: int, reason: str,
                   engine_reachable: bool) -> int:
        """Reclaim a dead worker's requests from the ROUTER's own
        mirrors (the dead process is never asked anything) and requeue
        them behind poison/retry/backoff — then make the death real:
        SIGKILL the pid (a SIGSTOPped corpse would otherwise thaw later
        and double-serve) and reap it. Re-placement happens in
        ``_place_pending`` once each request's backoff passes."""
        h = self.workers.get(replica_id)
        if h is None or h.state != ACTIVE:
            return 0
        h.state = DEAD
        self.failovers += 1
        self.health.mark_dead(replica_id, reason, engine_reachable)
        if self._async_sync is not None:
            # deferred, NOT deactivate_peer here: this very failover may
            # have been classified inside an edge delivery (_sync_apply
            # under the coordinator's _mu) — sync_step drains the set
            # before its next round, outside any delivery
            self._sync_dead.add(replica_id)
        try:
            h.proc.kill()
        except OSError:
            pass
        try:
            h.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            logger.error(f"procfleet: worker {replica_id} (pid {h.pid}) "
                         f"did not reap after SIGKILL")
        h.client.close()
        # the process is gone — nothing can still be resident on it
        self._maybe_resident.pop(replica_id, None)
        victims = sorted(u for u, rid in self.owner.items()
                         if rid == replica_id
                         and self.requests[u].state not in _TERMINAL)
        now = self.clock()
        requeued = 0
        for uid in victims:
            r = self.requests[uid]
            self.owner.pop(uid, None)
            mid_exec = r.state in ("prefill", "running")
            r.state = "queued"
            if mid_exec:
                r.replica_deaths += 1
                if r.replica_deaths >= self.rcfg.poison_death_threshold:
                    r.state = FAILED
                    r.finished_at = now
                    r.error = PoisonQuarantinedError(uid, r.replica_deaths)
                    self.quarantined[uid] = r.replica_deaths
                    logger.error(str(r.error))
                    continue
                r.retries += 1
                if r.retries > self.rcfg.max_retries:
                    r.state = FAILED
                    r.finished_at = now
                    r.error = RetriesExhaustedError(uid, r.retries,
                                                    self.rcfg.max_retries)
                    self.retries_exhausted += 1
                    logger.error(str(r.error))
                    continue
                r.not_before = now + (self.rcfg.retry_backoff_s
                                      * 2 ** (r.retries - 1))
            self._pending.append(uid)
            requeued += 1
        logger.warning(
            f"procfleet: worker {replica_id} failed over ({reason}): "
            f"{requeued}/{len(victims)} requests requeued from router "
            f"snapshots, {len(self.quarantined)} quarantined total")
        return requeued

    # -- elastic drain ---------------------------------------------------

    def drain(self, replica_id: int) -> int:
        """Gracefully drain one worker over RPC and requeue its export
        on survivors. The satellite-6 contract: a worker dying BETWEEN
        its export and the reply (the ``rpc_drain_reply`` fault window)
        must not error the drain — the router rolls back to its OWN
        snapshots and recovers through the normal failover path."""
        h = self.workers.get(replica_id)
        if h is None or h.state != ACTIVE:
            raise ValueError(f"replica {replica_id} is not ACTIVE")
        try:
            result, _ = self._call(h, "drain")
        except (RpcTimeout, RpcConnectionLost):
            # _call already classified the death and ran _fail_over — the
            # export is lost but the router-side mirrors are not; the
            # drain degrades to a failover instead of erroring
            return self._place_pending()
        exported = result.get("requests", ())
        for wire in exported:
            uid = int(wire["uid"])
            r = self.requests.get(uid)
            if r is None or r.state in _TERMINAL:
                continue
            # the worker's export is fresher than the last poll — adopt
            # its generated continuation before the replay
            r.generated = [int(t) for t in wire.get("generated", ())]
            r.state = "queued"
            self.owner.pop(uid, None)
            self._pending.append(uid)
        self.drains += 1
        self.requeued += len(exported)
        h.state = STOPPED
        try:
            h.client.call("shutdown", timeout_s=self.rcfg.rpc_ping_timeout_s)
        except RpcError:
            pass
        try:
            h.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            h.proc.kill()
        h.client.close()
        self.health.retire(replica_id)
        if self._async_sync is not None:
            # drain runs from user code, never inside an edge delivery —
            # direct deactivation is safe here
            self._async_sync.deactivate_peer(replica_id)
        self._place_pending()
        return len(exported)

    # -- two-phase weight publication ------------------------------------

    def publish_weights(self, params, version: Optional[int] = None) -> int:
        """Fleet-wide two-phase flip over the wire: stage the leaf planes
        on every ACTIVE worker, commit only when every stage succeeded;
        any stage failure discards every staged slot (whole fleet stays
        on the OLD version — the PR 10 atomicity bar). A worker dying
        between its stage and its commit fails over; the survivors'
        commits proceed (its replacement rebuilds from the spec and is
        republished by the caller).

        With ``router.sync.enabled`` (ISSUE 20) the barrier is replaced:
        the tree is retained once and flows to workers edge-by-edge over
        the decentralized schedule — see :meth:`_publish_async`."""
        import jax

        if self._async_sync is not None:
            return self._publish_async(params, version)
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]
        if version is None:
            version = (self.published_version or 0) + 1
        targets = self.active_workers
        if not targets:
            raise NoActiveReplicaError("no ACTIVE worker to publish to")
        staged: List[WorkerHandle] = []
        try:
            for h in targets:
                self._call(h, "stage_weights", {"version": version},
                           bufs=leaves)
                staged.append(h)
        except (RpcError, RpcRemoteError) as e:
            for h in staged:
                if h.state != ACTIVE:
                    continue
                try:
                    self._call(h, "discard_weights")
                except RpcError:
                    pass
            raise RuntimeError(
                f"publish_weights: staging failed ({e}); every staged "
                f"replica rolled back — the fleet still serves version "
                f"{self.published_version}") from e
        for h in staged:
            if h.state != ACTIVE:
                continue
            try:
                self._call(h, "commit_weights", {"defer": True})
            except RpcError as e:
                logger.error(f"procfleet: worker {h.replica_id} lost "
                             f"mid-commit ({e}); failover already ran")
        self.published_version = version
        self.weight_publishes += 1
        return version

    # -- async shuffle-exchange weight sync (ISSUE 20) -------------------

    def _sync_apply(self, rid: int, tree, version: int) -> None:
        """One edge delivery onto a worker process: the coordinator's
        ``apply_fn``. Ships the host leaves over the RPC frames and
        defer-commits, so the worker's tick boundary does the flip.
        Runs with the coordinator's ``_mu`` held; a death classified by
        ``_call`` lands in ``_sync_dead`` (via ``_fail_over``) rather
        than re-entering the coordinator, and the raise makes
        ``_deliver`` count a failed exchange."""
        import jax

        h = self.workers.get(rid)
        if h is None or h.state != ACTIVE:
            raise RuntimeError(f"sync apply: worker {rid} is not ACTIVE")
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
        self._call(h, "stage_weights", {"version": int(version)},
                   bufs=leaves)
        self._call(h, "commit_weights", {"defer": True})

    def _publish_async(self, params, version: Optional[int]) -> int:
        """The barrier-free publish: retain the tree once (O(tree
        bytes)), kick the trainer's current edge partners, and let
        ``sync_step`` (driven from the serve loop) propagate the rest.
        No fleet-wide stage/commit fan-out, no rollback choreography —
        a worker that never hears this version keeps serving its
        previous committed one (stale-but-honest, bounded by the
        staleness window)."""
        import jax

        sync = self._async_sync
        if not self.active_workers:
            raise NoActiveReplicaError("no ACTIVE worker to publish to")
        if version is None:
            version = max(sync.newest_version,
                          self.published_version or 0) + 1
        version = int(version)
        t0 = self.clock()
        retained = sync.publish(params, version)
        stage_dt = self.clock() - t0
        t1 = self.clock()
        kicked = sync.kick(version)
        commit_dt = self.clock() - t1
        self.weight_publishes += 1
        self.published_version = version
        self.publish_stage_s += stage_dt
        self.publish_commit_s += commit_dt
        self.publish_bytes += sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(retained))
        logger.info(
            f"procfleet: async publish v{version} retained in "
            f"{stage_dt * 1e3:.1f}ms, first hop reached {kicked} edge "
            f"partner(s); gossip owns the rest (window "
            f"{self.rcfg.sync.staleness_window})")
        return version

    def sync_step(self) -> int:
        """One background sync round. Deaths discovered inside an edge
        delivery were deferred into ``_sync_dead`` (the coordinator's
        ``_mu`` was held there) — drain them into ``deactivate_peer``
        first, outside any delivery, then run the edge round."""
        sync = self._async_sync
        if sync is None:
            return 0
        while self._sync_dead:
            sync.deactivate_peer(self._sync_dead.pop())
        return sync.step()

    def converge(self) -> int:
        """Reduce the fleet to the reference ``synchronization()``
        full-average on demand and record the minted version (see
        ``AsyncWeightSync.converge``). Every ACTIVE worker lands on the
        SAME averaged tree — bit-equal across processes because one
        retained host tree crosses the wire to all of them."""
        sync = self._async_sync
        if sync is None:
            raise RuntimeError(
                "converge: async sync is disabled (router.sync.enabled)")
        while self._sync_dead:
            sync.deactivate_peer(self._sync_dead.pop())
        _tree, version = sync.converge()
        self.weight_publishes += 1
        self.published_version = int(version)
        logger.info(f"procfleet: converge() installed full-average "
                    f"v{version} on every ACTIVE worker")
        return int(version)

    def publish_adapter(self, adapter_id: str, factors,
                        alpha: Optional[float] = None,
                        version: Optional[int] = None) -> int:
        """Register one LoRA adapter on every ACTIVE worker (ISSUE 18):
        the factors-only analogue of :meth:`publish_weights` — (A, B)
        planes per target ride one frame each, no base weights move.
        Single-phase by design: registration is content-keyed and
        idempotent on the pool side and pins nothing, so a partial
        publish needs no rollback — re-running it converges. Raises if
        any ACTIVE worker refused (no pool / bad factors); a worker that
        DIED mid-publish fails over normally and its replacement is
        caught up at spawn from the retained payload. Returns the
        version stamped on the fleet."""
        if not adapter_id:
            raise ValueError("publish_adapter: adapter_id must be non-empty")
        targets = sorted(factors)
        planes: List[np.ndarray] = []
        for t in targets:
            A, B = factors[t]
            planes += [np.asarray(A), np.asarray(B)]
        if version is None:
            prev = self._published_adapters.get(adapter_id)
            version = (int(prev[0].get("version", 0)) + 1) if prev else 1
        meta = {"adapter_id": str(adapter_id),
                "targets": [str(t) for t in targets],
                "alpha": None if alpha is None else float(alpha),
                "version": int(version)}
        active = self.active_workers
        if not active:
            raise NoActiveReplicaError("no ACTIVE worker to publish to")
        refusals = []
        for h in active:
            try:
                self._call(h, "publish_adapter", dict(meta), bufs=planes)
            except RpcRemoteError as e:
                refusals.append(f"replica {h.replica_id}: "
                                f"{e.remote_type}: {e.remote_message}")
            except RpcError as e:
                # death/hang: _call already ran the health consequence;
                # the replacement worker is caught up from the retained
                # payload at spawn, so this is not a refusal
                logger.error(f"procfleet: worker {h.replica_id} lost "
                             f"mid-adapter-publish ({e})")
        if refusals:
            raise RuntimeError(
                f"publish_adapter({adapter_id!r}): refused by "
                f"{'; '.join(refusals)} — registration is idempotent, "
                f"re-run after fixing the refusal")
        self._published_adapters[adapter_id] = (meta, planes)
        self.adapter_publishes += 1
        return int(version)

    # -- disagg KV handoff over the wire ---------------------------------

    def transfer_kv(self, src_rid: int, dst_rid: int, uid: int) -> int:
        """Move one live sequence's KV blocks src -> dst over the socket
        — the disagg prefill->decode handoff with the payload + scale
        planes shipped byte-exactly (PR 7 wire format, unchanged). The
        source exports-and-detaches atomically under its replica lock;
        the destination reserves, commits, and adopts mid-decode in one
        message (abort-on-failure leaves its pool clean). Returns the
        number of tokens whose KV moved without re-prefill."""
        uid = int(uid)
        src = self.workers.get(src_rid)
        dst = self.workers.get(dst_rid)
        if src is None or src.state != ACTIVE:
            raise ValueError(f"source replica {src_rid} is not ACTIVE")
        if dst is None or dst.state != ACTIVE:
            raise ValueError(f"destination replica {dst_rid} is not ACTIVE")
        try:
            result, planes = self._call(src, "export_kv",
                                        {"uid": uid, "handoff": True})
        except RpcTimeout:
            # the source may have detached the sequence (handoff=True)
            # before the reply was lost — the router mirror is then the
            # only live copy, so requeue it rather than leave it orphaned
            # in 'running'; if the export never actually ran, the stale
            # source copy is reaped as maybe-resident on recovery.
            # (RpcConnectionLost needs nothing here: _call already ran
            # _fail_over on src, which requeued every src-owned uid.)
            if src.state == ACTIVE:
                self._maybe_resident.setdefault(src_rid, set()).add(uid)
            self._requeue_from_mirror(uid)
            raise
        try:
            self._call(dst, "import_kv",
                       {"payload": result["payload"],
                        "request": result["request"]}, bufs=planes)
        except RpcError as e:
            # the source has already detached the sequence, so EVERY
            # import failure must hand the request back to the pending
            # path: a typed refusal (RpcRemoteError — the destination
            # aborted its reservation), a vanished destination
            # (RpcConnectionLost — dst's _fail_over requeues only
            # dst-OWNED uids, and owner still maps this one to src), or
            # a lost reply (RpcTimeout — the import may have landed;
            # reap the possible duplicate on recovery)
            if isinstance(e, RpcTimeout) and dst.state == ACTIVE:
                self._maybe_resident.setdefault(dst_rid, set()).add(uid)
            self._requeue_from_mirror(
                uid, generated=result["request"]["generated"])
            raise
        r = self.requests.get(int(uid))
        if r is not None:
            r.generated = [int(t) for t in result["request"]["generated"]]
        self.owner[int(uid)] = dst_rid
        self.migrated_sequences += 1
        seen = int(result["payload"]["seen_tokens"])
        self.migrated_blocks += -(-seen // int(result["payload"]["block_size"]))
        return seen

    # -- serve loop / stats / teardown -----------------------------------

    def serve(self, requests: Sequence[Union[Sequence[int], Tuple]],
              max_new_tokens: int = 32,
              arrivals: Optional[Sequence[float]] = None,
              deadline_s: Optional[float] = None,
              sampling=None,
              adapter_ids: Optional[Sequence[Optional[str]]] = None,
              timeout_s: float = 600.0) -> Dict[int, List[int]]:
        """Poisson-style offered-load loop (threaded ``serve`` shape):
        submit each prompt at its arrival offset, poll/health-check
        until every live uid reaches a terminal state. ``adapter_ids``
        aligns per-request LoRA adapters with ``requests`` (None entries
        serve the base model)."""
        n = len(requests)
        if sampling is None or not isinstance(sampling, (list, tuple)):
            samplings = [sampling] * n
        else:
            samplings = list(sampling)
        if adapter_ids is None:
            aids: List[Optional[str]] = [None] * n
        else:
            aids = list(adapter_ids)
            if len(aids) != n:
                raise ValueError(
                    f"adapter_ids has {len(aids)} entries for {n} requests")
        arrivals = list(arrivals) if arrivals is not None else [0.0] * n
        t0 = self.clock()
        uids: List[Optional[int]] = []
        i = 0
        deadline = time.monotonic() + timeout_s
        while True:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"process fleet did not drain in {timeout_s:.0f}s "
                    f"({len(uids)}/{n} submitted, "
                    f"pending={len(self._pending)})")
            if i < n and self.clock() - t0 >= arrivals[i]:
                try:
                    uids.append(self.submit(requests[i],
                                            max_new_tokens=max_new_tokens,
                                            deadline_s=deadline_s,
                                            sampling=samplings[i],
                                            adapter_id=aids[i]))
                except LoadShedError:
                    uids.append(None)
                i += 1
                continue
            self.poll()
            self.check_health()
            if self._async_sync is not None:
                # the background gossip round rides the control loop —
                # one edge set per iteration, never blocking a worker's
                # tick (deliveries defer-commit at tick boundaries)
                self.sync_step()
            self._place_pending()
            # serve() has no revive hook: with zero survivors nobody will
            # ever adopt the pending requests — fail them typed, don't hang
            self.fail_orphans()
            live = [u for u in uids if u is not None]
            if i >= n and all(self.requests[u].state in _TERMINAL
                              for u in live) and not self._pending:
                break
            time.sleep(0.005)
        return {u: list(self.requests[u].generated)
                for u in uids if u is not None}

    def stats(self) -> Dict[str, object]:
        def pct(xs, q):
            return float(np.percentile(xs, q)) if len(xs) else None

        done = [r for r in self.requests.values() if r.state == FINISHED]
        failed = [r for r in self.requests.values() if r.state == FAILED]
        ttft = [r.first_token_at - r.submitted_at for r in done
                if r.first_token_at is not None]
        total = sum(len(r.generated) for r in done)
        span = (max(r.finished_at for r in done)
                - min(r.submitted_at for r in done)) if done else 0.0
        return {
            "fleet_mode": "process",
            "replicas": len(self.workers),
            "active_replicas": len(self.active_workers),
            "requests": len(done),
            "failed_requests": len(failed),
            "generated_tokens": total,
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
            "shed": {"rejected": self.shed,
                     "queue_depth_bound": self.rcfg.shed_queue_depth},
            "drains": self.drains,
            "requeued": self.requeued,
            "weight_publishes": self.weight_publishes,
            "published_version": self.published_version,
            "publish": {"stage_s": self.publish_stage_s,
                        "commit_s": self.publish_commit_s,
                        "bytes": self.publish_bytes},
            "sync": (dict(self._async_sync.staleness(), enabled=True)
                     if self._async_sync is not None
                     else {"enabled": False}),
            "adapter_publishes": self.adapter_publishes,
            "published_adapters": sorted(self._published_adapters),
            "sustained_tokens_per_sec": (total / span) if span > 0 else None,
            "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
            "rpc": {rid: {"calls": h.client.calls,
                          "timeouts": h.client.timeouts,
                          "reconnects": h.client.reconnects}
                    for rid, h in self.workers.items()},
            "per_replica": [dict(h.load, state=h.state, pid=h.pid)
                            for h in self.workers.values()],
        }

    def publish_metrics(self, fleet_monitor) -> Dict[str, float]:
        """Write fleet-level RPC + fault-tolerance counters into a
        ``FleetMonitor`` ring under the ISSUE 12 router discipline
        (fleet-scoped labels, latest value wins) so process-mode fleets
        land on the same dashboards as threaded ones. Returns the values
        written. RPC counters are cumulative sums over every worker ever
        spawned — dead workers' totals are retained, so ``rpc/timeouts``
        keeps counting what the fleet has absorbed, not what survives."""
        vals: Dict[str, float] = {
            "rpc/calls": sum(h.client.calls
                             for h in self.workers.values()),
            "rpc/timeouts": sum(h.client.timeouts
                                for h in self.workers.values()),
            "rpc/reconnects": sum(h.client.reconnects
                                  for h in self.workers.values()),
            "rpc/workers_active": len(self.active_workers),
            "failover/deaths": self.failovers,
            "failover/recovered_requests": self.recovered,
            "failover/reprefill_tokens": self.reprefill_tokens,
            "shed/rejected": self.shed,
        }
        # getattr: duck-typed fleets (tests/metrics shims) carry only the
        # core counters; the publish/sync groups default to quiet zeros
        vals["publish/stage_s"] = getattr(self, "publish_stage_s", 0.0)
        vals["publish/commit_s"] = getattr(self, "publish_commit_s", 0.0)
        vals["publish/bytes"] = getattr(self, "publish_bytes", 0)
        sync = getattr(self, "_async_sync", None)
        if sync is not None:
            st = sync.staleness()
            vals["sync/edge_exchanges"] = st["edge_exchanges"]
            vals["sync/staleness_max"] = st["staleness_max"]
            vals["sync/versions_behind"] = st["versions_behind"]
            vals["sync/forced_catchups"] = st["forced_catchups"]
        self._metrics_step += 1
        fleet_monitor.write_events(
            [(label, v, self._metrics_step) for label, v in vals.items()])
        return vals

    def kill_worker(self, replica_id: int, sig: int = signal.SIGKILL) -> int:
        """Deliver a REAL signal to a worker process (the chaos seam:
        SIGKILL = vanish, SIGSTOP = freeze). Returns the pid signalled."""
        h = self.workers[replica_id]
        os.kill(h.pid, sig)
        return h.pid

    def stop(self) -> None:
        """Graceful fleet teardown: shutdown RPC, bounded wait, SIGKILL
        stragglers, reap everything (no zombie survives a drill)."""
        for h in self.workers.values():
            if h.state == ACTIVE:
                try:
                    h.client.call("shutdown",
                                  timeout_s=self.rcfg.rpc_ping_timeout_s)
                except RpcError:
                    pass
        for h in self.workers.values():
            if h.proc.poll() is None:
                try:
                    h.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    h.proc.kill()
                    try:
                        h.proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        logger.error(f"procfleet: worker {h.replica_id} "
                                     f"unreapable")
            h.client.close()


__all__ = ["ProcessReplicaRouter", "WorkerHandle"]
