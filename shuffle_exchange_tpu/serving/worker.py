"""Replica worker process entry for the cross-process fleet (ISSUE 17).

One worker = one real OS process owning one engine + scheduler pair,
serving the replica half of the router↔replica contract as RPC messages
(``serving/rpc.py``) instead of method calls: submit / inject / poll /
stats / load / drain / stage-weights / commit / KV export-import. The
process is the failure domain chaos actually kills — ``kill -9`` leaves
a refused connection (LOST), SIGSTOP leaves an accepting-but-silent
socket (REACHABLE-hung) — and the router's health machine discriminates
the two (``serving/health.py``).

Identity comes from the §5.3 launcher contract: ``SXT_REPLICA_ID`` /
``SXT_NUM_REPLICAS`` (what ``fleet_commands`` emits per hostfile host),
with the hostfile-position fallback for bare ssh fan-outs. Serving
workers must NOT join ``jax.distributed`` — replicas are independent
processes behind the router, not one SPMD job.

Engines are built from a DETERMINISTIC spec (model kwargs + init seed +
InferenceConfig kwargs): every worker — and the router's parity oracle —
derives byte-identical weights from the same seed, so process-fleet
token parity needs no weight shipping at startup. RLHF weight updates
arrive later through the two-phase stage/commit RPC pair, leaves on the
wire in ``jax.tree_util.tree_leaves`` order against the spec-derived
treedef.

Fault plans arrive via ``SXT_FAULTS`` in the worker's environment
(``testing/faults.py`` parses it at import), so ``fire_nth`` chaos
schedules stay deterministic across the process boundary — the parent
arms "crash on your 3rd tick" by spawning the child with the plan, and
the plan trips in the child exactly as it would in a thread. The
``replica_crash`` site escalates to ``os._exit`` here: in a process
fleet a simulated unclean death IS a real process death.

Module import stays stdlib+numpy cheap (jax loads lazily inside the
engine builder) so the identity/wire helpers are tier-1 testable without
paying a jax import.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..launcher.runner import parse_hostfile
from ..testing import faults, sanitizer
from ..utils.logging import logger
from .rpc import RpcServer

#: ready-file handshake: the worker binds port 0 and publishes the real
#: port (+ pid) here; the parent polls for it instead of racing the bind
READY_FILE_ENV = "SXT_WORKER_READY_FILE"


# ---------------------------------------------------------------------------
# identity (the §5.3 hostfile/env parse)
# ---------------------------------------------------------------------------

def resolve_replica_identity(env: Optional[Dict[str, str]] = None
                             ) -> Tuple[int, int]:
    """``(replica_id, num_replicas)`` from the launcher contract.

    Precedence: explicit ``SXT_REPLICA_ID``/``SXT_NUM_REPLICAS`` (what
    ``serving.fleet_commands`` emits per host), then position of this
    host (``SXT_HOST`` or the real hostname) in ``SXT_HOSTFILE``'s parse
    order, then the single-replica default. Raises ``ValueError`` on an
    inconsistent pair or a host missing from the hostfile — a worker
    with the wrong identity would shadow another replica's uid space."""
    env = dict(os.environ) if env is None else env
    num = int(env["SXT_NUM_REPLICAS"]) if env.get("SXT_NUM_REPLICAS") else 0
    rid_s = env.get("SXT_REPLICA_ID", "")
    if rid_s != "":
        rid = int(rid_s)
        num = num or rid + 1
    elif env.get("SXT_HOSTFILE"):
        hosts = list(parse_hostfile(env["SXT_HOSTFILE"]))
        if not hosts:
            raise ValueError(
                f"SXT_HOSTFILE={env['SXT_HOSTFILE']!r} parsed to zero "
                f"hosts and no SXT_REPLICA_ID is set")
        me = env.get("SXT_HOST") or socket.gethostname()
        if me not in hosts:
            raise ValueError(
                f"host {me!r} is not in the hostfile ({hosts}); set "
                f"SXT_HOST or SXT_REPLICA_ID explicitly")
        rid = hosts.index(me)
        num = num or len(hosts)
    else:
        rid, num = 0, num or 1
    if num < 1 or not 0 <= rid < num:
        raise ValueError(
            f"inconsistent replica identity: SXT_REPLICA_ID={rid} must "
            f"satisfy 0 <= id < SXT_NUM_REPLICAS={num}")
    return rid, num


# ---------------------------------------------------------------------------
# wire records (requests + sampling + KV payloads)
# ---------------------------------------------------------------------------

def sampling_to_wire(sp) -> Optional[dict]:
    if sp is None:
        return None
    if sp.logit_mask is not None:
        raise ValueError(
            "SamplingParams.logit_mask is a host callable and cannot cross "
            "the process boundary — constrained decoding is threads-mode "
            "only (fleet_mode: threads)")
    return {"temperature": sp.temperature, "top_k": sp.top_k,
            "top_p": sp.top_p, "seed": sp.seed,
            "eos_token_id": sp.eos_token_id,
            "stop": [list(s) for s in sp.stop]}


def sampling_from_wire(d: Optional[dict]):
    if d is None:
        return None
    from ..inference.config import SamplingParams

    return SamplingParams(
        temperature=float(d.get("temperature", 0.0)),
        top_k=int(d.get("top_k", 0)), top_p=float(d.get("top_p", 1.0)),
        seed=int(d.get("seed", 0)),
        eos_token_id=int(d.get("eos_token_id", -1)),
        stop=tuple(tuple(int(t) for t in s) for s in d.get("stop", ())))


def request_to_wire(r) -> dict:
    """A ServingRequest as a wire record — exactly the fields a replay
    needs (prompt + generated continuation + sampling seed + budgets);
    host-side timestamps stay home (clocks differ across processes)."""
    return {"uid": r.uid, "prompt": list(r.prompt),
            "max_new_tokens": r.max_new_tokens,
            "generated": list(r.generated),
            "deadline_s": r.deadline_s, "retries": r.retries,
            "replica_deaths": r.replica_deaths,
            "sampling": sampling_to_wire(r.sampling),
            "adapter_id": r.adapter_id,
            "stopped": bool(r.stopped), "state": r.state}


def request_from_wire(d: dict):
    from ..inference.scheduler import ServingRequest

    return ServingRequest(
        uid=int(d["uid"]), prompt=[int(t) for t in d["prompt"]],
        max_new_tokens=int(d["max_new_tokens"]),
        generated=[int(t) for t in d.get("generated", ())],
        deadline_s=d.get("deadline_s"),
        retries=int(d.get("retries", 0)),
        replica_deaths=int(d.get("replica_deaths", 0)),
        sampling=sampling_from_wire(d.get("sampling")),
        adapter_id=d.get("adapter_id"),
        stopped=bool(d.get("stopped", False)))


def kv_payload_to_wire(payload) -> Tuple[dict, List[np.ndarray]]:
    """KVBlockPayload -> (meta, planes). The planes are the payload's
    existing byte-exact wire format (PR 7) shipped UNCHANGED: k, v, then
    the f32 scale planes for quantized pools, then last_logits."""
    meta = {"uid": payload.uid, "tokens": list(payload.tokens),
            "seen_tokens": payload.seen_tokens,
            "kv_cache_dtype": payload.kv_cache_dtype,
            "block_size": payload.block_size,
            "weight_version": payload.weight_version,
            "quantized": payload.k_scale is not None,
            "has_logits": payload.last_logits is not None}
    planes = [payload.k, payload.v]
    if payload.k_scale is not None:
        planes += [payload.k_scale, payload.v_scale]
    if payload.last_logits is not None:
        planes.append(np.asarray(payload.last_logits))
    return meta, planes


def kv_payload_from_wire(meta: dict, planes: List[np.ndarray]):
    from ..inference.engine_v2 import KVBlockPayload

    quantized = bool(meta.get("quantized"))
    want = 2 + (2 if quantized else 0) + (1 if meta.get("has_logits") else 0)
    if len(planes) != want:
        raise ValueError(f"KV payload wants {want} planes, frame carries "
                         f"{len(planes)}")
    return KVBlockPayload(
        uid=int(meta["uid"]), tokens=[int(t) for t in meta["tokens"]],
        seen_tokens=int(meta["seen_tokens"]),
        last_logits=planes[-1] if meta.get("has_logits") else None,
        k=planes[0], v=planes[1],
        k_scale=planes[2] if quantized else None,
        v_scale=planes[3] if quantized else None,
        kv_cache_dtype=str(meta["kv_cache_dtype"]),
        block_size=int(meta["block_size"]),
        weight_version=meta.get("weight_version"))


# ---------------------------------------------------------------------------
# engine construction (deterministic spec)
# ---------------------------------------------------------------------------

def build_engine_from_spec(spec: dict):
    """Engine from a JSON spec — deterministic by construction: the same
    ``{"model": ..., "init_seed": N, "inference": ...}`` spec yields
    byte-identical weights in every process (seeded init), which is what
    makes process-fleet token parity checkable without shipping weights.
    ``{"factory": "pkg.mod:fn"}`` escapes to arbitrary construction."""
    if "factory" in spec:
        import importlib

        mod, _, fn = str(spec["factory"]).partition(":")
        if not fn:
            raise ValueError(f"factory spec must be 'module:callable', "
                             f"got {spec['factory']!r}")
        return getattr(importlib.import_module(mod), fn)(
            **spec.get("factory_kwargs", {}))
    import jax

    from ..inference import InferenceConfig, InferenceEngineV2
    from ..models import Transformer, tiny
    from ..models.transformer import tiny_moe

    # "model_kind" picks the tiny factory — "tiny_moe" puts an
    # expert-routed FFN on the wire (ISSUE 19) with the same seeded-init
    # determinism, so process-fleet MoE parity stays checkable
    kind = spec.get("model_kind", "tiny")
    factories = {"tiny": tiny, "tiny_moe": tiny_moe}
    if kind not in factories:
        raise ValueError(f"unknown model_kind {kind!r}; "
                         f"expected one of {sorted(factories)}")
    cfg = factories[kind](**spec.get("model", {}))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(int(spec.get("init_seed", 0))))
    return InferenceEngineV2(model, params,
                             InferenceConfig(**spec.get("inference", {})))


# ---------------------------------------------------------------------------
# the worker
# ---------------------------------------------------------------------------

class ReplicaWorker:
    """One process-fleet replica: engine + scheduler + RPC surface.

    A background tick thread drives the scheduler; RPC handler threads
    mutate it under ``_lock`` — the same rank-10 scheduler guard a
    threaded ``Replica`` holds (instrumented under the SAME sanitizer
    name, ``Replica.lock``, so the tick's hold-while-blocking allowance
    and the LOCK_ORDER rank both apply; the static analyzer additionally
    knows ``ReplicaWorker._lock`` at rank 10). The load report is read
    OUTSIDE the lock — plain int reads by the scheduler's own contract —
    so pings stay answerable while a tick sits in a multi-second compile
    (that responsiveness is exactly what separates a slow worker from a
    SIGSTOPped one)."""

    def __init__(self, engine, replica_id: int = 0,
                 host: str = "127.0.0.1", port: int = 0):
        from ..inference.scheduler import ContinuousBatchingScheduler

        self.replica_id = int(replica_id)
        self.engine = engine
        self.scheduler = ContinuousBatchingScheduler(
            engine, replica_id=self.replica_id)
        # the process-local replica scheduler guard — rank 10, shared
        # sanitizer identity with the threaded fleet's Replica.lock
        self._lock = sanitizer.wrap(threading.RLock(), "Replica.lock")
        import jax

        self._wire_treedef = jax.tree_util.tree_structure(engine.params)
        self.ticks = 0
        self.tick_errors = 0
        self.last_error = ""
        self._stop = threading.Event()
        self._tick_thread: Optional[threading.Thread] = None
        self.server = RpcServer({
            "ping": self._h_ping,
            "submit": self._h_submit,
            "inject": self._h_inject,
            "cancel": self._h_cancel,
            "poll": self._h_poll,
            "load": self._h_load,
            "stats": self._h_stats,
            "drain": self._h_drain,
            "publish_adapter": self._h_publish_adapter,
            "stage_weights": self._h_stage_weights,
            "commit_weights": self._h_commit_weights,
            "discard_weights": self._h_discard_weights,
            "export_kv": self._h_export_kv,
            "import_kv": self._h_import_kv,
            "shutdown": self._h_shutdown,
        }, host=host, port=port, load_provider=self.load_report)

    # -- drivers --------------------------------------------------------

    def start(self) -> "ReplicaWorker":
        self.server.start()
        t = threading.Thread(target=self._tick_loop,
                             name=f"serving-worker-tick-{self.replica_id}",
                             daemon=True)
        self._tick_thread = t
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._tick_thread is not None:
            self._tick_thread.join(timeout=30.0)
        self.server.stop()

    def _tick_loop(self) -> None:
        while not self._stop.is_set():
            try:
                with self._lock:
                    had = self.scheduler.tick()
            except faults.ReplicaCrashed as e:
                # in a process fleet, a simulated unclean death IS a real
                # one: no cleanup, no flush — the router sees a refused
                # connection, exactly what kill -9 leaves behind
                logger.error(f"worker {self.replica_id}: injected unclean "
                             f"death — {e}")
                os._exit(17)
            except BaseException as e:   # noqa: BLE001 — report, keep ticking
                self.tick_errors += 1
                self.last_error = f"{type(e).__name__}: {e}"
                logger.error(f"worker {self.replica_id}: tick raised "
                             f"{self.last_error}")
                self._stop.wait(0.01)
                continue
            if not had:
                self._stop.wait(0.002)

    # -- the pushed load report -----------------------------------------

    def load_report(self) -> dict:
        """Piggybacked on every RPC response (rpc.py load_provider): the
        placement numbers arrive PUSHED, never via a cross-process
        ``load()`` call. Lock-free by the scheduler's own load() contract
        (plain int reads) so it stays answerable mid-compile."""
        rep = dict(self.scheduler.load())
        rep.update(pid=os.getpid(), ticks=self.ticks,
                   tick_errors=self.tick_errors, last_error=self.last_error,
                   # the stamped serving version rides every reply so the
                   # router can audit async-sync staleness (ISSUE 20)
                   # without a dedicated call — a plain int read, still
                   # answerable mid-compile
                   weight_version=self.engine.weight_version)
        return rep

    # -- handlers --------------------------------------------------------

    def _h_ping(self, payload, bufs):
        return {"pid": os.getpid(), "replica_id": self.replica_id}

    def _h_submit(self, payload, bufs):
        with self._lock:
            uid = self.scheduler.submit(
                [int(t) for t in payload["prompt"]],
                max_new_tokens=int(payload.get("max_new_tokens", 32)),
                uid=payload.get("uid"),
                deadline_s=payload.get("deadline_s"),
                sampling=sampling_from_wire(payload.get("sampling")),
                adapter_id=payload.get("adapter_id"))
        return {"uid": uid}

    def _h_inject(self, payload, bufs):
        r = request_from_wire(payload["request"])
        with self._lock:
            self.scheduler.inject(r, front=bool(payload.get("front", True)))
        return {"uid": r.uid}

    def _h_cancel(self, payload, bufs):
        """Reap possibly-duplicate sequences (router timeout hygiene): a
        submit/inject whose reply was lost may have admitted the uid
        here while the router placed it elsewhere — drop each named uid
        from the scheduler and free its KV. Unknown uids are the common
        case (the timed-out call never landed) and are silently fine."""
        cancelled = []
        now = time.monotonic()
        with self._lock:
            for uid in payload.get("uids", ()):
                uid = int(uid)
                r = self.scheduler.requests.get(uid)
                if r is None:
                    continue
                if r.state not in ("finished", "failed"):
                    self.scheduler.fail(
                        r, RuntimeError("cancelled by router (duplicate "
                                        "reap after a lost reply)"), now)
                self.scheduler.requests.pop(uid, None)
                cancelled.append(uid)
        return {"cancelled": cancelled}

    def _h_poll(self, payload, bufs):
        """Token/state pickup for the router's bookkeeping mirror — the
        full generated list per uid (idempotent across lost responses;
        the router overwrites, never appends)."""
        out = {}
        with self._lock:
            for uid in payload.get("uids", ()):
                r = self.scheduler.requests.get(int(uid))
                if r is None:
                    continue
                out[str(uid)] = {
                    "state": r.state, "generated": list(r.generated),
                    "stopped": bool(r.stopped),
                    "error": (f"{type(r.error).__name__}: {r.error}"
                              if r.error is not None else None)}
        self.ticks = self.scheduler.ticks
        return {"requests": out}

    def _h_load(self, payload, bufs):
        return self.load_report()

    def _h_stats(self, payload, bufs):
        with self._lock:
            st = self.scheduler.stats()
        return {"stats": json.loads(json.dumps(st, default=str))}

    def _h_drain(self, payload, bufs):
        """Fence + export for an elastic drain. The rpc_drain_reply fault
        site sits BETWEEN the export and the reply — the satellite-6
        window: a worker dying here has already torn down its scheduler,
        so the router must recover from its OWN snapshots."""
        with self._lock:
            exported = self.scheduler.export_requests()
            wire = [request_to_wire(r) for r in exported]
        faults.maybe_die("rpc_drain_reply", self.replica_id)
        return {"requests": wire}

    def _h_publish_adapter(self, payload, bufs):
        """Register one LoRA adapter in this worker's pool (ISSUE 18).
        The factor planes ride the frame as binary buffers — (A, B) per
        target in ``payload["targets"]`` order — so a publish is one
        message, content-keyed and idempotent on the pool side (a resend
        after a lost reply is a no-op). Residency stays acquire's
        business: registering never pins a slot."""
        pool = getattr(self.engine, "adapters", None)
        if pool is None:
            raise ValueError(
                f"replica {self.replica_id} has no adapter pool — enable "
                f"inference config 'adapters' in the engine spec")
        targets = [str(t) for t in payload.get("targets", ())]
        if len(bufs) != 2 * len(targets):
            raise ValueError(
                f"publish_adapter wants {2 * len(targets)} factor planes "
                f"(A, B per target), frame carries {len(bufs)}")
        factors = {t: (bufs[2 * i], bufs[2 * i + 1])
                   for i, t in enumerate(targets)}
        alpha = payload.get("alpha")
        with self._lock:
            version = pool.register(
                str(payload["adapter_id"]), factors,
                alpha=None if alpha is None else float(alpha),
                version=payload.get("version"))
        return {"adapter_id": str(payload["adapter_id"]),
                "version": int(version)}

    def _h_stage_weights(self, payload, bufs):
        import jax

        leaves = [jax.numpy.asarray(b) for b in bufs]
        params = jax.tree_util.tree_unflatten(self._wire_treedef, leaves)
        with self._lock:
            self.engine.stage_weights(params,
                                      version=payload.get("version"))
        return {"staged": True}

    def _h_commit_weights(self, payload, bufs):
        with self._lock:
            committed = self.engine.commit_staged_weights(
                force=bool(payload.get("force", False)),
                defer=bool(payload.get("defer", True)))
        return {"committed": bool(committed),
                "version": self.engine.weight_version}

    def _h_discard_weights(self, payload, bufs):
        with self._lock:
            self.engine.discard_staged_weights()
        return {"discarded": True}

    def _h_export_kv(self, payload, bufs):
        """Serialize one sequence's KV blocks (+ its request record) for
        the wire. ``handoff: true`` additionally DETACHES the sequence
        under the replica lock — export, drop from the scheduler, flush
        the pool — so exactly one replica ever decodes it: the planes in
        the reply frame are copies, making the flush safe, and a failed
        import on the far side falls back to the router's drain-replay
        path (the snapshot it just received)."""
        uid = int(payload["uid"])
        handoff = bool(payload.get("handoff", False))
        with self._lock:
            r = self.scheduler.requests.get(uid)
            if handoff:
                if r is None or r.state != "running" or not r.generated:
                    raise ValueError(
                        f"uid {uid} is not a RUNNING mid-decode sequence "
                        f"on replica {self.replica_id} — handoff moves "
                        f"live KV; use drain/inject for the rest")
            payload_obj = self.engine.export_kv_blocks(uid)
            meta, planes = kv_payload_to_wire(payload_obj)
            wire_req = request_to_wire(r) if r is not None else None
            if handoff:
                if r in self.scheduler.active:
                    self.scheduler.active.remove(r)
                self.scheduler.requests.pop(uid, None)
                if uid in self.engine._seqs:
                    self.engine.flush([uid])
        return {"payload": meta, "request": wire_req}, planes

    def _h_import_kv(self, payload, bufs):
        """begin_import -> commit_import -> adopt_running in one message
        (the disagg handshake collapsed to one hop: the payload already
        crossed the wire, so reserve-then-pull has nothing left to
        overlap). Abort the reservation on ANY failure — the decode pool
        must come out clean (atomic-on-reject at the process boundary)."""
        kv = kv_payload_from_wire(payload["payload"], bufs)
        r = request_from_wire(payload["request"])
        with self._lock:
            resv = self.engine.begin_import(kv.uid, kv.seen_tokens)
            try:
                self.engine.commit_import(resv, kv)
                self.scheduler.adopt_running(r)
            except BaseException:
                self.engine.abort_import(resv)
                if kv.uid in self.engine._seqs:
                    self.engine.flush([kv.uid])
                raise
        return {"uid": kv.uid, "adopted": True}

    def _h_shutdown(self, payload, bufs):
        self._stop.set()
        return {"stopping": True}


# ---------------------------------------------------------------------------
# process entry
# ---------------------------------------------------------------------------

def _write_ready_file(path: str, info: dict) -> None:
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(info, f)
    os.replace(tmp, path)   # atomic: the parent never reads a torn file


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="shuffle_exchange_tpu.serving.worker",
        description="Process-fleet replica worker (ISSUE 17)")
    ap.add_argument("--spec", required=True,
                    help="path to the JSON engine spec "
                         "(model/init_seed/inference, or factory)")
    ap.add_argument("--ready-file",
                    default=os.environ.get(READY_FILE_ENV, ""),
                    help="where to publish {port, pid} once serving")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args(argv)

    # serving workers are independent processes behind the router — they
    # must NOT join jax.distributed. The platform is the parent's to state
    # (the worker inherits its environment): a worker that guessed "cpu"
    # would serve a TPU fleet's traffic on the host and nobody would notice
    if not os.environ.get("JAX_PLATFORMS"):
        raise SystemExit(
            "serving worker: JAX_PLATFORMS is not set. The process that "
            "starts a worker states its platform (cpu for the drills and "
            "tests; a TPU worker also needs a chip of its own)")
    import jax

    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    rid, num = resolve_replica_identity()
    with open(args.spec) as f:
        spec = json.load(f)
    engine = build_engine_from_spec(spec)
    worker = ReplicaWorker(engine, replica_id=rid,
                           host=args.host, port=args.port).start()
    logger.info(f"worker {rid}/{num}: serving on "
                f"{worker.server.host}:{worker.server.port} "
                f"(pid {os.getpid()}, faults={len(faults.armed())} armed)")
    if args.ready_file:
        _write_ready_file(args.ready_file,
                          {"port": worker.server.port, "pid": os.getpid(),
                           "replica_id": rid})
    try:
        while not worker._stop.wait(0.2):
            pass
        time.sleep(0.2)   # let the shutdown reply flush before teardown
    finally:
        worker.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
