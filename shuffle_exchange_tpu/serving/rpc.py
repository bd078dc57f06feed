"""RPC transport for the cross-process serving fleet (ISSUE 17).

The threaded fleet's replica boundary is a method call; this module makes
it a wire. One frame = a fixed header (magic + length), a JSON meta
document, and a raw binary tail for array planes — the KV payload and
weight-wire formats (PR 7/10) ship their existing byte-exact planes in
the tail unchanged, described (dtype/shape) in the meta:

    +------+--------+----------+---------------+------------------+
    | SXRP | u32 len| u32 mlen | meta (JSON)   | buf0 buf1 ...    |
    +------+--------+----------+---------------+------------------+

msgpack would be marginally tighter but is not in the image; JSON + raw
tail keeps the dependency surface at stdlib + numpy and the planes
uncopied on the wire (ISSUE 17 constraint: no new deps).

Failure classes (what the router's health machine consumes):

- :class:`RpcTimeout`        — the peer ACCEPTED the connection but never
  answered inside ``timeout_s``: the SIGSTOP/hung-process shape. The
  process is REACHABLE (kernel still completes the TCP handshake on a
  stopped process's listen backlog) but making no progress.
- :class:`RpcConnectionLost` — connect refused, reset, or EOF mid-frame:
  the kill -9 shape. Nothing is listening; the process is LOST.
- :class:`RpcProtocolError`  — the bytes are not a frame (bad magic,
  oversized length, torn meta): a peer/version bug, never a health
  signal. The server closes that connection and survives.
- :class:`RpcRemoteError`    — the remote handler RAISED; the typed error
  crosses back by name so `LoadShedError`-style refusals stay typed.

Every response envelope piggybacks the worker's current load report
(queue depth / running / KV pressure) — the process fleet's placement
reads this PUSHED report instead of calling a shared-memory ``load()``.

Locking: ``RpcClient`` is single-owner by contract (the process router's
serve loop); it holds no lock. ``RpcServer._mu`` guards only the
connection roster (rank 30 in ``utils.invariants.LOCK_ORDER`` — a leaf:
nothing is acquired while it is held, and handler dispatch runs OUTSIDE
it). Server threads are named ``sxt-rpc-*`` so the concurrency
sanitizer's thread-leak detector covers them.
"""

from __future__ import annotations

import json
import random
import socket
import struct
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..testing import sanitizer
from ..utils.logging import logger

MAGIC = b"SXRP"
_HDR = struct.Struct(">4sI")      # magic + frame length (beyond header)
_U32 = struct.Struct(">I")
#: frames above this are refused as protocol errors before any allocation
#: — a garbage length must not become a multi-GB recv buffer
MAX_FRAME_BYTES = 256 * 1024 * 1024


class RpcError(RuntimeError):
    """Base class for transport-level RPC failures."""


class RpcTimeout(RpcError):
    """The peer accepted the connection but did not answer in time — the
    hung/SIGSTOPped-process shape (REACHABLE, not progressing)."""

    def __init__(self, method: str, timeout_s: float):
        self.method = method
        self.timeout_s = timeout_s
        super().__init__(
            f"rpc {method!r} timed out after {timeout_s:.3f}s "
            f"(peer reachable but unresponsive)")


class RpcConnectionLost(RpcError):
    """Connect refused / reset / EOF mid-frame — the kill -9 shape
    (nothing is listening; the peer process is LOST)."""


class RpcProtocolError(RpcError):
    """The bytes on the wire are not a frame (bad magic, oversized
    length, torn meta) — a bug, never a health signal."""


class RpcRemoteError(RpcError):
    """The remote handler raised; carries the remote type name so typed
    refusals (shed/quarantine/validation) survive the wire."""

    def __init__(self, method: str, remote_type: str, message: str):
        self.method = method
        self.remote_type = remote_type
        self.remote_message = message
        super().__init__(f"rpc {method!r} failed remotely: "
                         f"{remote_type}: {message}")


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

def encode_frame(meta: dict, bufs: Sequence[np.ndarray] = ()) -> bytes:
    """One wire frame: meta gains a ``bufs`` plane table describing the
    binary tail (dtype/shape per plane, in tail order)."""
    arrs = [np.ascontiguousarray(b) for b in bufs]
    meta = dict(meta)
    meta["bufs"] = [{"dtype": a.dtype.str, "shape": list(a.shape)}
                    for a in arrs]
    mbytes = json.dumps(meta).encode("utf-8")
    tail = b"".join(a.tobytes() for a in arrs)
    body = _U32.pack(len(mbytes)) + mbytes + tail
    if len(body) > MAX_FRAME_BYTES:
        raise RpcProtocolError(
            f"frame of {len(body)} bytes exceeds MAX_FRAME_BYTES "
            f"({MAX_FRAME_BYTES})")
    return _HDR.pack(MAGIC, len(body)) + body


def decode_frame(data: bytes) -> Tuple[dict, List[np.ndarray]]:
    """Inverse of :func:`encode_frame` (whole frame, header included).
    Raises :class:`RpcProtocolError` on anything that is not a frame."""
    if len(data) < _HDR.size:
        raise RpcProtocolError(
            f"frame truncated: {len(data)} bytes < {_HDR.size}-byte header")
    magic, length = _HDR.unpack_from(data)
    if magic != MAGIC:
        raise RpcProtocolError(f"bad magic {magic!r} (want {MAGIC!r})")
    if length > MAX_FRAME_BYTES:
        raise RpcProtocolError(
            f"declared frame length {length} exceeds MAX_FRAME_BYTES "
            f"({MAX_FRAME_BYTES})")
    body = data[_HDR.size:]
    if len(body) != length:
        raise RpcProtocolError(
            f"frame truncated: header declares {length} body bytes, "
            f"got {len(body)}")
    return _decode_body(bytes(body))


def _decode_body(body: bytes) -> Tuple[dict, List[np.ndarray]]:
    if len(body) < _U32.size:
        raise RpcProtocolError("frame body shorter than its meta length")
    (mlen,) = _U32.unpack_from(body)
    if mlen > len(body) - _U32.size:
        raise RpcProtocolError(
            f"meta length {mlen} exceeds body ({len(body) - _U32.size} "
            f"bytes after the length word)")
    try:
        meta = json.loads(body[_U32.size:_U32.size + mlen].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise RpcProtocolError(f"frame meta is not JSON: {e}") from e
    if not isinstance(meta, dict):
        raise RpcProtocolError(
            f"frame meta must be an object, got {type(meta).__name__}")
    tail = memoryview(body)[_U32.size + mlen:]
    bufs: List[np.ndarray] = []
    off = 0
    for spec in meta.get("bufs", ()):
        try:
            dt = np.dtype(spec["dtype"])
            shape = tuple(int(s) for s in spec["shape"])
        except (TypeError, KeyError, ValueError) as e:
            raise RpcProtocolError(f"bad plane spec {spec!r}: {e}") from e
        nbytes = dt.itemsize * int(np.prod(shape, dtype=np.int64))
        if off + nbytes > len(tail):
            raise RpcProtocolError(
                f"plane table wants {off + nbytes} tail bytes, frame "
                f"carries {len(tail)}")
        bufs.append(np.frombuffer(tail[off:off + nbytes],
                                  dtype=dt).reshape(shape))
        off += nbytes
    if off != len(tail):
        raise RpcProtocolError(
            f"frame tail has {len(tail) - off} undeclared trailing bytes")
    return meta, bufs


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    """Read exactly n bytes; EOF mid-read is a lost connection."""
    chunks = []
    got = 0
    while got < n:
        chunk = sock.recv(min(1 << 20, n - got))
        if not chunk:
            raise RpcConnectionLost(
                f"connection closed mid-frame ({got}/{n} bytes)")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def read_frame(sock: socket.socket,
               max_frame: int = MAX_FRAME_BYTES
               ) -> Tuple[dict, List[np.ndarray]]:
    """Read one frame off a socket. Timeouts propagate as
    ``socket.timeout`` (the caller owns the timeout policy); a bad header
    raises :class:`RpcProtocolError` without consuming the declared
    length, so the caller can close the poisoned connection."""
    hdr = _recv_exact(sock, _HDR.size)
    magic, length = _HDR.unpack(hdr)
    if magic != MAGIC:
        raise RpcProtocolError(f"bad magic {magic!r} (want {MAGIC!r})")
    if length > max_frame:
        raise RpcProtocolError(
            f"declared frame length {length} exceeds the {max_frame}-byte "
            f"bound")
    return _decode_body(_recv_exact(sock, length))


# ---------------------------------------------------------------------------
# retry/backoff
# ---------------------------------------------------------------------------

def backoff_delays(attempts: int, base_s: float, *, factor: float = 2.0,
                   cap_s: float = 2.0, jitter: float = 0.1,
                   seed: int = 0) -> List[float]:
    """The full exponential-backoff schedule for ``attempts`` retries —
    ``base * factor**k`` capped at ``cap_s``, each stretched by a
    DETERMINISTIC jitter in ``[0, jitter)`` drawn from ``seed`` (full
    determinism is what lets the chaos drill reproduce a retry storm
    run-for-run; tests pin the exact schedule)."""
    if attempts < 0:
        raise ValueError(f"attempts must be >= 0, got {attempts}")
    rng = random.Random(seed)
    out = []
    for k in range(attempts):
        d = min(cap_s, base_s * (factor ** k))
        out.append(d * (1.0 + jitter * rng.random()))
    return out


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------

class RpcClient:
    """One worker's control connection. Single-owner by contract (the
    process router's serve loop) — no lock, no concurrent calls.

    ``call`` lazily (re)connects with a bounded, jittered backoff
    schedule; a timeout or lost connection poisons the socket (a torn
    stream cannot carry another frame) and the NEXT call reconnects.
    Calls are never auto-retried — submit/inject are not idempotent, and
    the router's failover layer owns the retry policy."""

    def __init__(self, host: str, port: int, *,
                 connect_retries: int = 5,
                 connect_backoff_s: float = 0.05,
                 backoff_cap_s: float = 2.0,
                 connect_timeout_s: float = 5.0,
                 default_timeout_s: float = 30.0,
                 max_frame: int = MAX_FRAME_BYTES,
                 seed: int = 0,
                 clock_sleep: Callable[[float], None] = time.sleep):
        self.host = host
        self.port = int(port)
        self.connect_retries = int(connect_retries)
        self.connect_backoff_s = float(connect_backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.connect_timeout_s = float(connect_timeout_s)
        self.default_timeout_s = float(default_timeout_s)
        self.max_frame = int(max_frame)
        self.seed = int(seed)
        self._sleep = clock_sleep
        self._sock: Optional[socket.socket] = None
        self._ever_connected = False
        self._next_id = 0
        self.calls = 0
        self.timeouts = 0
        self.reconnects = 0
        #: the last piggybacked load report (the PUSHED load path — the
        #: placement score reads this, never a cross-process ``load()``)
        self.last_load: Optional[dict] = None

    # -- connection management ------------------------------------------

    def _connect(self, timeout_budget: Optional[float] = None
                 ) -> socket.socket:
        """FIRST connect (the spawn handshake) retries with the jittered
        backoff schedule — the worker may still be binding. A RECONNECT
        (the previous stream was poisoned by a timeout/reset) gets
        exactly ONE attempt bounded by the caller's own timeout budget:
        a dead or frozen peer must surface as a typed error within one
        call budget, never stall the control loop through a retry loop —
        the retry POLICY lives in the router's failover layer, not
        here."""
        if self._ever_connected:
            timeout = self.connect_timeout_s
            if timeout_budget is not None:
                timeout = min(timeout, timeout_budget)
            try:
                sock = socket.create_connection((self.host, self.port),
                                                timeout=timeout)
            except OSError as e:
                raise RpcConnectionLost(
                    f"reconnect to {self.host}:{self.port} failed: "
                    f"{e}") from e
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.reconnects += 1
            return sock
        delays = backoff_delays(self.connect_retries,
                                self.connect_backoff_s,
                                cap_s=self.backoff_cap_s, seed=self.seed)
        last: Optional[BaseException] = None
        for attempt in range(self.connect_retries + 1):
            try:
                sock = socket.create_connection(
                    (self.host, self.port), timeout=self.connect_timeout_s)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                self._ever_connected = True
                return sock
            except OSError as e:
                last = e
                if attempt < self.connect_retries:
                    self._sleep(delays[attempt])
        raise RpcConnectionLost(
            f"connect to {self.host}:{self.port} failed after "
            f"{self.connect_retries + 1} attempts: {last}")

    def close(self) -> None:
        sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    # -- the call --------------------------------------------------------

    def call(self, method: str, payload: Optional[dict] = None,
             bufs: Sequence[np.ndarray] = (),
             timeout_s: Optional[float] = None
             ) -> Tuple[dict, List[np.ndarray]]:
        """One request/response exchange; returns ``(result, planes)``.
        Raises one of: :class:`RpcTimeout` (reachable, no answer),
        :class:`RpcConnectionLost` (refused/reset/EOF),
        :class:`RpcRemoteError` (handler raised),
        :class:`RpcProtocolError` (non-frame bytes)."""
        timeout = self.default_timeout_s if timeout_s is None else timeout_s
        if self._sock is None:
            self._sock = self._connect(timeout_budget=timeout)
        sock = self._sock
        self._next_id += 1
        call_id = self._next_id
        frame = encode_frame({"id": call_id, "method": method,
                              "payload": payload or {}}, bufs)
        self.calls += 1
        try:
            sock.settimeout(timeout)
            sock.sendall(frame)
            meta, planes = read_frame(sock, self.max_frame)
        except (socket.timeout, TimeoutError):
            self.timeouts += 1
            self.close()
            raise RpcTimeout(method, timeout) from None
        except RpcConnectionLost:
            self.close()
            raise
        except RpcProtocolError:
            self.close()
            raise
        except OSError as e:
            self.close()
            raise RpcConnectionLost(
                f"connection to {self.host}:{self.port} lost during "
                f"{method!r}: {e}") from e
        if meta.get("id") != call_id:
            self.close()
            raise RpcProtocolError(
                f"response id {meta.get('id')!r} does not match call id "
                f"{call_id} — the stream is desynchronized")
        if isinstance(meta.get("load"), dict):
            self.last_load = meta["load"]
        if not meta.get("ok", False):
            err = meta.get("error") or {}
            raise RpcRemoteError(method, str(err.get("type", "Exception")),
                                 str(err.get("message", "")))
        return meta.get("result") or {}, planes


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class RpcServer:
    """Frame server for one worker process.

    ``handlers`` maps method name -> ``fn(payload, bufs)`` returning
    either ``result_dict`` or ``(result_dict, planes)``. Handler
    exceptions become error envelopes (the connection survives — a typed
    refusal is an answer, not a failure); protocol errors close THAT
    connection and the server survives. Every envelope piggybacks
    ``load_provider()`` when one is given — the pushed load report."""

    def __init__(self, handlers: Dict[str, Callable], *,
                 host: str = "127.0.0.1", port: int = 0,
                 load_provider: Optional[Callable[[], dict]] = None,
                 max_frame: int = MAX_FRAME_BYTES):
        self.handlers = dict(handlers)
        self.load_provider = load_provider
        self.max_frame = int(max_frame)
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(16)
        self.host, self.port = self._listener.getsockname()[:2]
        # rank 30 (utils.invariants.LOCK_ORDER): a leaf — guards only the
        # connection roster; dispatch runs outside it
        self._mu = sanitizer.wrap(threading.Lock(), "RpcServer._mu")
        self._conns: List[socket.socket] = []
        self._threads: List[threading.Thread] = []
        self._stopping = False
        self._accept_thread: Optional[threading.Thread] = None
        self.served = 0
        self.protocol_errors = 0

    def start(self) -> "RpcServer":
        t = threading.Thread(target=self._accept_loop,
                             name=f"sxt-rpc-accept-{self.port}", daemon=True)
        self._accept_thread = t
        t.start()
        return self

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                conn, addr = self._listener.accept()
            except OSError:
                return   # listener closed by stop()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._mu:
                if self._stopping:
                    conn.close()
                    return
                self._conns.append(conn)
                t = threading.Thread(
                    target=self._serve_conn, args=(conn, addr),
                    name=f"sxt-rpc-conn-{addr[1]}", daemon=True)
                self._threads.append(t)
            t.start()

    def _serve_conn(self, conn: socket.socket, addr) -> None:
        try:
            while not self._stopping:
                try:
                    meta, bufs = read_frame(conn, self.max_frame)
                except RpcProtocolError as e:
                    # not a frame: this connection is poisoned — close it
                    # cleanly; the SERVER (and every other connection)
                    # survives, and nothing ever blocks forever
                    self.protocol_errors += 1
                    logger.warning(f"rpc: closing {addr} on protocol "
                                   f"error: {e}")
                    return
                except RpcConnectionLost:
                    return   # peer hung up between frames
                conn.sendall(self._dispatch(meta, bufs))
        except OSError:
            return           # peer reset mid-reply
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._mu:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _dispatch(self, meta: dict, bufs: List[np.ndarray]) -> bytes:
        call_id = meta.get("id")
        method = meta.get("method", "")
        envelope: dict = {"id": call_id}
        planes: Sequence[np.ndarray] = ()
        fn = self.handlers.get(method)
        try:
            if fn is None:
                raise KeyError(f"unknown rpc method {method!r}; known: "
                               f"{sorted(self.handlers)}")
            out = fn(meta.get("payload") or {}, bufs)
            if isinstance(out, tuple):
                result, planes = out
            else:
                result = out
            envelope["ok"] = True
            envelope["result"] = result or {}
        except BaseException as e:   # noqa: BLE001 — the wire must answer
            envelope["ok"] = False
            envelope["error"] = {"type": type(e).__name__, "message": str(e)}
        self.served += 1
        if self.load_provider is not None:
            try:
                envelope["load"] = self.load_provider()
            except Exception as e:
                logger.warning(f"rpc: load_provider raised: {e}")
        try:
            return encode_frame(envelope, planes)
        except RpcProtocolError as e:
            # an unencodable reply (e.g. result planes past
            # MAX_FRAME_BYTES) must NOT escape and tear the connection
            # down — the client would see EOF -> RpcConnectionLost and
            # the router would SIGKILL a healthy worker. Answer with a
            # typed error envelope instead, planes dropped.
            self.protocol_errors += 1
            logger.warning(f"rpc: reply to {method!r} unencodable: {e}")
            envelope.pop("result", None)
            envelope["ok"] = False
            envelope["error"] = {"type": "RpcProtocolError",
                                 "message": str(e)}
            return encode_frame(envelope, ())

    def stop(self) -> None:
        self._stopping = True
        try:
            self._listener.close()
        except OSError:
            pass
        with self._mu:
            conns = list(self._conns)
            threads = list(self._threads)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)
        for t in threads:
            t.join(timeout=5.0)


__all__ = [
    "MAGIC", "MAX_FRAME_BYTES",
    "RpcError", "RpcTimeout", "RpcConnectionLost", "RpcProtocolError",
    "RpcRemoteError",
    "encode_frame", "decode_frame", "read_frame", "backoff_delays",
    "RpcClient", "RpcServer",
]
