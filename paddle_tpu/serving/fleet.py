"""Disaggregated serving fleet: prefill workers, decode workers, and
the MigrateKV handoff between them (ISSUE 16 tentpole).

Everything PR 9/11 built for the generative tier lives in ONE process;
this module splits it DistServe/Splitwise-style:

- **Prefill workers** run only the prompt pass: a `FleetWorker` with
  ``role='prefill'`` wraps a GenerativeEngine, warms ONLY the prefill
  ladder, runs the prompt through it, and ships the resulting KV
  blocks to a decode worker over fastwire method ``MigrateKV`` —
  block-table header (json) + the raw K/V page payloads, received
  straight into the decode worker's BlockPool.  The source frees its
  blocks the moment the host-side export copy exists (migrated-away);
  its pool never holds decode-lifetime state.
- **Decode workers** (``role='decode'``) wrap the same engine plus a
  DecodeLoop; a migrated request joins the continuous batch WITHOUT a
  prefill (TokenScheduler/DecodeLoop admit it by its pre-installed
  blocks).  Each worker keeps a request-id -> future table, so a
  hedged or re-sent migration is deduplicated (exactly-once per
  worker) and ``wait`` can be called from any router attempt.
- **Torn migrations are named, not silent**: the page install runs
  under the engine's BufferEpochGuard (import_blocks brackets
  begin/rebind like a dispatch), and a payload that does not match the
  header's block table — the mid-payload tear fault_matrix injects —
  rolls back the destination's half-received blocks and raises
  ``BufferLifetimeError`` named ``kv_migration:<req_id>`` (flight
  artifact under FLAGS_telemetry_dump_dir, sanitizer trip counter).

Workers run as separate PROCESSES (``python -m paddle_tpu.serving.fleet
--role decode ...``; SIGKILL-able, which tools/serve_fleet_bench.py
does mid-run) speaking the fastwire framing over TCP, or in-process
behind ``LocalTransport`` for the --quick tier-1 smoke — same byte
codec either way, no ports needed beyond loopback.  The router in
front is router.FleetRouter.

Wire formats (MIGRATION.md "MigrateKV wire contract"):

``FleetCall`` (method 11)   u32 head_len | json head   (both directions)
``MigrateKV`` (method 10)   u32 head_len | json head | K pages | V pages
  head: {"v": 1, "req": {"id","prompt","first","max_new","eos"},
         "kv": {"n_blocks","block_size","n_layers","n_heads",
                "head_dim","dtype"},
         "epoch": <source kv epoch>, "src": <worker name>}
  pages: C-order fp32 ``[L, n_blocks, bs, H, d]``, K then V; sizes
  derive from the kv dims, so a short body is detectable (torn).
  reply: u32 head_len | json {"ok": true, "blocks": [...],
         "epoch": <dest post-install epoch>}  — the epoch handshake.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import struct
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np

from paddle_tpu.core import sanitizer as _san
from paddle_tpu.core.flags import FLAGS, define_flag
from paddle_tpu.distributed.fastwire import MAGIC, METHODS
from paddle_tpu.distributed.resilience import InjectedFault, fault_point
from paddle_tpu.observability import metrics as _metrics

from .batcher import RequestQueue
from .generative import DecodeLoop, GenRequest, GenerativeEngine, tiny_lm

__all__ = ["FleetWorker", "FleetEndpoint", "SocketTransport",
           "LocalTransport", "FleetRemoteError", "encode_call",
           "decode_call", "encode_migrate", "M_MIGRATE", "M_CALL"]

M_MIGRATE = METHODS["MigrateKV"]
M_CALL = METHODS["FleetCall"]

define_flag("fleet_lease_s", 2.0,
            "router-side worker lease: a worker unreachable for this "
            "long is evicted from membership and its in-flight "
            "requests re-prefilled on a survivor (PR 1 lease "
            "semantics applied to serving)")
define_flag("fleet_lease_interval_s", 0.5,
            "how often the router pings every member (lease renewal "
            "cadence; each sweep also recomputes "
            "serve_fleet_availability)")
define_flag("fleet_hedge_s", 0.0,
            "hedged re-dispatch: a request not finished after this "
            "many seconds gets a second full attempt on different "
            "workers, first completion wins (0 disables)")
define_flag("fleet_request_deadline_s", 120.0,
            "end-to-end per-request deadline across all router "
            "attempts (DeadlineExceeded past it)")
define_flag("fleet_max_attempts", 4,
            "bounded per-request dispatch attempts per router "
            "attempt-loop (each eviction/hedge runs its own loop)")
define_flag("fleet_prefix_tokens", 8,
            "token-id prefix length the router hashes for "
            "prefix-affinity prefill placement")
define_flag("fleet_decode_credits", 16,
            "router admission valve: max outstanding dispatches per "
            "decode worker — excess arrivals queue in the router "
            "instead of flooding worker KV pools into PoolExhausted "
            "retry storms")
define_flag("fleet_prefill_slots", 4,
            "max concurrent prefill+export+migrate admissions per "
            "prefill worker; excess connections queue (backpressure "
            "through the wire) instead of racing the block pool")

_M_MIGRATIONS = _metrics.counter(
    "fleet_migrations_total",
    "KV migrations received and installed by decode workers")
_M_MIGRATE_DUP = _metrics.counter(
    "fleet_migration_dups_total",
    "migrations deduplicated by request id (hedge/retry replays)")
_M_MIGRATE_MS = _metrics.histogram(
    "fleet_migrate_ms", "prefill-side MigrateKV send -> ack")


class FleetRemoteError(RuntimeError):
    """A worker answered ok=false.  ``kind`` is the remote exception
    class name; ``retryable`` mirrors RetryPolicy's classification —
    transient serving states (draining, pool pressure, a torn
    migration whose request is intact) retry on another worker,
    validation errors surface."""

    _RETRYABLE = ("Draining", "PoolExhausted", "BufferLifetimeError",
                  "InjectedFault", "ConnectionError", "TimeoutError")

    def __init__(self, kind, message):
        super().__init__("%s: %s" % (kind, message))
        self.kind = str(kind)
        self.retryable = self.kind in self._RETRYABLE


class Draining(RuntimeError):
    """Worker is draining; admission refused (retryable elsewhere)."""


class PoolExhausted(RuntimeError):
    """Worker's block pool cannot hold the request right now."""


# -- codec --------------------------------------------------------------

def encode_call(obj):
    hj = json.dumps(obj).encode()
    return struct.pack("<I", len(hj)) + hj


def decode_call(view):
    view = memoryview(view)
    (hlen,) = struct.unpack("<I", view[:4])
    return json.loads(bytes(view[4:4 + hlen]).decode())


def encode_migrate(head, k_bytes, v_bytes):
    """MigrateKV payload parts (send each; receivers reassemble by the
    frame length)."""
    hj = json.dumps(head).encode()
    return [struct.pack("<I", len(hj)), hj, k_bytes, v_bytes]


# -- transports ---------------------------------------------------------

def _kv_head(spec):
    """A page spec (``GenerativeEngine.page_spec``) under the MigrateKV
    header's ``kv`` keys."""
    (n_layers, n_blocks, block_size, n_heads, head_dim), dtype = spec
    return {"n_blocks": n_blocks, "block_size": block_size,
            "n_layers": n_layers, "n_heads": n_heads,
            "head_dim": head_dim, "dtype": np.dtype(dtype).name}


def _kv_spec(kv):
    """The ``(shape, dtype)`` of the K and of the V pages a MigrateKV
    header's ``kv`` states."""
    return ((int(kv["n_layers"]), int(kv["n_blocks"]),
             int(kv["block_size"]), int(kv["n_heads"]),
             int(kv["head_dim"])), np.dtype(kv.get("dtype", "float32")))


def _recv_exact(sock, n):
    buf = np.empty(n, np.uint8)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed (%d of %d)" % (got, n))
        got += r
    return memoryview(buf)


class SocketTransport:
    """Blocking fastwire-framed calls to ``host:port`` addresses, one
    pooled connection per outstanding call (a blocking ``wait`` holds
    its connection; parallel calls to the same worker open more)."""

    def __init__(self, timeout=60.0):
        self._timeout = float(timeout)
        self._idle = {}
        self._lock = _san.make_lock("fleet.socket_transport")

    def _checkout(self, addr):
        with self._lock:
            conns = self._idle.get(addr)
            if conns:
                return conns.pop()
        host, port = addr.rsplit(":", 1)
        sock = socket.create_connection((host, int(port)),
                                        timeout=self._timeout)
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.sendall(MAGIC)
            if bytes(_recv_exact(sock, len(MAGIC))) != MAGIC:
                raise ConnectionError("%s is not a fastwire endpoint"
                                      % addr)
        except BaseException:
            sock.close()
            raise
        return sock

    def call(self, addr, method, payload, timeout=None):
        parts = payload if isinstance(payload, (list, tuple)) \
            else [payload]
        total = sum(len(p) for p in parts)
        sock = self._checkout(addr)
        try:
            sock.settimeout(timeout if timeout is not None
                            else self._timeout)
            sock.sendall(struct.pack("<BQ", method, total))
            for p in parts:
                sock.sendall(p)
            (ln,) = struct.unpack("<Q", _recv_exact(sock, 8))
            reply = bytes(_recv_exact(sock, ln))
        except BaseException:
            sock.close()
            raise
        with self._lock:
            self._idle.setdefault(addr, []).append(sock)
        return reply

    def close(self):
        with self._lock:
            conns = [c for lst in self._idle.values() for c in lst]
            self._idle.clear()
        for c in conns:
            try:
                c.close()
            except OSError:
                pass


class LocalTransport:
    """In-process transport for the --quick smoke: same byte codec,
    direct dispatch into the worker's handler, no sockets.  ``kill``
    simulates a worker death — the worker stops serving and every call
    to it (including one already blocked in ``wait``) raises
    ConnectionError, exactly what a SIGKILL'd TCP peer produces."""

    def __init__(self):
        self._workers = {}
        self._lock = _san.make_lock("fleet.local_transport")

    def register(self, worker):
        addr = "local:%s" % worker.name
        with self._lock:
            self._workers[addr] = worker
        return addr

    def kill(self, name):
        addr = "local:%s" % name
        with self._lock:
            worker = self._workers.get(addr)
        if worker is not None:
            worker.kill()

    def call(self, addr, method, payload, timeout=None):
        # the wire boundary: under the weaver this is where a frame
        # hand-off can interleave with the peer's other work
        _san.weaver_yield("fleet.wire.call")
        with self._lock:
            worker = self._workers.get(addr)
        if worker is None or worker.killed:
            raise ConnectionError("fleet worker %s is dead" % addr)
        if isinstance(payload, (list, tuple)):
            payload = b"".join(payload)
        return worker.handle(method, memoryview(payload))

    def close(self):
        pass


# -- the worker ---------------------------------------------------------

class FleetWorker:
    """One fleet member: a GenerativeEngine plus the fastwire-facing
    op surface.  ``role='prefill'`` serves the ``prefill`` op (prompt
    pass + MigrateKV push to a decode worker); ``role='decode'`` runs a
    DecodeLoop and serves ``generate`` (local prefill fallback),
    ``MigrateKV`` receive, and blocking ``wait``.  Both serve ``ping``
    / ``status`` / ``drain``."""

    def __init__(self, name, role, config, params, quant="",
                 kv_blocks=None, warm=True, transport=None,
                 call_timeout=60.0):
        if role not in ("prefill", "decode"):
            raise ValueError("role must be 'prefill'/'decode'")
        self.name = str(name)
        self.role = role
        self.transport = transport
        self._call_timeout = float(call_timeout)
        self.engine = GenerativeEngine(config, params, quant=quant,
                                       kv_blocks=kv_blocks,
                                       name="fleet-%s" % self.name,
                                       warm=False)
        if warm:
            self.engine.warm_role(role)
        self._draining = False
        self._killed = _san.make_event("fleet.worker.killed")
        self._futures = {}
        self._flock = _san.make_lock("fleet.worker.futures")
        # prefill admission bound: every conn thread past this count
        # queues on the semaphore, so concurrent prompts can never
        # race the block pool into exhaustion
        self._slots = threading.BoundedSemaphore(
            max(1, int(FLAGS.fleet_prefill_slots))) \
            if role == "prefill" else None
        if role == "decode":
            self._queue = RequestQueue()
            self._loop = DecodeLoop(self.engine, self._queue,
                                    label="fleet-%s" % self.name)
        else:
            self._queue = self._loop = None

    @property
    def killed(self):
        return self._killed.is_set()

    def kill(self):
        """Abrupt death (LocalTransport kill drill): stop serving and
        abandon in-flight work — futures stay unresolved, like a
        SIGKILL'd process."""
        self._killed.set()
        if self._loop is not None:
            self._loop.stop(join=False)

    def shutdown(self):
        """Orderly local teardown (after drain, or test cleanup)."""
        self._killed.set()
        if self._loop is not None:
            self._loop.stop()
        self.engine.close()

    # -- transport-facing dispatch -------------------------------------

    def handle(self, method, payload):
        """One fastwire frame in, one reply payload out.  Never raises
        for op-level errors — they travel as ok=false replies the
        router classifies; an unknown method raises (the endpoint
        closes the connection, fastwire's raw-v1 behavior)."""
        if method == M_MIGRATE:
            return self._handle_migrate(payload)
        if method == M_CALL:
            head = decode_call(payload)
            op = head.get("op")
            fn = getattr(self, "_op_%s" % op, None)
            if fn is None:
                return encode_call({"ok": False, "kind": "ValueError",
                                    "error": "unknown op %r" % (op,)})
            try:
                return encode_call(fn(head))
            except Exception as e:
                return encode_call({"ok": False,
                                    "kind": type(e).__name__,
                                    "error": str(e)})
        raise ValueError("unknown fleet method %d" % method)

    # -- control ops ---------------------------------------------------

    def _op_ping(self, head):
        return {"ok": True, "name": self.name, "role": self.role,
                "draining": self._draining}

    def _op_status(self, head):
        from paddle_tpu.observability import slo as _slo
        with self._flock:
            inflight = sum(1 for f in self._futures.values()
                           if not f.done())
        return {"ok": True, "name": self.name, "role": self.role,
                "draining": self._draining, "inflight": inflight,
                "kv_free": self.engine.pool.free_blocks,
                # counters live in THIS process — a subprocess fleet's
                # bench must sum them over status replies, not read its
                # own (necessarily zero) registry
                "counters": {
                    "migrations": _M_MIGRATIONS.value,
                    "migration_dups": _M_MIGRATE_DUP.value},
                # the BarrierStatus rider: active burn-rate alerts
                # travel on every status reply, same as the training
                # plane's barrier frames
                "slo_alerts": _slo.alerts_brief()}

    def _op_drain(self, head):
        """Graceful drain: stop admitting, finish the running decodes,
        then report done — the __main__ worker exits 0 on it."""
        self._draining = True
        deadline = time.monotonic() + float(head.get("timeout", 60.0))
        while time.monotonic() < deadline:
            with self._flock:
                busy = sum(1 for f in self._futures.values()
                           if not f.done())
            if not busy:
                return {"ok": True, "drained": True}
            time.sleep(0.02)
        return {"ok": False, "kind": "TimeoutError",
                "error": "drain timed out with requests in flight"}

    # -- prefill role --------------------------------------------------

    def _validate(self, prompt, max_new):
        cfg = self.engine.config
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > cfg.max_seq:
            raise ValueError("prompt length %d exceeds max_seq %d"
                             % (len(prompt), cfg.max_seq))
        if int(max_new) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        bad = [t for t in prompt if not 0 <= int(t) < cfg.vocab]
        if bad:
            raise ValueError("prompt token %d outside vocab [0, %d)"
                             % (bad[0], cfg.vocab))

    def _op_prefill(self, head):
        """The disaggregated prompt pass: prefill locally, export the
        KV pages, push them to the decode worker named in ``dest`` via
        MigrateKV, free the local blocks (migrated-away), and hand the
        first token back to the router."""
        if self.role != "prefill":
            raise ValueError("prefill op on a %s worker" % self.role)
        if self._draining:
            raise Draining("%s is draining" % self.name)
        req = head["req"]
        prompt = [int(t) for t in req["prompt"]]
        self._validate(prompt, req["max_new"])
        self._slots.acquire()        # bounded admission: see flag doc
        try:
            fault_point("fleet_prefill")
            seq = GenRequest(prompt, req["max_new"], req.get("eos"),
                             Future())
            blocks = self.engine.pool.alloc(
                self.engine.pool.blocks_for(len(prompt)))
            if blocks is None:
                raise PoolExhausted(
                    "%s: no blocks for a %d-token prompt"
                    % (self.name, len(prompt)))
            seq.blocks = blocks
            try:
                first = self.engine.prefill(seq)
                kp, vp, epoch = self.engine.export_blocks(blocks)
            finally:
                # migrated-away: the host export is the only live copy
                self.engine.free_sequence(seq)
            mhead = {"v": 1, "src": self.name, "epoch": int(epoch),
                     "req": {"id": req["id"], "prompt": prompt,
                             "first": int(first),
                             "max_new": int(req["max_new"]),
                             "eos": req.get("eos")},
                     "kv": _kv_head(self.engine.page_spec(len(blocks)))}
            k_bytes, v_bytes = kp.tobytes(), vp.tobytes()
            migrate_error = dest_reply = None
            t0 = time.perf_counter()
            try:
                fault_point("fleet_migrate")
                try:
                    fault_point("fleet_migrate_tear")
                except InjectedFault:
                    # the crash-lab tear: full-size header, page body
                    # cut mid-payload — the receiver must roll back
                    # and name it
                    v_bytes = v_bytes[:len(v_bytes) // 2]
                reply = self.transport.call(
                    head["dest"], M_MIGRATE,
                    encode_migrate(mhead, k_bytes, v_bytes),
                    timeout=self._call_timeout)
                dest_reply = decode_call(reply)
                if not dest_reply.get("ok"):
                    migrate_error = dest_reply
            except Exception as e:
                migrate_error = {"kind": type(e).__name__,
                                 "error": str(e)}
            _M_MIGRATE_MS.observe((time.perf_counter() - t0) * 1e3)
        finally:
            self._slots.release()
        return {"ok": True, "first": int(first), "epoch": int(epoch),
                "migrated": migrate_error is None,
                "dest_epoch": (dest_reply or {}).get("epoch"),
                "dup": bool((dest_reply or {}).get("dup")),
                "migrate_error": migrate_error}

    # -- decode role ---------------------------------------------------

    def _register(self, rid):
        """Reserve ``rid``'s future (exactly-once admission); None when
        it already exists (hedge/retry replay)."""
        with self._flock:
            if rid in self._futures:
                return None
            fut = Future()
            self._futures[rid] = fut
            return fut

    def _op_generate(self, head):
        """Local-prefill fallback / re-prefill path: the whole request
        runs on this decode worker (greedy decode regenerates the same
        tokens a migrated run would have produced)."""
        if self.role != "decode":
            raise ValueError("generate op on a %s worker" % self.role)
        if self._draining:
            raise Draining("%s is draining" % self.name)
        req = head["req"]
        prompt = [int(t) for t in req["prompt"]]
        self._validate(prompt, req["max_new"])
        fut = self._register(req["id"])
        if fut is None:
            return {"ok": True, "dup": True}
        self._queue.put(GenRequest(prompt, req["max_new"],
                                   req.get("eos"), fut))
        return {"ok": True, "dup": False}

    def _op_wait(self, head):
        """Block until ``id`` finishes (or ``timeout``); the router
        calls this on its own pooled connection per attempt."""
        rid = head["id"]
        deadline = time.monotonic() + float(head.get("timeout", 60.0))
        with self._flock:
            fut = self._futures.get(rid)
        if fut is None:
            raise KeyError("unknown request id %r" % (rid,))
        # event-based wait: hundreds of outstanding waits must not
        # spin-poll a saturated core out from under the decode loop
        done = _san.make_event("fleet.worker.wait")
        fut.add_done_callback(lambda _f: done.set())
        while True:
            if fut.done():
                err = fut.exception()
                if err is not None:
                    raise err
                return {"ok": True, "done": True,
                        "result": fut.result()}
            if self._killed.is_set():
                raise ConnectionError("worker killed")
            now = time.monotonic()
            if now >= deadline:
                return {"ok": True, "done": False}
            done.wait(timeout=min(0.25, deadline - now))

    def _handle_migrate(self, payload):
        """MigrateKV receive: allocate destination blocks, install the
        pages under the epoch guard, admit the request into the decode
        loop.  A payload shorter than the header's block table is a
        TORN migration: the half-received destination blocks are freed
        (rollback) and the failure is a named BufferLifetimeError —
        never pages of garbage served as context."""
        try:
            view = memoryview(payload)
            (hlen,) = struct.unpack("<I", view[:4])
            head = json.loads(bytes(view[4:4 + hlen]).decode())
            if self.role != "decode":
                raise ValueError("MigrateKV sent to a %s worker"
                                 % self.role)
            if self._draining:
                raise Draining("%s is draining" % self.name)
            req = head["req"]
            rid = req["id"]
            kv = head["kv"]
            shape, dtype = _kv_spec(kv)
            n_blocks = shape[1]
            want, want_dtype = self.engine.page_spec(n_blocks)
            if shape != tuple(want) or dtype != np.dtype(want_dtype):
                raise ValueError("migration geometry %r does not match "
                                 "this worker's engine" % (kv,))
            with self._flock:
                if rid in self._futures:
                    _M_MIGRATE_DUP.inc()
                    return encode_call({"ok": True, "dup": True})
            page_bytes = int(np.prod(shape, dtype=np.int64)) \
                * dtype.itemsize
            blocks = self.engine.pool.alloc(n_blocks)
            if blocks is None:
                raise PoolExhausted("%s: no room for %d migrated blocks"
                                    % (self.name, n_blocks))
            try:
                off = 4 + hlen
                body = len(view) - off
                if body != 2 * page_bytes:
                    rollback, blocks = blocks, None
                    self.engine.pool.free(rollback)
                    _san.trip(
                        "kv_migration:%s" % rid, op="migrate_in",
                        site="%s: page body %d B != 2x%d B from the "
                             "block-table header (torn mid-payload; "
                             "%d dest blocks rolled back)"
                             % (self.name, body, page_bytes,
                                len(rollback)),
                        epoch=head.get("epoch"))
                k = np.frombuffer(view[off:off + page_bytes],
                                  dtype).reshape(shape)
                v = np.frombuffer(view[off + page_bytes:
                                       off + 2 * page_bytes],
                                  dtype).reshape(shape)
                dest_epoch = self.engine.import_blocks(blocks, k, v)
            except BaseException:
                if blocks is not None:
                    self.engine.pool.free(blocks)
                raise
            fut = self._register(rid)
            if fut is None:                  # a replay raced us in
                self.engine.pool.free(blocks)
                _M_MIGRATE_DUP.inc()
                return encode_call({"ok": True, "dup": True})
            gr = GenRequest(req["prompt"], req["max_new"],
                            req.get("eos"), fut)
            gr.blocks = list(blocks)
            gr.context_len = len(gr.prompt)
            gr.out = [int(req["first"])]
            gr.t_first = gr.t_last = time.perf_counter()
            self._queue.put(gr)
            _M_MIGRATIONS.inc()
            return encode_call({"ok": True, "dup": False,
                                "blocks": [int(b) for b in blocks],
                                "epoch": int(dest_epoch)})
        except Exception as e:
            return encode_call({"ok": False, "kind": type(e).__name__,
                                "error": str(e)})


# -- socket endpoint ----------------------------------------------------

class FleetEndpoint:
    """Accept loop + one thread per connection, serving MigrateKV and
    FleetCall frames into a FleetWorker (wire.PredictEndpoint's
    plumbing on the fleet methods).  Each connection is sequential —
    the router's transport opens one per outstanding call."""

    def __init__(self, worker, host="127.0.0.1", port=0):
        self._worker = worker
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, int(port)))
        self._sock.listen(256)
        self.host, self.port = self._sock.getsockname()[:2]
        self._stop = _san.make_event("fleet.server.stop")
        self._thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name="fleet-endpoint-%s" % worker.name)
        self._thread.start()

    @property
    def addr(self):
        return "%s:%d" % (self.host, self.port)

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn):
        try:
            if bytes(_recv_exact(conn, len(MAGIC))) != MAGIC:
                return
            conn.sendall(MAGIC)
            while not self._stop.is_set():
                try:
                    head = _recv_exact(conn, 9)
                except ConnectionError:
                    return
                method, ln = struct.unpack("<BQ", head)
                payload = _recv_exact(conn, ln)
                try:
                    reply = self._worker.handle(method, payload)
                except ValueError:
                    return          # unknown method: raw-v1 close
                conn.sendall(struct.pack("<Q", len(reply)))
                conn.sendall(reply)
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    def stop(self):
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass


# -- subprocess worker entrypoint ---------------------------------------

def _env_int(name, default):
    return int(os.environ.get(name, default))


def worker_main(argv=None):
    """``python -m paddle_tpu.serving.fleet --role decode --name d0``:
    build the bench-family model (FLEETW_* env dims, serve_bench's
    knobs), bind a FleetEndpoint, print the READY line the spawner
    parses, and serve until drained (exit 0) or killed.  Model dims
    must match across the whole fleet — MigrateKV checks geometry, not
    weights (same-checkpoint deployment is an operator invariant, as
    everywhere else in serving)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", required=True,
                    choices=("prefill", "decode"))
    ap.add_argument("--name", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--kv-blocks", type=int,
                    default=_env_int("FLEETW_KV_BLOCKS", 96))
    ap.add_argument("--max-batch", type=int,
                    default=_env_int("FLEETW_MAX_BATCH", 8))
    ap.add_argument("--quant", default="")
    args = ap.parse_args(argv)
    if _env_int("FLEETW_SCHED_BATCH", 0) and hasattr(os,
                                                     "SCHED_BATCH"):
        # co-located fleets time-slice one another; SCHED_BATCH's
        # longer quanta keep each decode step's working set in cache
        # instead of re-faulting it every preemption
        try:
            os.sched_setscheduler(0, os.SCHED_BATCH,
                                  os.sched_param(0))
        except OSError:
            pass
    cfg, params = tiny_lm(
        _env_int("FLEETW_SEED", 3),
        vocab=_env_int("FLEETW_VOCAB", 64),
        d_model=_env_int("FLEETW_DMODEL", 128),
        n_heads=_env_int("FLEETW_HEADS", 4),
        n_layers=_env_int("FLEETW_LAYERS", 3),
        d_ff=_env_int("FLEETW_DFF", 256),
        block_size=_env_int("FLEETW_BLOCK", 16),
        max_blocks=_env_int("FLEETW_MAX_BLOCKS", 8),
        max_batch=args.max_batch)
    transport = SocketTransport()
    worker = FleetWorker(args.name, args.role, cfg, params,
                         quant=args.quant, kv_blocks=args.kv_blocks,
                         transport=transport)
    endpoint = FleetEndpoint(worker, host=args.host, port=args.port)
    print("FLEET_READY name=%s role=%s port=%d pid=%d"
          % (args.name, args.role, endpoint.port, os.getpid()),
          flush=True)
    signal.signal(signal.SIGTERM, lambda *a: worker._killed.set())
    try:
        while not (worker._draining or worker._killed.is_set()):
            time.sleep(0.05)
        if worker._draining:
            # drain already waited for in-flight work in _op_drain;
            # give the reply a beat to flush, then leave cleanly
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    endpoint.stop()
    return 0


if __name__ == "__main__":
    sys.exit(worker_main())
