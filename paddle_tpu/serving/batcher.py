"""Continuous/dynamic batcher: per-tenant queue + dispatcher thread.

Orca-style continuous batching under a Clipper-style latency deadline,
TPU-native (Yu et al. OSDI '22; Crankshaw et al. NSDI '17): requests
land in a queue with their arrival stamp; one dispatcher thread per
tenant assembles batches and launches them on the tenant's bucket
executables.  The invariants:

- the device never idles while requests wait: the dispatcher drains
  whatever queued up while the previous dispatch ran and launches
  immediately (those requests' deadlines — anchored at ARRIVAL —
  already expired);
- a batch is never held for fullness: with the device free, assembly
  waits at most ``FLAGS_serve_max_wait_us`` past the first request's
  arrival, then launches the partial batch;
- a batch never mixes engines: the dispatcher snapshots the tenant's
  engine route once per batch, which is what makes hot swap
  (server.swap) atomic — queued requests simply dispatch on whichever
  engine is routed when their batch launches, none dropped, none torn.

Assembly pads the concatenated rows up to the chosen bucket with
zeros; the padded rows are computed and discarded (the bucket-padding
contract, MIGRATION.md).
"""
from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from paddle_tpu.core import sanitizer as _san
from paddle_tpu.distributed.resilience import fault_point
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.trace import TRACER

__all__ = ["Request", "RequestQueue", "Dispatcher", "TokenScheduler"]

_M_REQS = _metrics.counter("serve_requests_total",
                           "requests accepted by the serving tier")
_M_BATCHES = _metrics.counter("serve_batches_total",
                              "batches dispatched")
_M_PAD = _metrics.counter("serve_padding_rows_total",
                          "padding rows computed and discarded")
_M_OCC = _metrics.histogram(
    "serve_batch_occupancy", "real rows per dispatched batch",
    bounds=(1, 2, 4, 8, 16, 32, 64, 128, 256))
_M_QWAIT = _metrics.histogram("serve_queue_wait_ms",
                              "request arrival -> batch launch")
_M_ASSEMBLE = _metrics.histogram("serve_batch_assemble_ms",
                                 "feed concatenation + padding")
_M_DISPATCH = _metrics.histogram("serve_dispatch_ms",
                                 "bucket executable call")
_M_REQ_MS = _metrics.histogram("serve_request_ms",
                               "request arrival -> result ready")

# the telemetry_overhead.py serving gate A/Bs the per-request metric
# observations through this switch; leave it alone in production —
# metrics are meant to stay always-on
_METRICS_ON = True


def set_metrics_enabled(on):
    global _METRICS_ON
    prev = _METRICS_ON
    _METRICS_ON = bool(on)
    return prev


def metrics_probe(iters):
    """Execute the COMPLETE per-request metric op set once per
    iteration — every operation ``_METRICS_ON`` gates for a request
    that forms its own batch (the single-request worst case: the
    per-batch ops are not amortized across neighbours).  The
    telemetry_overhead.py serving gate micro-times this to get the
    deterministic metrics-on minus metrics-off delta; a wall-clock A/B
    at single-request scale is ~4 µs of signal under ±80 µs of
    scheduler noise (same reasoning as trace.disabled_step_probe)."""
    for _ in range(iters):
        # submit-side
        _M_REQS.inc()
        # launch-side, occupancy-1 batch
        _M_BATCHES.inc()
        _M_OCC.observe(1)
        _M_PAD.inc(0)
        _M_ASSEMBLE.observe(0.01)
        _M_DISPATCH.observe(0.4)
        _M_QWAIT.observe(0.1)
        # completion-side
        _M_REQ_MS.observe(0.5)


class Request:
    __slots__ = ("feed", "rows", "future", "t_arrival")

    def __init__(self, feed, rows, future):
        self.feed = feed
        self.rows = rows
        self.future = future
        self.t_arrival = time.perf_counter()


class RequestQueue:
    """Deque + condition: FIFO puts, timed gets, and put_front so the
    dispatcher can requeue the tail of a batch that outgrew its
    bucket without reordering it behind newer arrivals."""

    def __init__(self):
        self._q = deque()
        self._cv = _san.make_condition("batcher.queue")
        self._closed = False

    def put(self, item):
        _san.weaver_yield("batcher.queue.put")
        with self._cv:
            if self._closed:
                raise RuntimeError("queue closed")
            self._q.append(item)
            self._cv.notify()

    def put_front(self, items):
        with self._cv:
            for item in reversed(items):
                self._q.appendleft(item)
            self._cv.notify()

    def get(self, timeout=None):
        """Next request, or None on timeout / close-with-empty-queue."""
        _san.weaver_yield("batcher.queue.get")
        with self._cv:
            if not self._q:
                self._cv.wait_for(lambda: self._q or self._closed,
                                  timeout)
            if self._q:
                return self._q.popleft()
            return None

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    @property
    def closed(self):
        with self._cv:
            return self._closed and not self._q

    def __len__(self):
        with self._cv:
            return len(self._q)


class Dispatcher:
    """One per tenant.  ``engine_ref()`` returns the CURRENT engine
    (the tenant's atomically-swappable route); ``max_wait_us`` is read
    per batch so a runtime flag flip takes effect immediately."""

    def __init__(self, queue, engine_ref, max_wait_us=None, label=""):
        self.queue = queue
        self.engine_ref = engine_ref
        self.max_wait_us = max_wait_us
        self.label = label
        self._stop = _san.make_event("batcher.dispatch.stop")
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name="serve-dispatch-%s" % (label or id(self)))
        self._thread.start()

    def stop(self, join=True):
        self._stop.set()
        self.queue.close()
        if join:
            self._thread.join(timeout=30)

    def _wait_us(self):
        if self.max_wait_us is not None:
            return float(self.max_wait_us)
        from paddle_tpu.core.flags import FLAGS
        return float(FLAGS.serve_max_wait_us)

    # -- the continuous-batching loop ---------------------------------
    def _loop(self):
        while True:
            req = self.queue.get(timeout=0.25)
            if req is None:
                if self._stop.is_set() and self.queue.closed:
                    return
                continue
            engine = self.engine_ref()
            batch, rows = [req], req.rows
            deadline = req.t_arrival + self._wait_us() / 1e6
            while rows < engine.max_batch:
                remaining = deadline - time.perf_counter()
                nxt = self.queue.get(timeout=max(0.0, remaining)) \
                    if remaining > 0 else self.queue.get(timeout=0)
                if nxt is None:
                    break
                if rows + nxt.rows > engine.max_batch:
                    self.queue.put_front([nxt])
                    break
                batch.append(nxt)
                rows += nxt.rows
            # the dispatcher thread must survive ANYTHING — a dead
            # dispatcher wedges the tenant forever with unresolved
            # futures and no error anywhere
            try:
                self._launch(engine, batch, rows)
            except Exception as e:
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(e)

    def _launch(self, engine, batch, rows):
        if len(batch) == 1 and batch[0].rows > engine.max_batch:
            # validated against a pre-swap engine whose ladder was
            # taller: no bucket of THIS engine will ever fit it
            batch[0].future.set_exception(ValueError(
                "request batch %d exceeds the routed engine's "
                "serve_max_batch %d (shrunk by a hot swap) — split it "
                "client-side" % (batch[0].rows, engine.max_batch)))
            return
        bucket, missed = engine.pick_bucket(rows)
        if missed is not None:
            engine.ensure_bucket_async(missed)
        if bucket < rows:
            # every warm bucket is smaller than the batch: dispatch the
            # prefix that fits, requeue the tail AT THE FRONT (it keeps
            # its arrival stamps — its deadline has long expired, so it
            # ships on the very next loop turn)
            head, acc = [], 0
            while batch and acc + batch[0].rows <= bucket:
                acc += batch[0].rows
                head.append(batch.pop(0))
            if not head:
                # single request wider than any warm bucket: wait for
                # the ideal bucket to land rather than failing the
                # request (engine.validate capped rows <= max_batch, so
                # the ladder top always fits it)
                self._await_bucket(engine, batch)
                return
            self.queue.put_front(batch)
            batch, rows = head, acc
        t0 = time.perf_counter()
        span = TRACER.span("serve.batch",
                           args={"bucket": bucket, "rows": rows,
                                 "model": engine.name})
        try:
            with span:
                with TRACER.span("serve.assemble"):
                    feed = self._assemble(engine, batch, bucket, rows)
                t1 = time.perf_counter()
                exe = engine.executable(bucket)
                # fault-lab hook (ISSUE 13): the 'serve_dispatch'
                # point lets tools/fault_matrix.py's slo preset inject
                # a latency fault into the serving data plane and
                # assert the burn-rate alert + flight dump fire.
                # No-op (one empty-tuple check) without FLAGS_fault_spec
                fault_point("serve_dispatch")
                with TRACER.span("serve.dispatch"):
                    outs = exe.run(feed)
                    outs = [np.asarray(o) for o in outs]
                t2 = time.perf_counter()
            self._complete(engine, batch, outs)
        except Exception as e:
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        if _METRICS_ON:
            _M_BATCHES.inc()
            _M_OCC.observe(rows)
            _M_PAD.inc(bucket - rows)
            _M_ASSEMBLE.observe((t1 - t0) * 1e3)
            _M_DISPATCH.observe((t2 - t1) * 1e3)
            for r in batch:
                _M_QWAIT.observe((t0 - r.t_arrival) * 1e3)

    def _await_bucket(self, engine, batch):
        """Block (bounded) until the background compile for a bucket
        fitting ``batch`` lands, then launch.  Rare path: only reached
        when warm_buckets was restricted below a request's own width."""
        rows = sum(r.rows for r in batch)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 120.0:
            bucket, missed = engine.pick_bucket(rows)
            if missed is not None:
                engine.ensure_bucket_async(missed)
            if bucket >= rows:
                self._launch(engine, batch, rows)
                return
            if missed is not None:
                fail = engine.compile_error(missed)
                if fail is not None:
                    err = RuntimeError(
                        "bucket %d compile failed (%s) and no warm "
                        "bucket fits %d rows" % (missed, fail, rows))
                    for r in batch:
                        r.future.set_exception(err)
                    return
            time.sleep(0.005)
        err = TimeoutError("no bucket >= %d rows became warm" % rows)
        for r in batch:
            r.future.set_exception(err)

    @staticmethod
    def _assemble(engine, batch, bucket, rows):
        feed = {}
        for n, (sshape, sdtype) in engine.sample_specs.items():
            parts = [np.asarray(r.feed[n]) for r in batch]
            if bucket > rows:
                parts.append(np.zeros((bucket - rows,) + sshape,
                                      sdtype))
            feed[n] = parts[0] if len(parts) == 1 \
                else np.concatenate(parts, axis=0)
        return feed

    def _complete(self, engine, batch, outs):
        t_done = time.perf_counter()
        off = 0
        for r in batch:
            res = {name: np.array(o[off:off + r.rows])
                   for name, o in zip(engine.fetch_names, outs)}
            off += r.rows
            r.future.set_result(res)
            if _METRICS_ON:
                _M_REQ_MS.observe((t_done - r.t_arrival) * 1e3)


# ---------------------------------------------------------------------------
# Token-granular scheduling (ISSUE 11): the batcher above coalesces
# whole REQUESTS per dispatch; generative decode coalesces per TOKEN —
# every decode iteration re-decides the batch, admitting new prefills
# into the running set the moment blocks exist for them (Orca
# iteration-level scheduling, for real this time).
# ---------------------------------------------------------------------------

class TokenScheduler:
    """Admission + preemption policy over a kv_cache.BlockPool.

    Pure policy, no dispatch mechanics (generative.DecodeLoop owns the
    loop): sequences are duck-typed — the scheduler reads
    ``seq.prompt`` (token list) and owns ``seq.blocks`` (allocated
    block ids).  Invariants:

    - admission is FIFO and stops at the first request the pool cannot
      hold whole (counted in serve_kv_alloc_failures_total; the request
      stays at the queue front so arrival order survives — no
      starvation of big prompts by small ones);
    - a running sequence that cannot grow (mid-decode block boundary
      with an empty pool) preempts the YOUNGEST running sequence:
      recompute-style eviction — blocks freed, request requeued at the
      front, its greedy tokens regenerate bit-identically on
      re-admission (determinism is pinned by test);
    - the victim is never an older sequence (oldest-first completion
      keeps head-of-line latency bounded), and a lone sequence that
      cannot grow out of an EMPTY pool is a configuration error
      surfaced to the caller, not an infinite preempt-readmit loop;
    - where the pool keeps state slots (a model with per-sequence
      state, kv_cache.BlockPool), admission takes the sequence's slot
      before its blocks and hands it back if the blocks fail: a request
      is never admitted with one kind of state and not the other;
    - with a prefix index attached (ISSUE 19,
      generative.PrefixCache), admission takes the PARTIALLY-CACHED
      branch: the index shares the prompt's already-resident prefix
      blocks by refcount and allocates only the suffix, so the pool
      bar for a mostly-cached prompt is its few fresh blocks — a
      cache-hit prompt admits under pressure that would requeue a cold
      one.  The suffix-only prefill that completes the contract is the
      engine's (``seq.cached_len`` carries the boundary).
    """

    def __init__(self, pool, max_batch, prefix_cache=None):
        self.pool = pool
        self.max_batch = int(max_batch)
        self.prefix_cache = prefix_cache

    def try_admit(self, queue, n_running):
        """Pop and return the requests admissible RIGHT NOW (their
        prompt blocks are allocated on return, as ``req.blocks``)."""
        admitted = []
        while n_running + len(admitted) < self.max_batch:
            req = queue.get(timeout=0)
            if req is None:
                break
            if req.blocks:
                # migrated-in (serving/fleet.py MigrateKV): the pages
                # already landed in blocks allocated by the receive
                # path — admission is just batch membership, a second
                # alloc here would leak the originals
                admitted.append(req)
                continue
            if self.prefix_cache is not None:
                if not self.prefix_cache.acquire(req):
                    queue.put_front([req])  # keeps its arrival stamp
                    break
                admitted.append(req)
                continue
            slot = 0
            if self.pool.state_slots:
                # a model with per-sequence state: the sequence holds
                # one slot from here to finish or preemption
                slot = self.pool.take_slot()
                if slot is None:
                    queue.put_front([req])
                    break
            blocks = self.pool.alloc(self.pool.blocks_for(
                len(req.prompt)))
            if blocks is None:
                if slot:
                    self.pool.return_slot(slot)
                queue.put_front([req])      # keeps its arrival stamp
                break
            req.blocks = blocks
            if slot:
                req.slot = slot
            admitted.append(req)
        return admitted

    def grow(self, seq):
        """One more block for ``seq`` (decode crossed a block
        boundary); True on success."""
        got = self.pool.alloc(1)
        if got is None:
            return False
        seq.blocks.extend(got)
        return True

    def pick_victim(self, running, needing):
        """The sequence to preempt so ``needing`` can grow: the
        youngest running sequence other than ``needing`` — or
        ``needing`` itself when it IS the youngest (evicting an older
        peer for the youngest would invert completion order).  None
        when there is nothing to evict (lone sequence, empty pool)."""
        candidates = [s for s in running if s is not needing]
        if not candidates:
            return None
        victim = candidates[-1]
        # never steal from an OLDER sequence for a younger one
        if running.index(victim) < running.index(needing):
            return needing
        return victim

