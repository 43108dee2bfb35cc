"""Multi-tenant inference server on the AOT/prepared path.

One InferenceServer multiplexes any number of loaded models (tenants)
in one process.  Each tenant owns a ModelEngine (parameter scope
device-resident via the AotExecutable staging — the PR 2 Scope/prepared
machinery), a request queue, and a continuous-batching dispatcher
thread (batcher.py).  The request plane:

- in-process: ``submit(model, feed) -> Future`` / ``predict`` (the
  blocking convenience) — the API the C entry points (capi) route
  through;
- socket: ``start_endpoint(port)`` serves the fastwire-framed Predict
  method (wire.py) for out-of-process clients.

Hot swap: ``swap(model, new_dir)`` builds the new engine IN SHADOW
(fresh scope, params loaded, warm buckets compiled) and then atomically
flips the tenant's route pointer.  In-flight and queued requests are
never dropped or torn: a batch snapshots the route once, so every
request is served whole by exactly one engine version.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future

import numpy as np

from paddle_tpu.core import sanitizer as _san
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.observability import metrics as _metrics

from . import batcher as _batcher
from .batcher import Dispatcher, Request, RequestQueue
from .engine import ModelEngine

__all__ = ["InferenceServer"]

_M_MODELS = _metrics.gauge("serve_models", "tenants currently loaded")
_M_SWAPS = _metrics.counter("serve_swaps_total",
                            "hot model swaps completed")


def _tenant_metrics(name):
    """Per-tenant SLO tagging (ISSUE 13): every tenant gets its own
    always-on request-latency histogram and error counter, named
    ``serve_request_ms_<tenant>`` / ``serve_request_errors_total_
    <tenant>`` — the series a per-tenant latency/drop SLO
    (``serve_request_ms_<tenant>.p99 <= budget``) evaluates from the
    tsdb.  Registered once at tenant creation (registry lookups never
    ride the request path)."""
    safe = "".join(c if c.isalnum() or c == "_" else "_"
                   for c in str(name))
    return (_metrics.histogram(
                "serve_request_ms_" + safe,
                "end-to-end request latency, tenant %r" % name),
            _metrics.counter(
                "serve_request_errors_total_" + safe,
                "requests failed/dropped, tenant %r" % name))


class _Tenant:
    __slots__ = ("name", "engine", "queue", "dispatcher", "m_lat",
                 "m_err")

    def __init__(self, name, engine, max_wait_us):
        self.name = name
        self.engine = engine     # the atomically-swappable route
        self.queue = RequestQueue()
        self.dispatcher = Dispatcher(self.queue, lambda: self.engine,
                                     max_wait_us=max_wait_us,
                                     label=name)
        self.m_lat, self.m_err = _tenant_metrics(name)


class _GenTenant:
    """A generative (token-level) tenant: GenerativeEngine + its
    DecodeLoop (serving/generative.py) instead of the request-granular
    Dispatcher — requests are admitted per ITERATION, not per batch."""

    __slots__ = ("name", "engine", "queue", "dispatcher", "m_lat",
                 "m_err")

    def __init__(self, name, engine):
        from .generative import DecodeLoop

        self.name = name
        self.engine = engine
        self.queue = RequestQueue()
        self.dispatcher = DecodeLoop(engine, self.queue, label=name)
        self.m_lat, self.m_err = _tenant_metrics(name)


class InferenceServer:
    """``load`` tenants, ``submit``/``predict`` requests, ``swap``
    checkpoints, ``start_endpoint`` for socket clients."""

    def __init__(self, place=None, max_batch=None, max_wait_us=None):
        self.place = place
        self.max_batch = max_batch
        self.max_wait_us = max_wait_us
        self._tenants = {}
        self._lock = _san.make_lock("serve.server.tenants")
        self._endpoint = None
        self._closed = False
        # Watchtower (ISSUE 13): a serving process with FLAGS_tsdb_dir
        # set retains its request/latency history and arms the SLO
        # evaluator (per-tenant p99/drop SLOs).  No-op without the
        # flag.
        try:
            from paddle_tpu.observability import tsdb as _tsdb
            _tsdb.ensure_sampler()
        except Exception:
            pass

    # -- tenants -------------------------------------------------------
    def load(self, name, model_dir, warm=None):
        """Load ``model_dir`` as tenant ``name`` (its bucket ladder is
        compiled per ``warm`` / FLAGS_serve_warm_buckets before the
        first request is accepted)."""
        self._check_loadable(name)   # reject BEFORE the warm compiles
        engine = ModelEngine(model_dir, place=self.place,
                             max_batch=self.max_batch, warm=warm,
                             name=name)
        with self._lock:
            self._check_loadable(name, locked=True)
            self._tenants[name] = _Tenant(name, engine,
                                          self.max_wait_us)
            _M_MODELS.set(len(self._tenants))
        return engine

    def _check_loadable(self, name, locked=False):
        """Fail a doomed load cheaply — building an engine compiles
        the whole warm ladder, seconds of work.  Re-checked under the
        lock at insert (a concurrent load of the same name can still
        win the race; the loser raises after its build)."""
        if not locked:
            with self._lock:
                return self._check_loadable(name, locked=True)
        if self._closed:
            raise RuntimeError("server closed")
        if name in self._tenants:
            raise ValueError("tenant %r already loaded (use swap)"
                             % name)

    def swap(self, name, model_dir, warm=None):
        """Hot-swap tenant ``name`` to the model in ``model_dir`` (a
        fresh training checkpoint export).  The new engine is built and
        warmed in shadow; the route flip is one reference assignment —
        zero dropped, zero torn requests."""
        with self._lock:
            if self._closed:
                raise RuntimeError("server closed")
        tenant = self._tenant(name)
        if isinstance(tenant, _GenTenant):
            raise TypeError("tenant %r is generative — hot swap serves "
                            "the predict tier; reload the generative "
                            "tenant instead" % (name,))
        shadow = ModelEngine(model_dir, place=self.place,
                             max_batch=self.max_batch, warm=warm,
                             name=name)
        tenant.engine = shadow    # the atomic flip
        _M_SWAPS.inc()
        return shadow

    def load_generative(self, name, config, params, quant="",
                        kv_blocks=None, warm=True, prefix_cache=None,
                        spec_k=None, draft=None):
        """Load a generative (autoregressive decode) tenant: a
        GenerativeEngine built from ``(config, params)`` — e.g.
        ``lm.tiny_lm`` output — with int8 weight quantization
        gated per tenant via ``quant='int8'``.  Requests go through
        ``generate()``; the tenant runs token-level continuous batching
        (serving/generative.py), not the predict dispatcher.

        ISSUE 19 knobs (default to FLAGS_serve_prefix_cache /
        FLAGS_serve_spec_k): ``prefix_cache=True`` turns on
        copy-on-write prefix KV reuse for this tenant; ``spec_k > 0``
        turns on speculative decoding, which REQUIRES
        ``draft=(config, params)`` — a small LM with the same vocab
        and paging geometry, load-time state like the target's own
        weights (there is no hot-swap path for the draft)."""
        from .generative import GenerativeEngine

        self._check_loadable(name)
        engine = GenerativeEngine(config, params, quant=quant,
                                  kv_blocks=kv_blocks, name=name,
                                  place=self.place, warm=warm,
                                  prefix_cache=prefix_cache,
                                  spec_k=spec_k, draft=draft)
        try:
            with self._lock:
                self._check_loadable(name, locked=True)
                self._tenants[name] = _GenTenant(name, engine)
                _M_MODELS.set(len(self._tenants))
        except Exception:
            engine.close()
            raise
        return engine

    def unload(self, name):
        with self._lock:
            tenant = self._tenants.pop(name, None)
            _M_MODELS.set(len(self._tenants))
        if tenant is not None:
            tenant.dispatcher.stop()
            if isinstance(tenant, _GenTenant):
                tenant.engine.close()

    def _tenant(self, name):
        with self._lock:
            tenant = self._tenants.get(name)
        if tenant is None:
            raise KeyError("unknown model %r (loaded: %r)"
                           % (name, sorted(self._tenants)))
        return tenant

    def models(self):
        with self._lock:
            return sorted(self._tenants)

    def engine(self, name):
        return self._tenant(name).engine

    # -- request plane -------------------------------------------------
    def submit(self, name, feed):
        """Enqueue one request; returns a Future resolving to
        {fetch_name: ndarray} with the request's own batch dim."""
        tenant = self._tenant(name)
        if isinstance(tenant, _GenTenant):
            raise TypeError("tenant %r is generative — use generate(), "
                            "not submit/predict" % (name,))
        feed = {k: np.asarray(v) for k, v in feed.items()}
        try:
            rows = tenant.engine.validate(feed)
        except Exception:
            # a rejected request is a per-tenant drop too — the drop
            # SLO must see admission failures, not just batch failures
            if _batcher._METRICS_ON:
                tenant.m_err.inc()
            raise
        fut = Future()
        if _batcher._METRICS_ON:
            _batcher._M_REQS.inc()
            self._tag_tenant(tenant, fut)
        tenant.queue.put(Request(feed, rows, fut))
        return fut

    @staticmethod
    def _tag_tenant(tenant, fut):
        """Per-tenant SLO tagging: observe this request's end-to-end
        latency (success) or error/drop (exception) into the tenant's
        own metrics when the future resolves — every completion path
        (dispatch, validation inside the batch, dispatcher failure,
        wire) funnels through the future, so nothing is missed."""
        import time as _time

        t0 = _time.perf_counter()

        def _done(f):
            try:
                failed = f.exception() is not None
            except Exception:   # cancelled: that is a drop
                failed = True
            if failed:
                tenant.m_err.inc()
            else:
                tenant.m_lat.observe((_time.perf_counter() - t0) * 1e3)
        fut.add_done_callback(_done)

    def predict(self, name, feed, timeout=None):
        return self.submit(name, feed).result(timeout)

    def generate(self, name, prompt, max_new_tokens, eos_id=None):
        """Enqueue one generate request against a generative tenant;
        returns a Future resolving to ``{"tokens": [...], "ttft_ms":
        float, "itl_ms": [...], "preempted": int}``.  Greedy decode;
        the request joins the tenant's running decode batch at the next
        iteration the block pool can hold its prompt."""
        from . import generative as _gen
        from .generative import GenRequest

        tenant = self._tenant(name)
        if not isinstance(tenant, _GenTenant):
            raise TypeError("tenant %r is a predict model — generate() "
                            "needs a load_generative tenant" % (name,))
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max(prompt) >= tenant.engine.config.vocab or min(prompt) < 0:
            raise ValueError("prompt token out of range [0, %d)"
                             % tenant.engine.config.vocab)
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # reject HERE, not in the decode loop (MIGRATION.md contract):
        # an unadmittable request would otherwise wedge the tenant —
        # admission is FIFO and stops at the first request that does
        # not fit, so a prompt that can NEVER fit blocks all behind it
        cfg = tenant.engine.config
        if len(prompt) > cfg.max_seq:
            raise ValueError(
                "prompt length %d exceeds max_seq %d (block_size x "
                "max_blocks)" % (len(prompt), cfg.max_seq))
        pool = tenant.engine.pool
        if pool.blocks_for(len(prompt)) > pool.capacity:
            raise ValueError(
                "prompt needs %d KV blocks but the tenant's pool holds "
                "%d — raise FLAGS_serve_kv_blocks"
                % (pool.blocks_for(len(prompt)), pool.capacity))
        fut = Future()
        if _batcher._METRICS_ON:
            _gen._M_GEN_REQS.inc()
            self._tag_tenant(tenant, fut)
        tenant.queue.put(GenRequest(prompt, max_new_tokens, eos_id,
                                    fut))
        return fut

    # -- socket endpoint -----------------------------------------------
    def start_endpoint(self, port=0, host="127.0.0.1"):
        """Serve the fastwire-framed Predict method; returns the bound
        port (``port=0`` picks a free one)."""
        from .wire import PredictEndpoint

        if self._endpoint is not None:
            raise RuntimeError("endpoint already running on port %d"
                               % self._endpoint.port)
        self._endpoint = PredictEndpoint(self, host=host, port=port)
        return self._endpoint.port

    # -- lifecycle -----------------------------------------------------
    def close(self):
        with self._lock:
            self._closed = True
            tenants = list(self._tenants.values())
            self._tenants.clear()
            _M_MODELS.set(0)
        if self._endpoint is not None:
            self._endpoint.stop()
            self._endpoint = None
        for t in tenants:
            t.dispatcher.stop()
            if isinstance(t, _GenTenant):
                t.engine.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
