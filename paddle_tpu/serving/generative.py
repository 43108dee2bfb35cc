"""Token-level generative serving: paged-KV decode on the AOT path.

The engine of the generate plane.  What it serves is the model behind
the config object it was given (serving/lm.py: the page spec, the
staging rule, the forward over paged rows, the forward over a fresh
prompt, the logit layer); everything here is the engine's:

- **Paged KV cache** — the tenant's cache lives as ONE device-
  resident tree of arrays that the model's ``cache_spec`` names
  (``LMConfig``'s is the K/V page pair, shaped by its page spec; a
  model with recurrent layers adds its per-sequence state arrays),
  DONATED whole
  through every prefill/decode dispatch and re-bound to the returned
  buffers, so cache state never round-trips the host between steps —
  the prepared-program donation contract applied to serving state.
  Host-side accounting (free list, per-sequence block tables and state
  slots, pressure gauges) is kv_cache.BlockPool.  A model that says it
  has per-sequence state (``state_slots``) gets the slot ids as one
  more ``[R]`` int32 operand of its steps; copy-on-write, the prefix
  cache, speculation and page migration are K/V-only and refuse such
  a model by name.
- **Prefill/decode disaggregated continuous batching** — DecodeLoop
  runs Orca iteration-level scheduling: every decode iteration
  re-decides the batch, admitting queued prefills the moment the block
  pool can hold them (batcher.TokenScheduler policy) and retiring
  finished sequences without stalling their neighbours.  One decode
  step is always in flight: where the next step runs over the same
  rows it is dispatched on the device tokens of the one before, ahead
  of their read, so the device does not wait for the host between
  steps (``DecodeLoop``).  Prefill is a
  separate dispatch (its own power-of-2 sequence-length bucket ladder);
  decode batches compile per power-of-2 ``(batch, block-count)`` bucket
  on the engine.StepCache ladder — ahead-of-time ``lower().compile()``
  at load for the warm set, background compile on a miss with traffic
  falling to the smallest covering bucket, exactly the ModelEngine
  bucket discipline.
- **Five compiled steps over the model's two forwards** — decode
  (paged rows at each sequence's length), verify (the same on
  batch x (k+1) flattened rows), propose (k decodes chained in one
  program), the suffix prefill (rows ``start + i`` of one table) and
  prefill (the prompt forward); each adds the logit layer and a greedy
  argmax.

Greedy decode is deterministic: a request admitted into a running
decode batch produces bit-identical tokens to the same request run
solo (per-row math is batch-independent; pinned by
tests/test_generative_serving.py).

Two raw-decode-speed levers ride on top, both default-off:

- **Copy-on-write prefix caching** (``FLAGS_serve_prefix_cache`` /
  ``load_generative(prefix_cache=True)``) — PrefixCache keeps a
  radix-style index over prompt token ids at block granularity.
  Admission shares the already-resident prefix blocks by refcount
  (kv_cache.BlockPool.share), prefill computes and stores ONLY the
  un-cached suffix (the ``_prefill_cached`` step: paged attention over
  the shared prefix + the in-flight suffix), a shared block that would
  be written mid-block is copied first (BlockPool.cow +
  GenerativeEngine.copy_block), and finished prompts' blocks park in a
  refcount-zero LRU, evicted only under allocation pressure.
- **Speculative decoding** (``FLAGS_serve_spec_k`` /
  ``load_generative(spec_k=k, draft=(cfg, params))``) — a small draft
  LM (its pages mirror the target's block ids, so the block tables are
  shared verbatim) proposes k tokens per iteration; the target
  verifies all k in ONE batched ``(batch, k+1)``-bucket dispatch;
  greedy acceptance keeps the longest proposal prefix matching the
  target's own greedy tokens plus the target's correction token, so
  output is bit-identical to non-speculative greedy decode (certified
  with per-step accept-rate accounting by tools/serve_bench.py).
"""
from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from paddle_tpu.core import sanitizer as _san
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.trace import TRACER

from . import batcher as _batcher
from .batcher import TokenScheduler
from .engine import StepCache, bucket_ladder, pow2_bucket
from .kv_cache import BlockPool
from .lm import LMConfig, tiny_lm

__all__ = ["LMConfig", "GenerativeEngine", "GenRequest", "DecodeLoop",
           "PrefixCache", "tiny_lm", "token_metrics_probe",
           "spec_metrics_probe"]

_M_TOKENS = _metrics.counter("serve_tokens_total",
                             "tokens generated by the decode loop")
_M_GEN_REQS = _metrics.counter("serve_gen_requests_total",
                               "generate requests accepted")
_M_PREFILLS = _metrics.counter("serve_prefills_total",
                               "prefill dispatches (includes "
                               "re-prefills after preemption)")
_M_TTFT = _metrics.histogram("serve_ttft_ms",
                             "request arrival -> first token")
_M_ITL = _metrics.histogram("serve_itl_ms",
                            "inter-token latency (per emitted token "
                            "after the first)")
_M_DECODE_STEPS = _metrics.counter("serve_decode_steps_total",
                                   "decode iterations dispatched")
_M_DECODE_AHEAD = _metrics.counter(
    "serve_decode_ahead_total",
    "decode iterations dispatched while the one before was unread: "
    "their input tokens never left the device (ahead / steps is the "
    "share of steps whose dispatch the device did not wait for)")
_M_DECODE_ROWS = _metrics.counter("serve_decode_rows_total",
                                  "live sequences summed over decode "
                                  "iterations")
_M_DECODE_SLOTS = _metrics.counter("serve_decode_slots_total",
                                   "bucket rows summed over decode "
                                   "iterations (occupancy denominator)")
_M_DECODE_PAGES = _metrics.counter("serve_decode_pages_total",
                                   "KV pages the live rows hold, summed "
                                   "over decode iterations: what the "
                                   "paged kernel has to read")
_M_DECODE_PAGE_SLOTS = _metrics.counter(
    "serve_decode_page_slots_total",
    "bucket rows x bucket table width summed over decode iterations: "
    "pages / page slots is the share of the block table that is live")
_M_OCC_PCT = _metrics.histogram(
    "serve_decode_occupancy_pct",
    "live rows / bucket rows per decode iteration, percent",
    bounds=(10, 20, 30, 40, 50, 60, 70, 80, 90, 100))
_M_GEN_MS = _metrics.histogram("serve_gen_request_ms",
                               "generate request arrival -> finished")
# speculative decoding (ISSUE 19).  Counters, not histograms: the
# export/trace_report accept-rate and draft-overhead columns are
# ratios of counter snapshots (accepted/proposed,
# draft_us/(draft_us+verify_us)), computable from any dump
_M_SPEC_ROUNDS = _metrics.counter(
    "serve_spec_rounds_total",
    "speculative draft-propose/target-verify rounds dispatched")
_M_SPEC_PROPOSED = _metrics.counter(
    "serve_spec_proposed_total",
    "draft tokens proposed (k per sequence per round)")
_M_SPEC_ACCEPTED = _metrics.counter(
    "serve_spec_accepted_total",
    "proposed tokens accepted by target verification (the correction "
    "token is NOT counted — accept rate is accepted/proposed)")
_M_SPEC_DRAFT_US = _metrics.counter(
    "serve_spec_draft_us_total",
    "wall micros in draft-model dispatches (propose + catch-up)")
_M_SPEC_VERIFY_US = _metrics.counter(
    "serve_spec_verify_us_total",
    "wall micros in target verify dispatches")


# where a DecodeLoop iteration's wall time goes, counted where it is
# spent (integers of microseconds, like the spec meters above): every
# instant of an iteration is host, prefill or wait, so the three add up
# to wall.
# The benchmark's sched_host_share / prefill_share / queue_wait_ms read
# these; an operator cross-checks wall - host - prefill against wait
_M_LOOP_WALL_US = _metrics.counter(
    "serve_loop_wall_us_total",
    "wall micros of decode-loop iterations")
_M_LOOP_HOST_US = _metrics.counter(
    "serve_loop_host_us_total",
    "iteration micros in the scheduler's own work: admit, grow, "
    "stage, dispatch, emit and what lies between them (neither inside "
    "a prefill nor blocked on a decode result)")
_M_LOOP_PREFILL_US = _metrics.counter(
    "serve_loop_prefill_us_total",
    "iteration micros inside prefills run from the loop (the running "
    "batch waits for each)")
_M_LOOP_WAIT_US = _metrics.counter(
    "serve_loop_wait_us_total",
    "iteration micros blocked on the device for a decode result "
    "(speculative rounds: their draft and verify dispatches)")
_M_QUEUE_WAIT_US = _metrics.counter(
    "serve_queue_wait_us_total",
    "arrival -> admission micros summed over admissions (a preempted "
    "request's re-admission counts again, from its original arrival)")
_M_ADMISSIONS = _metrics.counter(
    "serve_admissions_total",
    "requests admitted into the running batch (re-admissions after "
    "preemption included)")

_REQUEST_IDS = itertools.count(1)
_clock_ns = time.perf_counter_ns
_FLUSH_NS = 10_000_000      # of loop wall time between counter flushes


class _LoopTimes:
    """Nanoseconds of DecodeLoop iterations by phase, not yet counted.
    ``mark`` is the clock at the last phase boundary: a boundary adds
    the time since to the phase that ends there and moves the mark, so
    nothing of an iteration goes uncounted.  The loop flushes whole micros to the ``serve_loop_*`` counters (and
    keeps the remainder) at the end of an iteration once ``_FLUSH_NS``
    of wall time have gathered, and whenever it goes idle — four
    locked counter increments an iteration would be most of the
    phase clock's cost on a sub-millisecond step.  So the counters lag
    a running loop by under ``_FLUSH_NS`` and are exact for an idle
    one."""

    __slots__ = ("wall", "host", "prefill", "wait", "mark")

    def __init__(self):
        self.wall = self.host = self.prefill = self.wait = self.mark = 0

    def flush(self):
        us, self.wall = divmod(self.wall, 1000)
        _M_LOOP_WALL_US.inc(us)
        us, self.host = divmod(self.host, 1000)
        _M_LOOP_HOST_US.inc(us)
        us, self.prefill = divmod(self.prefill, 1000)
        _M_LOOP_PREFILL_US.inc(us)
        us, self.wait = divmod(self.wait, 1000)
        _M_LOOP_WAIT_US.inc(us)


def token_metrics_probe(iters):
    """The COMPLETE per-token metric op set of the decode loop, for the
    telemetry_overhead.py decode gate (batcher.metrics_probe's rule):
    every operation ``_METRICS_ON`` gates per emitted token in the
    single-sequence worst case, where the per-iteration ops are not
    amortized across batch neighbours — the iteration's liveness check
    and phase clock reads with the ``serve_loop_*`` flush included."""
    times = _LoopTimes()
    for _ in range(iters):
        # per-iteration (worst case: one live row): the dead-path
        # sites of DecodeLoop._iterate and decode_read — one liveness
        # check, four clock reads (start, around the wait, end), the
        # phase adds, a flush every _FLUSH_NS of (here, 0.2-ms)
        # iterations
        TRACER.live()
        times.mark = t_iter = _clock_ns()
        now = _clock_ns()
        times.host += now - times.mark              # up to the wait
        times.mark = now
        now = _clock_ns()
        times.wait += now - times.mark              # the wait
        times.mark = now
        now = _clock_ns()
        times.host += now - times.mark              # emit
        times.wall += now - t_iter + 200000
        if times.wall >= _FLUSH_NS:
            times.flush()
        _M_DECODE_STEPS.inc()
        _M_DECODE_AHEAD.inc()
        _M_DECODE_ROWS.inc(1)
        _M_DECODE_SLOTS.inc(1)
        _M_DECODE_PAGES.inc(1)
        _M_DECODE_PAGE_SLOTS.inc(1)
        _M_OCC_PCT.observe(100.0)
        # per-token
        _M_TOKENS.inc()
        _M_ITL.observe(0.4)


def spec_metrics_probe(iters):
    """The per-ROUND metric op set speculative decoding adds on top of
    token_metrics_probe's per-token ops, for the telemetry_overhead.py
    spec gate: one spec round runs these once and emits >= 1 token, so
    the single-sequence worst case charges the full set per round."""
    for _ in range(iters):
        _M_SPEC_ROUNDS.inc()
        _M_SPEC_PROPOSED.inc(4)
        _M_SPEC_ACCEPTED.inc(3)
        _M_SPEC_DRAFT_US.inc(120)
        _M_SPEC_VERIFY_US.inc(300)


# ---------------------------------------------------------------------------
# Requests / sequences
# ---------------------------------------------------------------------------

class GenRequest:
    """One generate request; doubles as the running-sequence state (the
    scheduler's admit/preempt unit).  ``blocks`` / ``context_len`` /
    ``out`` are reset by preemption — greedy decode regenerates the
    same tokens on re-admission."""

    __slots__ = ("rid", "prompt", "max_new", "eos_id", "future",
                 "t_arrival", "blocks", "context_len", "out", "t_first",
                 "t_last", "itl_ms", "preempted", "cached_len",
                 "draft_len", "slot")

    def __init__(self, prompt, max_new, eos_id, future):
        # small process-wide id: the cid of this request's spans
        self.rid = next(_REQUEST_IDS)
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.future = future
        self.t_arrival = time.perf_counter()
        self.reset()
        self.preempted = 0
        # the state slot held from admission to finish or preemption
        # (a model with per-sequence state; 0: none, the scratch slot)
        self.slot = 0

    def reset(self):
        self.blocks = []
        self.context_len = 0
        self.out = []
        self.t_first = None
        self.t_last = None
        self.itl_ms = []
        # prompt tokens whose K/V came from shared prefix blocks at
        # admission (prefill computes positions cached_len..n-1 only)
        self.cached_len = 0
        # positions with valid K/V in the DRAFT engine's pages (spec
        # decoding; always <= context_len, re-prefilled after a reset)
        self.draft_len = 0

    def token_at(self, pos):
        """The token AT position ``pos`` of the full sequence (prompt
        then generated) — the spec-decode catch-up feed."""
        n = len(self.prompt)
        return self.prompt[pos] if pos < n else self.out[pos - n]

    def result(self):
        return {"tokens": list(self.out),
                "ttft_ms": (self.t_first - self.t_arrival) * 1e3
                if self.t_first is not None else None,
                "itl_ms": list(self.itl_ms),
                "preempted": self.preempted}


# ---------------------------------------------------------------------------
# Prefix caching (ISSUE 19 tentpole a): a radix-style index over prompt
# token ids at block granularity
# ---------------------------------------------------------------------------

class PrefixCache:
    """Block-granularity prompt-prefix index over one engine's pool.

    The trie's edges are ``block_size``-token chunks; each node owns
    the block holding that chunk's K/V.  The index does NOT hold
    references: a node's block is either referenced by live sequences
    or parked in the pool's refcount-zero cached LRU — when allocation
    pressure reclaims a parked block, the pool's eviction callback
    drops its node (and the now-unreachable subtree below it).  K/V is
    a pure function of the token prefix, so any sequence whose prompt
    walks the same chunks can share the same pages.

    Admission contract (``acquire``): the prompt's longest indexed
    chunk path is SHARED by refcount; a partial tail whose tokens
    prefix an indexed chunk is copy-on-written (the sequence will
    write its own suffix K/V mid-block); the rest is a plain
    allocation.  At least the final prompt token is always left
    un-cached — the suffix prefill has to compute SOMETHING to emit
    the first generated token.

    Lock order: the pool's eviction callback runs UNDER the pool lock
    and takes the index lock, so index methods never call into the
    pool while holding the index lock (lookup snapshots under the
    lock, then shares/allocates outside it; ``share`` returning False
    closes the snapshot-to-share race as a cold miss)."""

    def __init__(self, engine):
        self.engine = engine
        self.pool = engine.pool
        self.block_size = engine.config.block_size
        self._root = {}       # chunk tuple -> {"block","children"}
        self._by_block = {}   # block id -> (parent children dict, chunk)
        self._lock = _san.make_lock(
            "serve.prefix_cache:%s" % engine.name)
        self.pool.set_evict_callback(self._on_evict)

    # -- read side ------------------------------------------------------

    def _lookup(self, prompt):
        """(shared block ids, cow source block or None, cached token
        count) for ``prompt`` — caller holds the index lock."""
        n = len(prompt)
        bs = self.block_size
        shared = []
        children = self._root
        i = 0
        # full-chunk walk, capped so position n-1 stays un-cached
        while (i + 1) * bs <= n - 1:
            nd = children.get(tuple(prompt[i * bs:(i + 1) * bs]))
            if nd is None:
                break
            shared.append(nd["block"])
            children = nd["children"]
            i += 1
        cached = i * bs
        # partial tail: the child chunk sharing the longest common
        # prefix with the remaining (cache-eligible) tokens is a COW
        # source — its early positions' K/V is exactly ours
        tail = prompt[cached:n - 1][:bs]
        cow_src, best = None, 0
        if tail:
            for chunk, nd in children.items():
                m = 0
                for a, b in zip(chunk, tail):
                    if a != b:
                        break
                    m += 1
                if m > best:
                    cow_src, best = nd["block"], m
        return shared, cow_src, cached + best

    def probe(self, prompt):
        """(shared block count, cached token count) the index would
        serve for ``prompt`` right now — test/debug introspection."""
        with self._lock:
            shared, cow_src, cached = self._lookup(list(prompt))
        return len(shared) + (1 if cow_src is not None else 0), cached

    # -- admission (batcher.TokenScheduler's partially-cached branch) ---

    def acquire(self, req):
        """Stock ``req.blocks`` for its whole prompt: shared prefix
        blocks by refcount, a COW copy for a partially-matching tail
        chunk, fresh blocks for the rest.  Sets ``req.cached_len`` to
        the token count whose K/V needs no recompute.  False — with
        every reference rolled back — when the pool cannot supply the
        un-cached remainder (the scheduler requeues the request)."""
        prompt = req.prompt
        n = len(prompt)
        total = self.pool.blocks_for(n)
        with self._lock:
            shared, cow_src, cached_len = self._lookup(prompt)
        pin = shared + ([cow_src] if cow_src is not None else [])
        if pin and not self.pool.share(pin):
            # an eviction raced the lookup (fleet migration allocates
            # from another thread): treat the whole lookup as cold
            shared, cow_src, cached_len, pin = [], None, 0, []
        blocks = list(shared)
        if cow_src is not None:
            dst = self.pool.cow(cow_src, copy=self.engine.copy_block)
            if dst is None:
                self.pool.free(pin)
                return False
            blocks.append(dst)
        fresh_n = total - len(blocks)
        if fresh_n > 0:
            fresh = self.pool.alloc(fresh_n)
            if fresh is None:
                self.pool.free(blocks)
                return False
            blocks.extend(fresh)
        req.blocks = blocks
        req.cached_len = cached_len
        self.pool.note_prefix_lookup(n, cached_len)
        return True

    # -- write side -----------------------------------------------------

    def insert(self, seq):
        """Index ``seq``'s fully-written prompt blocks after a
        successful prefill.  Chunks already indexed keep their
        canonical block (this sequence's duplicate stays un-indexed
        and returns to the free list on release); new chunks are
        marked cacheable so they PARK instead of freeing when the
        sequence lets go."""
        prompt = seq.prompt
        bs = self.block_size
        full = len(prompt) // bs
        if not full:
            return
        fresh = []
        with self._lock:
            children = self._root
            for i in range(full):
                chunk = tuple(prompt[i * bs:(i + 1) * bs])
                nd = children.get(chunk)
                if nd is None:
                    b = int(seq.blocks[i])
                    nd = {"block": b, "children": {}}
                    children[chunk] = nd
                    self._by_block[b] = (children, chunk)
                    fresh.append(b)
                children = nd["children"]
        if fresh:
            self.pool.set_cacheable(fresh)

    # -- pool eviction callback (runs UNDER the pool lock) --------------

    def _on_evict(self, block):
        with self._lock:
            ent = self._by_block.pop(int(block), None)
            if ent is None:
                return ()
            children, chunk = ent
            nd = children.pop(chunk, None)
            if nd is None:
                return ()
            # the subtree below is unreachable now; every block in it
            # is parked (a live descendant would pin its ancestors
            # live too), so hand them back for the free list
            orphans = []
            stack = [nd["children"]]
            while stack:
                for d in stack.pop().values():
                    orphans.append(d["block"])
                    self._by_block.pop(d["block"], None)
                    stack.append(d["children"])
            return orphans

    @property
    def nodes(self):
        with self._lock:
            return len(self._by_block)


# ---------------------------------------------------------------------------
# The engine: device pages + bucket-compiled prefill/decode steps
# ---------------------------------------------------------------------------

def _as_config(config):
    """A dict is ``LMConfig``'s fields; anything else is the served
    model itself (serving/lm.py says what the engine asks of it)."""
    return LMConfig(**config) if isinstance(config, dict) else config


def _greedy(logits):
    import jax.numpy as jnp

    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _DecodeFlight:
    """A dispatched decode step whose tokens the host has not read:
    ``nxt`` is the executable's ``(bb,)`` int32 output on the engine's
    device (``logits`` its ``[bb, V]`` one, where asked for), ``b`` the
    live rows of the ``bb`` the batch bucket holds.  ``host`` is the
    read-back once ``read`` has made it."""

    __slots__ = ("nxt", "logits", "stats", "b", "bb", "host")

    def __init__(self, nxt, logits, b, bb, stats=None):
        self.nxt, self.logits, self.b, self.bb = nxt, logits, b, bb
        # what the model counted in the step (a small device array, read
        # with the tokens), where it counts anything
        self.stats = stats
        self.host = None

    def read(self):
        """The live rows' tokens on the host; the first call blocks
        until the device has them."""
        if self.host is None:
            self.host = np.asarray(self.nxt)[:self.b]
        return self.host


class GenerativeEngine:
    """One generative tenant: device-resident params + KV pages +
    StepCache ladders of AOT-compiled prefill/decode steps.  The model
    is ``config``'s (serving/lm.py says what is asked of it); a dict
    becomes an ``LMConfig``."""

    def __init__(self, config, params, quant="", kv_blocks=None,
                 name="", place=None, warm=True, prefix_cache=None,
                 spec_k=None, draft=None, _register_pool=True):
        self.config = _as_config(config)
        self.name = name or "generative"
        self.quant = str(quant or "")
        if self.quant not in ("", "int8"):
            raise ValueError("unsupported quant mode %r (want ''/'int8')"
                             % (self.quant,))
        cfg = self.config
        n_blocks = int(kv_blocks or FLAGS.serve_kv_blocks)
        # a model with per-sequence state says how many slots it keeps
        # (the scratch slot included); the pool hands them out
        state_slots = int(getattr(cfg, "state_slots", 0))
        self.stateful = state_slots > 0
        self.pool = BlockPool(n_blocks, cfg.block_size,
                              register=_register_pool,
                              state_slots=state_slots)
        self.prefix_cache = None
        self.draft = None
        self.spec_k = int(FLAGS.serve_spec_k if spec_k is None
                          else spec_k)
        if self.spec_k < 0:
            self.pool.close()
            raise ValueError("spec_k must be >= 0 (got %d)"
                             % self.spec_k)
        prefix_cache = (FLAGS.serve_prefix_cache if prefix_cache is None
                        else prefix_cache)
        try:
            if prefix_cache:
                self._kv_only("the prefix cache (prefix_cache=True)")
            if self.spec_k > 0:
                self._kv_only("speculative decoding (spec_k=%d)"
                              % self.spec_k)
            self._init_device_state(params, n_blocks, place, warm)
            if prefix_cache:
                self.prefix_cache = PrefixCache(self)
                if warm:
                    self._prefill_cached.warm(
                        [(s,) for s in self.prefill_ladder])
            if self.spec_k > 0:
                self._init_draft(draft, n_blocks, place, warm)
        except Exception:
            # a half-built engine must retire its pool from the
            # process gauges, or every failed load leaves phantom
            # serve_kv_blocks capacity behind forever
            if self.draft is not None:
                self.draft.close()
            self.pool.close()
            raise

    def _init_draft(self, draft, n_blocks, place, warm):
        """Stand up the speculative draft engine: a second (small)
        GenerativeEngine whose page pool MIRRORS the target's block
        ids — same block count, same geometry — so the sequences'
        block tables are shared verbatim between the two.  The draft's
        pool is a shadow (never allocated from, unregistered from the
        serve_kv gauges); its K/V validity is tracked per sequence as
        ``seq.draft_len``."""
        cfg = self.config
        if draft is None:
            raise ValueError(
                "spec_k=%d needs a draft model: "
                "load_generative(..., draft=(config, params))"
                % self.spec_k)
        dcfg, dparams = draft
        dcfg = _as_config(dcfg)
        if getattr(dcfg, "state_slots", 0):
            raise ValueError(
                "a draft model with per-sequence state (%s) cannot share "
                "the target's block tables: speculation is K/V-only"
                % type(dcfg).__name__)
        for f in ("vocab", "block_size", "max_blocks", "max_batch"):
            if getattr(dcfg, f) != getattr(cfg, f):
                raise ValueError(
                    "draft/target %s mismatch (%r != %r): the draft "
                    "shares the target's block tables, so its paging "
                    "geometry and token space must match exactly"
                    % (f, getattr(dcfg, f), getattr(cfg, f)))
        self.draft = GenerativeEngine(
            dcfg, dparams, quant="", kv_blocks=n_blocks,
            name=self.name + ".draft", place=place, warm=False,
            prefix_cache=False, spec_k=0, _register_pool=False)
        if warm:
            # the draft serves single-token decode steps (propose +
            # catch-up) and the occasional re-prefill; the target
            # verifies on the (batch, blocks, k+1) ladder
            self.draft._decode.warm([(b, self.draft.nb_top)
                                     for b in self.batch_ladder])
            self.draft._propose.warm(
                [(b, self.draft.nb_top, self.spec_k)
                 for b in self.batch_ladder])
            self.draft._prefill.warm(
                [(s,) for s in self.draft.prefill_ladder])
            self._verify.warm([(b, self.nb_top, self.spec_k + 1)
                               for b in self.batch_ladder])

    def _init_device_state(self, params, n_blocks, place, warm):
        import jax

        from paddle_tpu.core.flags import ensure_compile_cache
        from paddle_tpu.core.place import default_place

        ensure_compile_cache()
        cfg = self.config
        # the server's place, else the default device — the predict
        # plane's rule (engine.ModelEngine), so both planes of one
        # InferenceServer sit on the same chip
        self._dev = (place if place is not None
                     else default_place()).jax_device()
        self._params = cfg.stage(params, self.quant, self._dev)
        self._cache = jax.tree_util.tree_map(
            lambda a: jax.device_put(np.zeros(a.shape, a.dtype), self._dev),
            cfg.cache_spec(n_blocks))
        self._cache_tree = jax.tree_util.tree_structure(self._cache)
        self._state_lock = _san.make_lock(
            "serve.generative.state:%s" % self.name)
        # buffer sanitizer: the cache tree is donated through every
        # prefill/decode dispatch and re-bound to the returned buffers
        # — the guard names a read of the pool mid-dispatch or through
        # a stale (pre-rebind) epoch
        self._kv_guard = _san.BufferEpochGuard(
            "kv_pool:%s" % self.name)
        self._kv_steps = 0
        # bucket ladders
        self.batch_ladder = bucket_ladder(cfg.max_batch)
        self.nb_top = cfg.max_blocks
        self.prefill_ladder = []
        s = cfg.block_size
        while s < cfg.max_seq:
            self.prefill_ladder.append(s)
            s *= 2
        self.prefill_ladder.append(cfg.max_seq)
        self._decode = StepCache(self._compile_decode,
                                 name=self.name + ".decode")
        self._decode_logits = StepCache(
            lambda key: self._compile_decode(key, with_logits=True),
            name=self.name + ".decode_logits")
        self._prefill = StepCache(self._compile_prefill,
                                  name=self.name + ".prefill")
        # warmed only when their feature is on: suffix-only prefill
        # for prefix-cache hits, keyed by suffix bucket; batched verify
        # and the draft's fused proposal for speculative decode, keyed
        # (batch, blocks, k+1) and (batch, blocks, k)
        self._prefill_cached = StepCache(
            self._compile_prefill_cached,
            name=self.name + ".prefill_cached")
        self._verify = StepCache(self._compile_verify,
                                 name=self.name + ".verify")
        self._propose = StepCache(self._compile_propose,
                                  name=self.name + ".propose")
        if warm:
            # decode: whole batch ladder at the top block-count bucket
            # (covering bucket for every narrower request — tighter
            # block-count buckets background-compile on first miss);
            # prefill: the whole sequence ladder — prefill has no
            # covering fallback wider than itself, so a cold bucket
            # would pay a synchronous compile in some request's TTFT
            self._decode.warm([(b, self.nb_top)
                               for b in self.batch_ladder])
            self._prefill.warm([(s,) for s in self.prefill_ladder])

    def page_spec(self, n_blocks):
        """``(shape, dtype)`` of each of the K and V pools for
        ``n_blocks`` blocks — the model's, asked here by everything
        that installs or ships pages."""
        return self.config.page_spec(n_blocks)

    def _kv_only(self, what):
        """``what`` works on K/V pages alone: a model with per-sequence
        state is refused by name (its state has no snapshot, copy or
        wire form yet)."""
        if self.stateful:
            raise ValueError(
                "%s is K/V-only: %s keeps per-sequence state beside its "
                "pages (state_slots=%d), which it cannot share, copy or "
                "ship" % (what, type(self.config).__name__,
                          self.config.state_slots))

    # -- compiled steps -------------------------------------------------

    def _aot(self, name, step, *specs):
        """AOT-compile ``step(params, cache, *args)`` for THIS engine's
        device (not whatever jax's default is), the whole cache tree
        donated; ``specs`` are the ``(shape, dtype)`` of the trailing
        args.  ``name`` is the executable's stable name in a trace."""
        import jax
        from jax.sharding import SingleDeviceSharding

        from paddle_tpu.core.place import placed_on

        on_dev = SingleDeviceSharding(self._dev)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=on_dev)

        flat = self._flat(step)

        def placed(*args):
            with placed_on(self._dev):
                return flat(*args)

        placed.__name__ = name
        pspec, cspec = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), (self._params, self._cache))
        return jax.jit(placed, donate_argnums=(1,)).lower(
            pspec, cspec,
            *[sds(shape, dtype) for shape, dtype in specs]).compile()

    @staticmethod
    def _flat(step):
        """``step`` with the cache tree it returns last flattened into
        its leaves, as ``_dispatch`` takes them back: the executable's
        results are then the step's own outputs followed by the cache
        arrays, whatever tree the model named."""
        import jax

        def flat(*args):
            *out, cache = step(*args)
            return (*out, *jax.tree_util.tree_leaves(cache))

        return flat

    def _compile_decode(self, key, with_logits=False):
        """AOT-compile one decode iteration at bucket ``(B, NB)``: one
        token per sequence in, K/V written through the block table,
        paged attention over each sequence's pages, greedy next token
        out.  The cache is donated — the returned buffers REPLACE the
        engine's, nothing round-trips the host.  A model with
        per-sequence state takes its rows' slot ids as one more operand
        and may hand back what it counted in the step."""
        import jax.numpy as jnp

        model = self.config
        bb, nbb = key
        slot_spec = (((bb,), jnp.int32),) if self.stateful else ()

        def step(p, cache, tables, lens, toks, *slots):
            h, cache, *stats = model.paged_forward(
                p, cache, toks, lens, tables,
                **({"slots": slots[0]} if slots else {}))
            logits = model.head(p, h)                      # [B, V] f32
            nxt = _greedy(logits)
            if with_logits:
                return (nxt, logits, *stats, cache)
            return (nxt, *stats, cache)

        return self._aot(
            "serve_decode", step,
            ((bb, nbb), jnp.int32),
            ((bb,), jnp.int32),
            ((bb,), jnp.int32), *slot_spec)

    def _compile_prefill(self, key):
        """AOT-compile one prefill at sequence bucket ``(S,)``: the
        whole (padded) prompt forward in one dispatch, every position's
        K/V written into the sequence's blocks, greedy first token from
        the last real position."""
        import jax.numpy as jnp

        model = self.config
        (s_len,) = key
        n_ids = max(1, s_len // model.block_size)

        slot_spec = (((), jnp.int32),) if self.stateful else ()

        def step(p, cache, toks, length, block_ids, *slot):
            h, cache = model.prompt_forward(
                p, cache, toks, length, block_ids,
                **({"slot": slot[0]} if slot else {}))
            return _greedy(model.head(p, h, n_live=length)), cache

        return self._aot(
            "serve_prefill", step,
            ((s_len,), jnp.int32),
            ((), jnp.int32),
            ((n_ids,), jnp.int32), *slot_spec)

    def _compile_prefill_cached(self, key):
        """AOT-compile a SUFFIX prefill at suffix bucket ``(S,)``: the
        prompt's first ``start`` positions are already resident in
        shared prefix blocks, so only the ``count`` un-cached suffix
        tokens run — row i is position ``start + i`` of the one block
        table, attending over the shared prefix plus the just-written
        suffix.  Greedy first token from the last real row.  Compute
        scales with the suffix, not the prompt — the entire point of
        the cache."""
        import jax.numpy as jnp

        model = self.config
        (s_len,) = key
        n_ids = model.max_blocks

        def step(p, cache, toks, start, count, block_ids):
            rows = jnp.arange(s_len, dtype=jnp.int32)
            h, cache = model.paged_forward(
                p, cache, toks, start + rows,
                jnp.broadcast_to(block_ids, (s_len, n_ids)),
                live=rows < count)
            return _greedy(model.head(p, h, n_live=count)), cache

        return self._aot(
            "serve_prefill_cached", step,
            ((s_len,), jnp.int32),
            ((), jnp.int32),
            ((), jnp.int32),
            ((n_ids,), jnp.int32))

    def _compile_propose(self, key):
        """AOT-compile a FUSED k-step greedy decode at bucket
        ``(B, NB, k)`` — the speculative DRAFT's proposal loop in one
        dispatch.  The k single-token steps run unrolled inside one
        program, each feeding its argmax to the next, so the
        per-dispatch overhead is paid once per round instead of k
        times — on a dispatch-bound host that overhead IS most of a
        small draft's step cost.  K/V for the k positions lands in the
        pages exactly as k separate decode steps would have written
        it."""
        import jax.numpy as jnp

        model = self.config
        bb, nbb, k = key

        def step(p, cache, tables, lens, toks):
            cur = toks                                     # [B]
            props = []
            for j in range(k):
                h, cache = model.paged_forward(p, cache, cur,
                                               lens + j, tables)
                cur = _greedy(model.head(p, h))
                props.append(cur)
            return jnp.stack(props, axis=1), cache       # [B, k]

        return self._aot(
            "serve_propose", step,
            ((bb, nbb), jnp.int32),
            ((bb,), jnp.int32),
            ((bb,), jnp.int32))

    def _compile_verify(self, key):
        """AOT-compile one speculative VERIFY at bucket
        ``(B, NB, k+1)``: for each sequence, k+1 candidate tokens (last
        emitted + the draft's k proposals) run as k+1 paged rows of ONE
        dispatch — row (i, j) feeds candidate j at position
        ``lens[i] + j``, writes its K/V, and attends over
        ``lens[i] + j + 1`` positions (the earlier candidates' K/V
        included, written this same dispatch), so the greedy outputs
        are EXACTLY the tokens non-speculative decode would have
        produced one iteration at a time.  Rows past a mismatch write
        K/V the host will roll back over — attention never reads past
        ``lens``, so stale writes are dead until overwritten."""
        import jax.numpy as jnp

        model = self.config
        bb, nbb, k1 = key

        def step(p, cache, tables, lens, toks):
            pos = (lens[:, None]
                   + jnp.arange(k1, dtype=jnp.int32)[None, :])  # [B,k1]
            h, cache = model.paged_forward(
                p, cache, toks.reshape(bb * k1), pos.reshape(bb * k1),
                jnp.repeat(tables, k1, axis=0))            # [B*k1, NB]
            return _greedy(model.head(p, h)).reshape(bb, k1), cache

        return self._aot(
            "serve_verify", step,
            ((bb, nbb), jnp.int32),
            ((bb,), jnp.int32),
            ((bb, k1), jnp.int32))

    # -- dispatch -------------------------------------------------------

    def _dispatch(self, op, run):
        """The bracket around everything that donates the cache: under
        the state lock, count the step, open the epoch guard,
        ``run(cache)``, re-bind the cache tree to the arrays that what
        it returns ends in (one a leaf, in the tree's order) and close
        the guard.  Returns the rest and the epoch the re-bind left."""
        tree = self._cache_tree
        with self._state_lock:
            self._kv_steps += 1
            self._kv_guard.begin(op, self._kv_steps)
            out = list(run(self._cache))
            self._cache = tree.unflatten(out[len(out) - tree.num_leaves:])
            del out[len(out) - tree.num_leaves:]
            self._kv_guard.rebind()
            return out, self._kv_guard.epoch

    def _stage_rows(self, cache, blocks_list, lens_list, *tail):
        """Pick ``cache``'s ``(batch, block-count) + tail`` bucket for
        these rows and pad their block tables and lengths up to it
        (padding rows aim every table slot at the reserved scratch
        block).  Returns ``(key, exe, tables, lens, pages held)``."""
        cfg = self.config
        b = len(blocks_list)
        nb = max(len(bl) for bl in blocks_list)
        key, exe = cache.pick((pow2_bucket(b, cfg.max_batch),
                               pow2_bucket(nb, self.nb_top)) + tail)
        if key[2:] != tail:
            raise RuntimeError("%s bucket %r does not end in %r"
                               % (cache.name, key, tail))
        tables = np.zeros(key[:2], np.int32)
        lens = np.zeros(key[0], np.int32)
        lens[:b] = lens_list
        pages = 0
        for i, bl in enumerate(blocks_list):
            tables[i, :len(bl)] = bl
            pages += len(bl)
        return key, exe, tables, lens, pages

    def prefill(self, seq, _tr=None):
        """Run ``seq``'s prompt through the prefill bucket that fits it;
        returns the first generated token.  ``seq.blocks`` must already
        hold the prompt's blocks (TokenScheduler.try_admit).  With a
        prefix-cache hit recorded on the sequence (``seq.cached_len``),
        only the un-cached suffix is computed.  ``_tr`` is the tracer
        where the caller found its spans live (DecodeLoop asks once an
        iteration), else None: the ``serve.prefill`` span then carries
        the request's id as its cid."""
        cfg = self.config
        n = len(seq.prompt)
        if n < 1:
            raise ValueError("empty prompt")
        if n > cfg.max_seq:
            raise ValueError("prompt length %d exceeds max_seq %d "
                             "(block_size x max_blocks)" % (n, cfg.max_seq))
        cached = getattr(seq, "cached_len", 0)
        if self.prefix_cache is None or not 0 < cached < n:
            cached = 0
        tok = self.prefill_tokens(seq.prompt, seq.blocks, _tr,
                                  getattr(seq, "rid", None), start=cached,
                                  slot=getattr(seq, "slot", 0))
        seq.context_len = n
        if _batcher._METRICS_ON:
            _M_PREFILLS.inc()
        return tok

    def prefill_tokens(self, tokens, blocks, _tr=None, _cid=None,
                       start=0, slot=0):
        """Raw prefill: write K/V for positions ``start..n-1`` of
        ``tokens`` into ``blocks`` and return the greedy next token.
        ``start`` > 0 is a prefix-cache hit: the first ``start``
        positions are read through the pages, and the bucket is picked
        by SUFFIX length — an 80%-cached prompt runs a bucket ~5x
        narrower than its full prefill.  No sequence bookkeeping — the
        speculative draft's re-prefill path uses this directly (its
        catch-up feed is tokens, not a GenRequest).  ``slot`` is the
        sequence's state slot, an operand of a stateful model's prefill
        only (0: the scratch slot).  ``serve.prefill``
        spans staging, dispatch and the wait for the token; its child
        ``serve.prefill.dispatch`` the dispatch."""
        cfg = self.config
        n = len(tokens)
        count = n - start
        if start:
            cache, op = self._prefill_cached, "prefill_cached"
            span_args = {"tokens": n, "cached": start}
        else:
            cache, op, span_args = self._prefill, "prefill", {"tokens": n}
        sp = (_tr.begin("serve.prefill", _cid, span_args)
              if _tr is not None else None)
        want = pow2_bucket(max(count, cfg.block_size), cfg.max_seq)
        key, exe = cache.pick((want,))
        (s_len,) = key
        toks = np.zeros(s_len, np.int32)
        toks[:count] = tokens[start:]
        if start:   # reads the whole table, through the cached prefix
            n_ids, lead = cfg.max_blocks, (np.int32(start), np.int32(count))
        else:       # writes its bucket's blocks
            n_ids, lead = max(1, s_len // cfg.block_size), (np.int32(n),)
        # the sequence may hold MORE blocks than the table's slots
        # (growth provisions ahead for speculative rounds); the extras
        # hold positions past this prefill's writes
        ids = np.zeros(n_ids, np.int32)
        m = min(len(blocks), n_ids)
        ids[:m] = blocks[:m]
        tail = (np.int32(slot),) if self.stateful else ()
        spd = (_tr.begin("serve.prefill.dispatch", _cid)
               if sp is not None else None)
        (nxt,), _ = self._dispatch(
            op, lambda cache: exe(self._params, cache, toks, *lead, ids,
                                  *tail))
        if sp is None:
            return int(nxt)
        _tr.end(spd)
        tok = int(nxt)      # blocks until the device has the token
        _tr.end(sp, args={"bucket": s_len})
        return tok

    def copy_block(self, src, dst):
        """Device-copy one block's K/V pages across all layers — the
        COW copy (BlockPool.cow calls this before dropping the shared
        reference).  Donated like a dispatch: the pool aliases through
        the update, costing O(one block), and the epoch guard brackets
        it so a concurrent reader never sees a half-copied pool."""
        import jax

        self._kv_only("copy_block")
        if not hasattr(self, "_copy_jit"):
            def _cp(cache, s, d):
                kp, vp = cache
                return kp.at[:, d].set(kp[:, s]), vp.at[:, d].set(vp[:, s])

            self._copy_jit = jax.jit(_cp, donate_argnums=(0,))
        self._dispatch("cow", lambda cache: self._copy_jit(
            cache, np.int32(src), np.int32(dst)))

    def decode(self, seqs, with_logits=False):
        """One decode iteration over the running sequences, dispatched
        and read: the next token per live sequence."""
        return self.decode_read(
            self.decode_start(seqs, with_logits=with_logits))

    def decode_start(self, seqs, after=None, with_logits=False, _tr=None):
        """Dispatch one decode iteration over ``seqs`` and advance their
        contexts; the tokens stay on the device behind the returned
        flight (``decode_read``).  ``after`` is the unread flight of the
        step before, over these sequences in this order: its tokens are
        this step's input where they are.  Without it the input is each
        sequence's last token as the host knows it."""
        toks = after if after is not None else [
            s.out[-1] if s.out else (s.prompt[-1] if s.prompt else 0)
            for s in seqs]
        flight = self.decode_dispatch(
            [s.blocks for s in seqs], [s.context_len for s in seqs],
            toks, with_logits=with_logits, _count=True, _tr=_tr,
            slots=[s.slot for s in seqs] if self.stateful else None)
        for s in seqs:
            s.context_len += 1
        return flight

    def decode_step(self, blocks_list, lens_list, toks_list,
                    with_logits=False, _count=False, _tr=None,
                    _times=None, slots=None):
        """Raw single-token decode over parallel lists (one entry per
        row): no sequence bookkeeping, callers own their own length
        accounting — the speculative draft's propose/catch-up steps
        run the draft engine through this.

        ``decode_dispatch`` then ``decode_read``, so it blocks until
        the device has the tokens.  DecodeLoop calls the two halves
        itself and dispatches the next step between them."""
        return self.decode_read(
            self.decode_dispatch(blocks_list, lens_list, toks_list,
                                 with_logits=with_logits, _count=_count,
                                 _tr=_tr, slots=slots),
            _tr=_tr, _times=_times)

    def decode_dispatch(self, blocks_list, lens_list, toks,
                        with_logits=False, _count=False, _tr=None,
                        slots=None):
        """Stage and dispatch one decode step; returns its
        ``_DecodeFlight`` without waiting for the device.  Two phases,
        each a span where ``_tr`` is given: ``serve.decode.stage``
        (bucket pick, tables/lens/toks) and ``serve.decode`` (the
        dispatch under the state lock; ``ahead`` says where the input
        tokens were).

        ``toks`` is one token a row from the host, or the flight of the
        step before over the same rows in the same order.  An unread
        flight of this step's batch bucket hands its device tokens to
        the executable as they are (the ``(bb,)`` int32 output is the
        ``toks`` operand's own shape and device): the host never sees
        them in between, so this dispatch does not wait for that step.
        Any other flight is read here first.  ``slots`` is one state
        slot a row, for a model with per-sequence state (left out, every
        row aims at the scratch slot)."""
        sp = (_tr.begin("serve.decode.stage")
              if _tr is not None else None)
        b = len(blocks_list)
        key, exe, tables, lens, pages = self._stage_rows(
            self._decode_logits if with_logits else self._decode,
            blocks_list, lens_list)
        bb, nbb = key
        ahead = False
        if isinstance(toks, _DecodeFlight):
            ahead = toks.host is None and toks.bb == bb
            toks = toks.nxt if ahead else toks.read()
        if ahead:
            operand = toks
        else:
            operand = np.zeros(bb, np.int32)
            operand[:b] = toks
        tail = ()
        if self.stateful:
            tail = (np.zeros(bb, np.int32),)
            if slots is not None:
                tail[0][:b] = slots
        if sp is not None:
            bucket = "%dx%d" % key
            _tr.end(sp, args={"bucket": bucket})
            sp = _tr.begin("serve.decode", None,
                           {"bucket": bucket, "rows": b, "pages": pages,
                            "ahead": int(ahead)})
        (nxt, *rest), _ = self._dispatch(
            "decode", lambda cache: exe(self._params, cache,
                                        tables, lens, operand, *tail))
        logits = rest.pop(0) if with_logits else None
        stats = rest[0] if rest else None
        if _count and _batcher._METRICS_ON:
            _M_DECODE_STEPS.inc()
            if ahead:
                _M_DECODE_AHEAD.inc()
            _M_DECODE_ROWS.inc(b)
            _M_DECODE_SLOTS.inc(bb)
            _M_DECODE_PAGES.inc(pages)
            _M_DECODE_PAGE_SLOTS.inc(bb * nbb)
            _M_OCC_PCT.observe(100.0 * b / bb)
        if sp is not None:
            _tr.end(sp)
        return _DecodeFlight(nxt, logits, b, bb, stats)

    def decode_read(self, flight, _tr=None, _times=None):
        """The tokens of a dispatched decode step, one a live row (and
        its logits, where it was dispatched with them): blocks until
        the device has them.  A span where ``_tr`` is given,
        ``serve.decode.wait``; ``_times`` gets the time up to here as
        host time and the read-back as wait."""
        sp = (_tr.begin("serve.decode.wait")
              if _tr is not None else None)
        if _times is not None:
            now = _clock_ns()
            _times.host += now - _times.mark
            _times.mark = now
        nxt = flight.read()
        logits = (None if flight.logits is None
                  else np.asarray(flight.logits)[:flight.b])
        if _times is not None:
            now = _clock_ns()
            _times.wait += now - _times.mark
            _times.mark = now
        # what the model counted in this step rides the same read
        counted = (self.config.note_decode(np.asarray(flight.stats),
                                           _batcher._METRICS_ON)
                   if flight.stats is not None else None)
        if sp is not None:
            _tr.end(sp, args=counted)
        return nxt if logits is None else (nxt, logits)

    # -- speculative decoding -------------------------------------------

    def propose_step(self, blocks_list, lens_list, toks_list, k):
        """Fused k-token greedy proposal over parallel lists: ONE
        dispatch runs k chained decode steps (the draft's propose
        phase), returning the [B, k] proposed tokens.  Callers own
        length accounting (each row's pages gained k positions)."""
        b = len(blocks_list)
        key, exe, tables, lens, _ = self._stage_rows(
            self._propose, blocks_list, lens_list, int(k))
        toks = np.zeros(key[0], np.int32)
        toks[:b] = toks_list
        with TRACER.span("serve.propose",
                         args={"bucket": "%dx%dx%d" % key, "rows": b}):
            (props,), _ = self._dispatch(
                "propose", lambda cache: exe(self._params, cache,
                                             tables, lens, toks))
        return np.asarray(props)[:b]

    def verify_step(self, seqs, props):
        """ONE batched target dispatch verifying ``props`` (the
        draft's [B, k] proposals): row (i, j) of the ``(batch, blocks,
        k+1)`` bucket runs candidate j of sequence i.  Returns the
        [B, k+1] greedy tokens — acceptance is the caller's
        (``spec_decode``)."""
        b = len(seqs)
        k1 = int(props.shape[1]) + 1
        key, exe, tables, lens, _ = self._stage_rows(
            self._verify, [s.blocks for s in seqs],
            [s.context_len for s in seqs], k1)
        toks = np.zeros((key[0], k1), np.int32)
        toks[:b, 0] = [s.out[-1] if s.out else s.prompt[-1] for s in seqs]
        toks[:b, 1:] = props
        with TRACER.span("serve.verify",
                         args={"bucket": "%dx%dx%d" % key, "rows": b}):
            (nxt,), _ = self._dispatch(
                "verify", lambda cache: exe(self._params, cache,
                                            tables, lens, toks))
        return np.asarray(nxt)[:b]

    def spec_decode(self, seqs, _times=None):
        """One speculative round over the running set: catch the draft
        pages up to the target context (a re-prefill for fresh or
        preemption-reset sequences, single catch-up steps otherwise),
        draft k proposals, verify all of them in ONE target dispatch,
        and accept the longest matching prefix + the target's
        correction token.  Returns one >= 1-token list per sequence —
        every token is a token non-speculative greedy decode would
        have produced, in order (the bit-parity contract certified by
        tools/serve_bench.py).

        Position bookkeeping: with target context c, verify row j
        writes position c+j and the accepted prefix of length m
        advances context to c+m+1.  The draft's pages are valid
        through position c+m (its proposals matched there), which is
        exactly the new context minus one when m < k — only a full
        accept leaves the draft one catch-up step behind.

        ``_times`` (DecodeLoop's phase clock) gets the draft and verify
        dispatches this round already meters as wait, the rest of the
        round as host."""
        k = self.spec_k
        d = self.draft
        t_draft = 0.0
        # catch-up: fresh/reset sequences re-prefill the draft pages
        # whole (one dispatch beats context-many single steps)
        for s in seqs:
            if s.draft_len == 0 and s.context_len > 0:
                toks = [s.token_at(i) for i in range(s.context_len)]
                t0 = time.perf_counter()
                d.prefill_tokens(toks, s.blocks)
                t_draft += time.perf_counter() - t0
                s.draft_len = s.context_len
        while True:
            behind = [s for s in seqs if s.draft_len < s.context_len]
            if not behind:
                break
            t0 = time.perf_counter()
            d.decode_step([s.blocks for s in behind],
                          [s.draft_len for s in behind],
                          [s.token_at(s.draft_len) for s in behind])
            t_draft += time.perf_counter() - t0
            for s in behind:
                s.draft_len += 1
        # propose: k chained draft steps, fused into ONE dispatch
        b = len(seqs)
        t0 = time.perf_counter()
        props = d.propose_step(
            [s.blocks for s in seqs],
            [s.draft_len for s in seqs],
            [s.out[-1] if s.out else s.prompt[-1] for s in seqs], k)
        t_draft += time.perf_counter() - t0
        for s in seqs:
            s.draft_len += k
        # verify + greedy acceptance
        t0 = time.perf_counter()
        ver = self.verify_step(seqs, props)
        t_verify = time.perf_counter() - t0
        emitted = []
        accepted = 0
        for i, s in enumerate(seqs):
            g = [int(t) for t in ver[i]]
            m = 0
            while m < k and int(props[i, m]) == g[m]:
                m += 1
            emitted.append(g[:m + 1])
            s.context_len += m + 1
            s.draft_len = s.context_len - (1 if m == k else 0)
            accepted += m
        if _batcher._METRICS_ON:
            _M_SPEC_ROUNDS.inc()
            _M_SPEC_PROPOSED.inc(b * k)
            _M_SPEC_ACCEPTED.inc(accepted)
            _M_SPEC_DRAFT_US.inc(int(t_draft * 1e6))
            _M_SPEC_VERIFY_US.inc(int(t_verify * 1e6))
            _M_DECODE_STEPS.inc()
            _M_DECODE_ROWS.inc(b)
            _M_DECODE_SLOTS.inc(b)
            _M_OCC_PCT.observe(100.0)
        if _times is not None:
            now = _clock_ns()
            busy = int((t_draft + t_verify) * 1e9)
            _times.wait += busy
            _times.host += now - _times.mark - busy
            _times.mark = now
        return emitted

    @property
    def kv_epoch(self):
        """Donation generation of the page pool (bumps on every
        prefill/decode re-bind under FLAGS_sanitizer=buffers)."""
        return self._kv_guard.epoch

    def kv_pages(self):
        """Debug access to the live page arrays: ``(kp, vp, epoch)``.
        Snapshotted under the state lock so the triple is consistent
        against a concurrent decode (never one old and one rebound
        page).  With the buffer sanitizer on, a call while a donating
        dispatch is in flight raises BufferLifetimeError; validate a
        retained handle later with ``check_kv_epoch(epoch)`` — the
        pages behind a stale epoch were donated and re-bound under the
        caller (the PR 11 rebind contract, lint-checkable via
        analysis.lifetime.check_serving_fetches)."""
        self._kv_only("kv_pages")
        with self._state_lock:
            self._kv_guard.check()
            return (*self._cache, self._kv_guard.epoch)

    def cache_state(self):
        """Debug access to the whole live cache tree, as the model's
        ``cache_spec`` names it, with the epoch it was read at:
        ``(tree, epoch)``, under ``kv_pages``'s contract."""
        with self._state_lock:
            self._kv_guard.check()
            return self._cache, self._kv_guard.epoch

    def check_kv_epoch(self, epoch):
        """Raise BufferLifetimeError when pages observed at ``epoch``
        have since been donated (re-bound) — no-op with the sanitizer
        off."""
        self._kv_guard.check(epoch=epoch, var="kv_pool")

    # -- KV migration (serving/fleet.py: prefill -> decode handoff) -----

    def export_blocks(self, blocks):
        """Host copies of the K/V pages behind ``blocks`` plus the
        epoch they were read at: ``(k_pages, v_pages, epoch)``, each
        page array ``page_spec(len(blocks))``'s.  Taken
        under the state lock with a guard check, so an export racing an
        in-flight donating dispatch is a named BufferLifetimeError (the
        MigrateKV race check), never a copy of donated buffers.  The
        returned HOST copies stay valid across later rebinds — the
        epoch rides the MigrateKV frame so the two ends can log the
        handoff, not to revalidate the copy."""
        self._kv_only("export_blocks")
        ids = np.asarray([int(b) for b in blocks], np.int32)
        with self._state_lock:
            self._kv_guard.check()
            kp, vp = self._cache
            # gather on a host VIEW of the pool (zero-copy on CPU),
            # not via jax fancy indexing — a traced gather pays full
            # dispatch overhead per export (measured 1.4 ms vs 0.02 ms
            # for the numpy take; at fleet rates that overhead was the
            # single largest migration cost).  numpy's take copies, so
            # the result is host-owned either way.
            return (np.asarray(kp).take(ids, axis=1),
                    np.asarray(vp).take(ids, axis=1),
                    self._kv_guard.epoch)

    def import_blocks(self, blocks, k_pages, v_pages):
        """Install migrated K/V pages into ``blocks`` (already
        allocated from this engine's pool by the caller).  Shapes must
        be exactly ``page_spec(len(blocks))``'s — a mismatch trips the
        buffer sanitizer by name instead of scattering garbage into
        live pages.  The write is bracketed begin/rebind like a
        dispatch, so a concurrent ``kv_pages()`` reader sees either the
        pre- or post-migration epoch, never a half-installed pool.
        Returns the post-install epoch (the MigrateKV handshake
        value)."""
        self._kv_only("import_blocks")
        ids = np.asarray([int(b) for b in blocks], np.int32)
        if (ids == 0).any():
            raise ValueError("cannot import into reserved block 0")
        want, dtype = self.page_spec(len(ids))
        k_pages = np.asarray(k_pages, dtype)
        v_pages = np.asarray(v_pages, dtype)
        if k_pages.shape != want or v_pages.shape != want:
            _san.trip("kv_pool:%s" % self.name, op="migrate_in",
                      site="import_blocks: page shape %r/%r != %r "
                           "(torn or mis-framed migration)"
                           % (k_pages.shape, v_pages.shape, want),
                      epoch=self._kv_guard.epoch)
        import jax

        if not hasattr(self, "_scatter_jit"):
            # donated scatter: the pool aliases through the update, so
            # an install costs O(migrated pages), not a copy of the
            # whole pool (compiled per distinct block count — prompts
            # cluster on a handful of counts)
            def _scatter(cache, idx, kpg, vpg):
                kp, vp = cache
                return kp.at[:, idx].set(kpg), vp.at[:, idx].set(vpg)

            self._scatter_jit = jax.jit(_scatter, donate_argnums=(0,))
        _, epoch = self._dispatch(
            "migrate_in", lambda cache: self._scatter_jit(
                cache, ids, k_pages, v_pages))
        return epoch

    def warm_role(self, role):
        """Warm only one side of the ladder — a fleet worker
        (serving/fleet.py) is prefill-only or decode-heavy, so warming
        the other ladder just slows its start.  ``role`` is
        ``'prefill'`` or ``'decode'``."""
        if role == "prefill":
            self._prefill.warm([(s,) for s in self.prefill_ladder])
        elif role == "decode":
            # the FULL (batch, block-count) grid, not just the top
            # block-count bucket: a fleet decode worker serves whatever
            # batch mix the router deals it, and every cold bucket
            # background-compiles — on a loaded single-core rig those
            # compiles steal exactly the CPU the running batches need
            # (measured: a 4-decode fleet ran at 1/3 of its steady-state
            # tok/s until the grid finished compiling mid-bench)
            self._decode.warm([(b, nb)
                               for b in self.batch_ladder
                               for nb in bucket_ladder(self.nb_top)])
            # decode workers also prefill: the router's degraded-migrate
            # fallback runs a full local generate, and the solo-floor
            # worker serves whole requests — a cold prefill bucket there
            # is a synchronous compile inside someone's TTFT
            self._prefill.warm([(s,) for s in self.prefill_ladder])
        else:
            raise ValueError("unknown role %r" % (role,))

    def free_sequence(self, seq):
        """Hand back what ``seq`` holds of the pool: its blocks and,
        for a model with per-sequence state, its state slot."""
        seq_blocks, seq.blocks = seq.blocks, []
        if seq_blocks:
            self.pool.free(seq_blocks)
        slot = getattr(seq, "slot", 0)
        if slot:
            seq.slot = 0
            self.pool.return_slot(slot)

    @property
    def device(self):
        """The jax device this tenant's params, pages and compiled
        steps live on."""
        return self._dev

    def drain_compiles(self):
        """Join every in-flight background bucket compile."""
        for cache in (self._decode, self._decode_logits, self._prefill,
                      self._prefill_cached, self._verify, self._propose):
            cache.drain()

    def close(self):
        self.drain_compiles()
        if self.draft is not None:
            self.draft.close()
        self.pool.close()

    @property
    def warm_decode_buckets(self):
        return self._decode.warm_keys


# ---------------------------------------------------------------------------
# The decode loop: Orca iteration-level scheduling
# ---------------------------------------------------------------------------

class DecodeLoop:
    """One thread per generative tenant.  An iteration ends with ONE
    decode step over the whole running set dispatched and unread (in
    flight), and the next one starts from it:

    - **ahead**, where the next step runs over the same rows and the
      host already knows it (``_may_run_ahead``): grow, dispatch step
      n+1 on step n's device tokens, THEN read step n, emit its tokens
      and retire finished sequences.  The device goes from step n
      straight into step n+1; the host's work between two steps runs
      beside it.
    - **drain**, anything else: read step n and emit first, then admit
      queued prefills the block pool can hold (TokenScheduler),
      grow/preempt for sequences crossing a block boundary and dispatch
      the step from the host's tokens.  A speculative round is read
      inside its own iteration and leaves nothing in flight.

    Tokens are delivered one a step, when the device has them, in both
    orders.  The loop must survive anything — a dead loop wedges the
    tenant with unresolved futures (the PR 9 dispatcher rule)."""

    def __init__(self, engine, queue, label=""):
        self.engine = engine
        self.queue = queue
        self.scheduler = TokenScheduler(engine.pool,
                                        engine.config.max_batch,
                                        prefix_cache=engine.prefix_cache)
        self.label = label
        self._times = _LoopTimes()
        # the decode step dispatched and not yet read, with the rows it
        # runs over in its order: (_DecodeFlight, [GenRequest]) or None
        self._flight = None
        self._stop = _san.make_event("generative.decode.stop")
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name="serve-decode-%s" % (label or id(self)))
        self._thread.start()

    def stop(self, join=True):
        self._stop.set()
        self.queue.close()
        if join:
            self._thread.join(timeout=60)

    # -- loop body ------------------------------------------------------

    def _loop(self):
        running = []
        while True:
            if not running:
                self._times.flush()
                with TRACER.span("serve.idle"):
                    req = self.queue.get(timeout=0.25)
                if req is None:
                    if self._stop.is_set() and self.queue.closed:
                        return
                    continue
                self.queue.put_front([req])   # one admission path
            try:
                self._iterate(running)
            except Exception as e:
                self._flight = None     # its rows fail with the rest
                for seq in running:
                    self.engine.free_sequence(seq)
                    if not seq.future.done():
                        seq.future.set_exception(e)
                del running[:]

    def _iterate(self, running):
        """One scheduler iteration.  Liveness of the span sites is
        asked ONCE here and handed down (``trc``), as is whether the
        ``serve_loop_*`` phase clock runs (``times``): every inner site
        tests a local, and with neither no span is built and no clock
        read.  All spans are this thread's, children of
        ``serve.iterate``."""
        trc = TRACER if TRACER.live() else None
        times = self._times if _batcher._METRICS_ON else None
        if times is not None:
            times.mark = t0 = _clock_ns()
        sp = (trc.begin("serve.iterate", None, {"rows": len(running)})
              if trc is not None else None)
        try:
            self._iterate_phases(running, trc, times)
        finally:
            if sp is not None:
                trc.end(sp)
            if times is not None:
                now = _clock_ns()
                times.host += now - times.mark
                times.wall += now - t0
                if times.wall >= _FLUSH_NS:
                    times.flush()

    def _iterate_phases(self, running, trc, times):
        # 0. the step the last iteration left in flight: the next one
        # goes out ahead of its tokens where it can, else it is drained
        flight, self._flight = self._flight, None
        if flight is not None:
            step, rows = flight
            ahead = self._may_run_ahead(rows, running, trc)
            if ahead:
                flight = (self.engine.decode_start(running, after=step,
                                                   _tr=trc),
                          list(running))
            pairs = zip(rows, self.engine.decode_read(step, _tr=trc,
                                                      _times=times))
            if len(rows) != len(running):
                # a row that finished under this step (an EOS the host
                # could not foresee) ran dead in it: its token is dropped
                live = {id(seq) for seq in running}
                pairs = (p for p in pairs if id(p[0]) in live)
            self._emit_all([(seq, int(tok)) for seq, tok in pairs],
                           running, trc, ahead=int(ahead))
            if ahead:
                # with no row left the step ahead runs dead rows only:
                # nothing of it is ever read
                self._flight = flight if running else None
                return
        # 1. admission: stream prefills into the running batch.  A
        # prefill failure (over-long prompt that slipped validation, a
        # cold-bucket compile error) fails THAT request and returns its
        # just-allocated blocks — it must not leak pool capacity or
        # take the rest of the batch down with it
        sp = trc.begin("serve.admit") if trc is not None else None
        admitted = self.scheduler.try_admit(self.queue, len(running))
        if admitted and times is not None:
            now = time.perf_counter()
            _M_ADMISSIONS.inc(len(admitted))
            _M_QUEUE_WAIT_US.inc(int(1e6 * sum(
                now - req.t_arrival for req in admitted)))
        joining = []    # (request, still needs its prefill), in order
        for req in admitted:
            if not (req.blocks and req.context_len):
                joining.append((req, True))
                continue
            # migrated-in (serving/fleet.py): the prompt's KV pages
            # are already resident and `out` holds the first token
            # — joining the batch IS the admission, no prefill
            if len(req.out) >= req.max_new or (
                    req.eos_id is not None and req.out
                    and req.out[-1] == req.eos_id):
                self.engine.free_sequence(req)
                if not req.future.done():
                    req.future.set_result(req.result())
                continue
            joining.append((req, False))
        if sp is not None:
            trc.end(sp, args={"admitted": len(admitted)})
        for req, needs_prefill in joining:
            if needs_prefill:
                if times is not None:
                    now = _clock_ns()
                    times.host += now - times.mark
                    times.mark = now
                try:
                    tok = self.engine.prefill(req, trc)
                except Exception as e:
                    self.engine.free_sequence(req)
                    if not req.future.done():
                        req.future.set_exception(e)
                    continue
                finally:
                    if times is not None:
                        now = _clock_ns()
                        times.prefill += now - times.mark
                        times.mark = now
                if self.engine.prefix_cache is not None:
                    # index the fully-written prompt blocks — the NEXT
                    # request sharing this prefix admits against them
                    self.engine.prefix_cache.insert(req)
            running.append(req)
            if needs_prefill:
                self._emit_all(((req, tok),), running, trc)
        if not running:
            return
        # this iteration runs speculatively when every sequence has
        # room for the k+1 verify positions; otherwise (tail of a
        # max_seq-bounded sequence) it falls back to plain decode —
        # the emitted tokens are identical either way, only the
        # per-iteration yield changes
        spec = self._speculative()
        if spec and any(
                s.context_len + self.engine.spec_k + 1
                > self.engine.config.max_seq for s in running):
            spec = False
        # 2. growth/preemption: every sequence writing into a fresh
        # block this iteration needs one allocated (a speculative
        # iteration writes k+1 positions, so it provisions that far)
        self._grow(running, self.engine.spec_k + 1 if spec else 1, trc)
        if not running:
            return
        # 3. one decode iteration over the whole running set — a
        # speculative round emits up to k+1 tokens per sequence; a
        # plain step stays in flight for the next iteration
        if spec:
            pairs = []
            for seq, toks in zip(list(running),
                                 self.engine.spec_decode(running, times)):
                # replay the round token-at-a-time so _emit's
                # done-checks (max_new / eos / max_seq) see the same
                # intermediate context a plain-decode iteration would
                seq.context_len -= len(toks)
                pairs.extend((seq, int(tok)) for tok in toks)
            self._emit_all(pairs, running, trc, replay=True)
        else:
            self._flight = (self.engine.decode_start(running, _tr=trc),
                            list(running))

    def _speculative(self):
        return self.engine.spec_k > 0 and self.engine.draft is not None

    def _may_run_ahead(self, rows, running, trc):
        """Whether the step after the one in flight over ``rows`` can be
        dispatched before that one is read: it runs over the same rows
        in the same order, and nothing the host has yet to learn or do
        comes between the two.  So not when the step in flight ends a
        row by count (``max_new``, ``max_seq``: known before the read;
        an EOS is not, and costs one dead row), a request waits for a
        free row, the rounds are speculative (acceptance reads the
        tokens), the loop is stopping, or a row cannot grow without a
        preemption.  Rows that could grow keep their new blocks: the
        drained step writes the same positions."""
        if (len(rows) != len(running)
                or any(a is not b for a, b in zip(rows, running))
                or self._speculative() or self._stop.is_set()
                or (len(running) < self.scheduler.max_batch
                    and len(self.queue))):
            return False
        max_seq = self.engine.config.max_seq
        if any(len(seq.out) + 1 >= seq.max_new
               or seq.context_len >= max_seq for seq in running):
            return False
        return self._grow(running, 1, trc, preempt=False)

    def _grow(self, running, need, trc, preempt=True):
        """Provision every running sequence for the ``need`` positions
        its next step writes.  With ``preempt`` a sequence the pool
        cannot grow evicts the youngest (or fails, alone in an empty
        pool); without, the first such sequence ends the attempt and
        False comes back."""
        sp = trc.begin("serve.grow") if trc is not None else None
        preempted = 0
        grown = True
        block = self.engine.config.block_size
        for seq in list(running):
            if seq not in running:
                continue
            cap = len(seq.blocks) * block
            while seq.context_len + need > cap and seq in running:
                if self.scheduler.grow(seq):
                    cap += block
                    continue
                if not preempt:
                    grown = False
                    break
                victim = self.scheduler.pick_victim(running, seq)
                if victim is None:
                    self.engine.free_sequence(seq)
                    running.remove(seq)
                    seq.future.set_exception(RuntimeError(
                        "KV block pool too small for this sequence "
                        "(%d blocks total; raise FLAGS_serve_kv_blocks "
                        "or lower max_new_tokens)" %
                        self.engine.pool.capacity))
                    break
                self._preempt(victim, running)
                preempted += 1
            if not grown:
                break
        if sp is not None:
            trc.end(sp, args={"preempted": preempted})
        return grown

    def _emit_all(self, pairs, running, trc, replay=False, ahead=0):
        """Emit ``(sequence, token)`` pairs under ONE ``serve.emit``
        span (not one a token).  The span's cid
        is the first finished request's id (``finished_ids`` has all),
        so a request's prefill and its finishing emit share a cid.
        ``replay``: a speculative round's tokens, each advancing the
        context by one; a sequence that finishes mid-round drops the
        rest.  ``ahead``: how many steps past these tokens' own the
        sequences' contexts already stand (1 where the next step went
        out before these were read)."""
        sp = trc.begin("serve.emit") if trc is not None else None
        finished = []
        tokens = 0
        for seq, tok in pairs:
            if replay:
                if seq not in running:
                    continue    # finished mid-round; discard the rest
                seq.context_len += 1
            tokens += 1
            if self._emit(seq, tok, running, ahead):
                finished.append(seq.rid)
        if sp is not None:
            trc.end(sp, cid=finished[0] if finished else None,
                    args={"tokens": tokens,
                          "finished": len(finished),
                          "finished_ids": finished})

    def _emit(self, seq, tok, running, ahead=0):
        """Deliver one token; True when it finished the sequence.  The
        done-checks see the context of the step that made the token:
        ``ahead`` steps behind where the sequence stands."""
        now = time.perf_counter()
        metrics_on = _batcher._METRICS_ON
        if seq.t_first is None:
            seq.t_first = now
            # serve_ttft_ms is observed at COMPLETION, not here: a
            # preemption resets t_first and re-emits a first token, and
            # observing both would double-count the request with an
            # artificially low sample for tokens that were never
            # delivered
        else:
            itl = (now - seq.t_last) * 1e3
            seq.itl_ms.append(itl)
            if metrics_on:
                _M_ITL.observe(itl)
        seq.t_last = now
        seq.out.append(tok)
        if metrics_on:
            _M_TOKENS.inc()
        done = (len(seq.out) >= seq.max_new
                or (seq.eos_id is not None and tok == seq.eos_id)
                or seq.context_len - ahead >= self.engine.config.max_seq)
        if done:
            if seq in running:
                running.remove(seq)
            self.engine.free_sequence(seq)
            if metrics_on:
                _M_GEN_MS.observe((now - seq.t_arrival) * 1e3)
                _M_TTFT.observe((seq.t_first - seq.t_arrival) * 1e3)
            seq.future.set_result(seq.result())
        return done

    def _preempt(self, victim, running):
        """Recompute-style eviction: free the victim's blocks, requeue
        it at the FRONT (it keeps its arrival stamp and re-admits
        before newer requests), and let greedy determinism regenerate
        its tokens on re-admission."""
        running.remove(victim)
        self.engine.free_sequence(victim)
        victim.reset()
        victim.preempted += 1
        self.engine.pool.note_preemption()
        self.queue.put_front([victim])
