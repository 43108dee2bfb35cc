"""Paged KV cache accounting: the refcounted block pool behind
generative decode (ISSUE 11 tentpole a; refcounts/COW ISSUE 19).

The vLLM/PagedAttention memory design, TPU-native: the device holds ONE
pool of fixed-size KV blocks per tenant (``generative.GenerativeEngine``
owns the actual [L, N, bs, H, D] page arrays, donated through every
prefill/decode dispatch so they never round-trip the host — the PR 2
prepared-program contract applied to serving state).  This module is
the host-side ledger over that pool: per-block REFCOUNTS, a free list,
an LRU of refcount-zero cached blocks, and the always-on accounting:

- ``serve_kv_blocks_used`` / ``serve_kv_blocks_total`` gauges — live
  pool pressure.  Refcount semantics (ISSUE 19 satellite): a block
  shared by N sequences counts ONCE in used, and a decref that leaves
  the refcount nonzero is not a free;
- ``serve_kv_blocks_shared`` — blocks currently referenced by more
  than one owner (prefix sharing at work);
- ``serve_kv_blocks_cached`` — refcount-zero blocks parked in the
  prefix-cache LRU (reusable, reclaimed under allocation pressure);
- ``serve_kv_prefix_hits`` — prefix-index lookups that shared at
  least one cached block (plus ``serve_prefix_tokens_*`` counters for
  the token-level hit rate);
- ``serve_kv_cow_copies_total`` — shared blocks copied before a
  mid-block write (copy-on-write);
- ``serve_kv_alloc_failures_total`` / ``serve_kv_preemptions_total`` —
  as before.

Ownership protocol (ISSUE 19): ``alloc`` hands out blocks at refcount
1; ``share`` takes one more reference (reviving a parked refcount-zero
block from the cached LRU); ``free`` DROPS one reference — the block
returns to circulation only at refcount zero, parking in the cached
LRU when the prefix index marked it cacheable, else going straight to
the free list.  ``cow`` is the mid-block-write escape: a private
replacement block is allocated and the shared reference dropped (the
caller copies the device pages).  Under ``FLAGS_sanitizer=buffers`` a
decref without a matching reference — the refcount generalization of
double-free — trips the sanitizer by block id.

Block 0 is RESERVED as the padding scratch block: bucket-padding rows
of a decode batch point every block-table slot at it and write their
(discarded) K/V there, so a padded dispatch never touches a live
sequence's blocks.

**State slots** (the second kind of state in this one manager): a
model with recurrent layers carries per-sequence state of a fixed size
(a conv window, an SSM state) beside its pages.  Such a pool is built
with ``state_slots`` > 0 and hands each admitted sequence ONE slot
(``take_slot``), held from admission to finish or preemption
(``return_slot``); a row's index in the batch moves when a neighbour
finishes, its slot does not.  Slot 0 is reserved like block 0: padding
and dead rows aim their state reads and writes at it.
``serve_state_slots_held`` is the live count,
``serve_state_slot_waits_total`` the admissions that found none free.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

from paddle_tpu.core import sanitizer as _san
from paddle_tpu.observability import metrics as _metrics

__all__ = ["BlockPool"]

M_USED = _metrics.gauge(
    "serve_kv_blocks_used",
    "KV cache blocks currently referenced by live sequences (a shared "
    "block counts once)")
M_TOTAL = _metrics.gauge(
    "serve_kv_blocks_total",
    "KV cache blocks in the pool (excludes the reserved padding block)")
M_SHARED = _metrics.gauge(
    "serve_kv_blocks_shared",
    "KV cache blocks referenced by more than one sequence (prefix "
    "sharing)")
M_CACHED = _metrics.gauge(
    "serve_kv_blocks_cached",
    "refcount-zero KV blocks parked in the prefix-cache LRU, "
    "reclaimable under allocation pressure")
M_PREFIX_HITS = _metrics.gauge(
    "serve_kv_prefix_hits",
    "prefix-index lookups that shared at least one cached block")
M_PREFIX_TOK = _metrics.counter(
    "serve_prefix_tokens_total",
    "prompt tokens looked up in the prefix index")
M_PREFIX_TOK_CACHED = _metrics.counter(
    "serve_prefix_tokens_cached_total",
    "prompt tokens served from shared cached blocks instead of "
    "recomputed by prefill")
M_COW = _metrics.counter(
    "serve_kv_cow_copies_total",
    "shared blocks copied before a mid-block write (copy-on-write)")
M_ALLOC_FAIL = _metrics.counter(
    "serve_kv_alloc_failures_total",
    "block allocations (admission or mid-decode growth) the pool could "
    "not satisfy")
M_PREEMPT = _metrics.counter(
    "serve_kv_preemptions_total",
    "sequences evicted (blocks freed, request requeued) because the "
    "block pool was exhausted")
M_SLOTS_HELD = _metrics.gauge(
    "serve_state_slots_held",
    "per-sequence state slots (recurrent state of a model that has it) "
    "held by admitted sequences; the reserved scratch slot not counted")
M_SLOT_WAITS = _metrics.counter(
    "serve_state_slot_waits_total",
    "admissions that found no free state slot and went back to the "
    "queue front")


# live pools; the process gauges are recomputed ABSOLUTELY from this
# registry (never incremented by deltas) so a mid-run
# metrics.zero_all() — the bench/test rebasing pattern — self-heals at
# the next allocation instead of leaving the gauges negative forever
_LIVE = []
_LIVE_LOCK = threading.Lock()


def _refresh_gauges():
    with _LIVE_LOCK:
        pools = list(_LIVE)
    used = shared = cached = hits = total = slots = 0
    for p in pools:
        total += p.capacity
        u, s, c, h, held = p._gauge_snapshot()
        slots += held
        used += u
        shared += s
        cached += c
        hits += h
    M_TOTAL.set(total)
    M_USED.set(used)
    M_SHARED.set(shared)
    M_CACHED.set(cached)
    M_PREFIX_HITS.set(hits)
    M_SLOTS_HELD.set(slots)


class BlockPool:
    """Refcounted free-list allocator over ``num_blocks`` fixed-size KV
    blocks.

    Thread-safe; the gauges track the process-wide combined pressure
    of every live pool (multi-tenant processes read the sum, like
    every serve_* metric)."""

    def __init__(self, num_blocks, block_size, register=True,
                 state_slots=0):
        if num_blocks < 2:
            raise ValueError("kv pool needs >= 2 blocks (one is the "
                             "reserved padding block)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # block 0 reserved: the padding scratch target
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._ref = {}                 # block id -> refcount (> 0)
        self._cached = OrderedDict()   # refcount-zero LRU (oldest first)
        self._cacheable = set()        # park in _cached at refcount 0
        self._evict_cb = None          # prefix index invalidation hook
        self._prefix_hits = 0
        # slot 0 reserved: the scratch target of padding and dead rows
        self.state_slots = int(state_slots)
        self._free_slots = list(range(self.state_slots - 1, 0, -1))
        self._lock = _san.make_lock("serve.kv_pool")
        if register:
            # register=False: a shadow pool (the speculative draft
            # engine mirrors the target's block ids and never
            # allocates) — counting its capacity in serve_kv_blocks_*
            # would double every spec tenant's apparent pool
            with _LIVE_LOCK:
                _LIVE.append(self)
        _refresh_gauges()

    # -- gauge feed (called by _refresh_gauges with no pool lock held;
    # the reads are a consistent-enough snapshot for pressure gauges
    # and the absolute recompute self-heals next refresh) --------------

    def _gauge_snapshot(self):
        with self._lock:
            used = len(self._ref)
            shared = sum(1 for r in self._ref.values() if r >= 2)
            cached = len(self._cached)
            hits = self._prefix_hits
            slots = max(0, self.state_slots - 1 - len(self._free_slots))
        return used, shared, cached, hits, slots

    @property
    def capacity(self):
        return self.num_blocks - 1

    @property
    def free_blocks(self):
        """Blocks allocatable right now: the free list PLUS the
        refcount-zero cached LRU (reclaimed under pressure)."""
        with self._lock:
            return len(self._free) + len(self._cached)

    @property
    def used_blocks(self):
        """Blocks referenced by at least one live owner — refcount
        semantics: a block shared N ways counts once, and a parked
        (refcount-zero, cached) block is NOT used."""
        with self._lock:
            return len(self._ref)

    @property
    def cached_blocks(self):
        with self._lock:
            return len(self._cached)

    def ref(self, block):
        """Current refcount of ``block`` (0 when parked or free)."""
        with self._lock:
            return self._ref.get(int(block), 0)

    def blocks_for(self, tokens):
        """Blocks needed to hold ``tokens`` positions."""
        return max(1, -(-int(tokens) // self.block_size))

    def set_evict_callback(self, cb):
        """``cb(block_id) -> iterable of descendant block ids`` called
        when a parked cached block is reclaimed by allocation pressure
        — the prefix index drops the block's node and returns any
        cached blocks that became unreachable with it (they move to
        the free list too).  Called UNDER the pool lock: the callback
        must not call back into the pool."""
        with self._lock:
            self._evict_cb = cb

    def set_cacheable(self, blocks, on=True):
        """Mark ``blocks`` to park in the cached LRU (instead of the
        free list) when their refcount reaches zero — the prefix
        index's retention bit."""
        blocks = [int(b) for b in blocks]
        with self._lock:
            if on:
                self._cacheable.update(blocks)
            else:
                for b in blocks:
                    self._cacheable.discard(b)
                    # an un-indexed parked block is plain free space
                    if b in self._cached:
                        del self._cached[b]
                        self._free.append(b)
        _refresh_gauges()

    # -- allocation ----------------------------------------------------

    def _evict_locked(self, n):
        """Reclaim up to ``n`` parked blocks, LRU first, into _free.
        Returns the number reclaimed."""
        got = 0
        while got < n and self._cached:
            b, _ = self._cached.popitem(last=False)
            self._cacheable.discard(b)
            self._free.append(b)
            got += 1
            if self._evict_cb is not None:
                for d in (self._evict_cb(b) or ()):
                    d = int(d)
                    if d in self._cached:
                        del self._cached[d]
                        self._cacheable.discard(d)
                        self._free.append(d)
                        got += 1
        return got

    def alloc(self, n):
        """``n`` block ids at refcount 1, or None (counted) when the
        pool cannot satisfy the request even after reclaiming parked
        cached blocks — the caller decides between waiting, requeueing,
        and preempting (batcher.TokenScheduler)."""
        n = int(n)
        with self._lock:
            if n > len(self._free) + len(self._cached):
                ok = False
            else:
                if n > len(self._free):
                    self._evict_locked(n - len(self._free))
                out = [self._free.pop() for _ in range(n)]
                for b in out:
                    self._ref[b] = 1
                ok = True
        if not ok:
            M_ALLOC_FAIL.inc()
            return None
        _refresh_gauges()
        return out

    def share(self, blocks):
        """Take one more reference on each of ``blocks`` (the prefix
        hit path).  A parked refcount-zero block is revived to
        refcount 1.  Returns True on success; False — with every
        reference taken by this call rolled back — when any block is
        not live or parked (it was reclaimed between the index lookup
        and the share: the caller treats the lookup as a miss)."""
        blocks = [int(b) for b in blocks]
        if any(b == 0 for b in blocks):
            raise ValueError("block 0 is the reserved padding block; "
                             "it is never shared")
        taken = []
        ok = True
        with self._lock:
            for b in blocks:
                if b in self._ref:
                    self._ref[b] += 1
                elif b in self._cached:
                    del self._cached[b]
                    self._ref[b] = 1
                else:
                    ok = False
                    break
                taken.append(b)
            if not ok:
                for b in taken:
                    self._ref[b] -= 1
                    if self._ref[b] == 0:
                        del self._ref[b]
                        self._cached[b] = None
        _refresh_gauges()
        return ok

    def cow(self, block, copy=None):
        """Copy-on-write for a shared ``block`` about to be written
        mid-block: allocate a private replacement (counted in
        serve_kv_cow_copies_total), run ``copy(src, dst)`` — the
        device-page copy, GenerativeEngine.copy_block — and only THEN
        drop the caller's reference on the shared original, so the
        source pages cannot be reclaimed out from under the copy.
        Returns the replacement id, or None when the pool cannot supply
        one (the caller preempts or requeues — its reference on the
        original is NOT dropped)."""
        got = self.alloc(1)
        if got is None:
            return None
        if copy is not None:
            try:
                copy(int(block), got[0])
            except Exception:
                self.free(got)
                raise
        M_COW.inc()
        self.free([block])
        return got[0]

    def free(self, blocks):
        """Drop one reference per listed block.  A block returns to
        circulation only at refcount zero — to the cached LRU when the
        prefix index marked it cacheable, else to the free list.
        Dropping a reference that does not exist (the refcount
        generalization of double-free) trips the sanitizer under
        FLAGS_sanitizer=buffers and is ignored otherwise."""
        blocks = [int(b) for b in blocks]
        if not blocks:
            return
        # validate BEFORE mutating: a partial decref on the guard
        # raising mid-loop would desync the ledger from the gauge —
        # the caller bug stays a caller bug
        if any(b == 0 for b in blocks):
            raise ValueError("block 0 is the reserved padding block; "
                             "it is never allocated")
        with self._lock:
            if _san.buffers_on():
                # a decref without a live reference is the refcount
                # form of double-donation: two owners each think they
                # returned the buffer — the next alloc would hand one
                # sequence's live pages to another.  Checked and
                # applied under ONE lock hold so two racing frees of
                # the same last reference cannot both pass.  O(n)
                # bookkeeping paid only in sanitizer mode.
                avail = dict(self._ref)
                for b in blocks:
                    if avail.get(b, 0) <= 0:
                        _san.trip("kv_block:%d" % b, op="free",
                                  site="BlockPool(block_size=%d): "
                                       "decref without a reference"
                                       % self.block_size)
                    avail[b] = avail.get(b, 0) - 1
            for b in blocks:
                r = self._ref.get(b, 0)
                if r <= 0:
                    continue          # unmatched decref (tripped above)
                if r > 1:
                    self._ref[b] = r - 1
                    continue          # decref-to-nonzero is not a free
                del self._ref[b]
                if b in self._cacheable:
                    self._cached[b] = None   # park, most-recent end
                else:
                    self._free.append(b)
        _refresh_gauges()

    def note_prefix_lookup(self, tokens, tokens_cached):
        """Prefix-index accounting: one lookup over ``tokens`` prompt
        tokens of which ``tokens_cached`` came from shared blocks."""
        M_PREFIX_TOK.inc(int(tokens))
        if tokens_cached > 0:
            M_PREFIX_TOK_CACHED.inc(int(tokens_cached))
            with self._lock:
                self._prefix_hits += 1
        _refresh_gauges()

    def note_preemption(self):
        M_PREEMPT.inc()

    # -- per-sequence state slots ---------------------------------------

    @property
    def slots_held(self):
        if not self.state_slots:
            return 0
        with self._lock:
            return self.state_slots - 1 - len(self._free_slots)

    def take_slot(self):
        """A free state slot (>= 1) for a sequence being admitted, or
        None (counted) when every one is held."""
        with self._lock:
            slot = self._free_slots.pop() if self._free_slots else None
        if slot is None:
            M_SLOT_WAITS.inc()
        else:
            _refresh_gauges()
        return slot

    def return_slot(self, slot):
        """Hand ``slot`` back (finish, preemption, a failed admission).
        What the device holds in it is dead: the next owner's prefill
        overwrites all of it."""
        slot = int(slot)
        with self._lock:
            if not 0 < slot < self.state_slots or slot in self._free_slots:
                raise ValueError("state slot %d is not held" % slot)
            self._free_slots.append(slot)
        _refresh_gauges()

    def close(self):
        """Retire the pool from the process gauges (tenant unload) —
        without this, every load/unload cycle would leave phantom
        capacity in serve_kv_blocks_total."""
        with self._lock:
            self._free = []
            self._ref = {}
            self._cached = OrderedDict()
            self._cacheable = set()
            self._prefix_hits = 0
            self.num_blocks = 1
            self.state_slots = 0
            self._free_slots = []
        with _LIVE_LOCK:
            if self in _LIVE:
                _LIVE.remove(self)
        _refresh_gauges()

    def __repr__(self):
        return "BlockPool(%d/%d free, %d cached, block_size=%d)" % (
            self.free_blocks, self.capacity, self.cached_blocks,
            self.block_size)
