"""What a decode step costs, counted by DecodeLoop where it happens: the
interval from one read to the next (plain or carrying a prompt), a
dispatch that went out ahead after the step before had already ended
(``late``), the time inside the runtime call, and a prompt's wait from
admission to the step that carries it.  The counts ride the loop's
flushed record (``_LoopTimes``): read only once the loop has gone idle
or stopped."""
import time
from concurrent.futures import Future

import numpy as np
import pytest
from jax._src.array import ArrayImpl

from paddle_tpu.observability import metrics
from paddle_tpu.observability.trace import TRACER
from paddle_tpu.serving import GenerativeEngine, InferenceServer, tiny_lm
from paddle_tpu.serving import batcher as _batcher
from paddle_tpu.serving import generative
from paddle_tpu.serving.batcher import RequestQueue
from paddle_tpu.serving.generative import (DecodeLoop, GenRequest,
                                           _DecodeFlight, _LoopTimes)

# wide enough that a step takes a millisecond or more on a CPU: the
# host's own microseconds stay small beside it
WIDE = dict(vocab=256, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
            block_size=8, max_blocks=8, max_batch=4)
NEW = ("serve_step_interval_us_total", "serve_step_reads_total",
       "serve_ride_interval_us_total", "serve_ride_reads_total",
       "serve_decode_late_total", "serve_dispatch_us_total",
       "serve_ride_wait_us_total")
MOVED = ("serve_decode_steps_total", "serve_decode_ahead_total",
         "serve_decode_rows_total", "serve_decode_slots_total",
         "serve_decode_pages_total", "serve_decode_page_slots_total",
         "serve_prefill_rides_total", "serve_tokens_total")


@pytest.fixture(scope="module")
def model():
    return tiny_lm(7, **WIDE)


def _counters():
    return {k: v["value"] for k, v in metrics.snapshot().items()
            if v.get("type") == "counter" and k.startswith("serve_")}


def _settled():
    """The counters once they no longer move: the loop has gone idle
    and flushed its record."""
    return _delta({})


def _delta(c0):
    """The counters' change since ``c0``, once the loop has gone idle
    and flushed its record (they no longer move)."""
    last = None
    for _ in range(200):
        c1 = _counters()
        d = {k: c1[k] - c0.get(k, 0) for k in c1}
        if d == last:
            return d
        last = d
        time.sleep(0.05)
    raise AssertionError("the serve counters never settled")


def _prompts(seed, n, lo=3, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 256, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


class _Loop:
    """An engine and its DecodeLoop, with every decode read logged:
    whether the step read carried a prompt."""

    def __init__(self, model):
        self.engine = GenerativeEngine(*model, kv_blocks=64, warm=False)
        self.reads = []
        read = self.engine.decode_read

        def logged(flight, **kw):
            out = read(flight, **kw)
            self.reads.append(bool(flight.riding))
            return out

        self.engine.decode_read = logged
        self.queue = RequestQueue()
        self.loop = DecodeLoop(self.engine, self.queue, label="counters")

    def serve(self, requests):
        # all at once: the loop admits them together
        self.queue.put_front(list(requests))
        return [r.future.result(300)["tokens"] for r in requests]

    def close(self):
        self.loop.stop()
        self.engine.close()


def _requests(prompts, lengths):
    return [GenRequest(p, n, None, Future()) for p, n in zip(prompts, lengths)]


# ---------------------------------------------------- the step intervals

def test_intervals_lie_inside_the_wall_and_hold_each_steps_wait(model):
    """Queued before the loop starts: the first prompt meets no row and
    is prefilled, the next rides the first decode step (a ride but no
    interval: no read came before it), and the rest ride as rows end.
    Every later read closes one interval, plain or riding; together they
    lie inside the loop's wall, and each holds its step's wait.  Every
    counted step spent time inside the runtime call."""
    prompts = _prompts(3, 6)
    lengths = [12, 7, 9, 5, 11, 6]
    srv = _Loop(model)
    try:
        srv.serve(_requests(prompts, lengths))     # compiles the buckets
        srv.engine.drain_compiles()
        del srv.reads[:]
        c0 = _settled()
        TRACER.clear()
        TRACER.enable()
        try:
            srv.serve(_requests(prompts, lengths))
            srv.loop.stop()
        finally:
            TRACER.disable()
        d = _delta(c0)
        spans = TRACER.completed()
        TRACER.clear()
    finally:
        srv.close()
    reads = srv.reads
    assert reads[0], "the first decode step carries the second prompt"
    assert d["serve_prefill_rides_total"] == len(prompts) - 1
    assert d["serve_ride_reads_total"] == sum(reads[1:])
    assert d["serve_step_reads_total"] == len(reads) - 1 - sum(reads[1:])
    assert d["serve_step_reads_total"] > 0 and d["serve_ride_reads_total"] > 0
    intervals = (d["serve_step_interval_us_total"]
                 + d["serve_ride_interval_us_total"])
    assert 0 < intervals <= d["serve_loop_wall_us_total"]
    waits = [s for s in spans if s["name"] == "serve.decode.wait"]
    assert len(waits) == len(reads)
    timed = [s for s in waits if "interval_us" in (s.get("args") or ())]
    assert len(timed) == len(reads) - 1 and timed[0] is waits[1]
    assert sum(s["args"]["interval_us"] for s in timed) == pytest.approx(
        intervals, abs=len(timed))
    for s in timed:
        # the span closes some microseconds after the read's clock
        assert s["args"]["interval_us"] >= s["dur_us"] - 100, s
    # every counted step spent time inside the runtime call
    decodes = [s for s in spans if s["name"] == "serve.decode"]
    assert len(decodes) == d["serve_decode_steps_total"]
    assert all(s["args"]["dispatch_us"] > 0 for s in decodes)
    assert d["serve_dispatch_us_total"] >= len(decodes)


def test_the_loop_going_idle_breaks_the_run(model):
    """Two requests one after the other: the loop goes idle between
    them, so the first read of the second starts a run of its own."""
    srv = _Loop(model)
    try:
        (prompt,) = _prompts(5, 1)
        srv.serve(_requests([prompt], [8]))
        srv.engine.drain_compiles()
        del srv.reads[:]
        c0 = _settled()
        srv.serve(_requests([prompt], [8]))
        srv.serve(_requests([prompt], [8]))
        srv.loop.stop()
        d = _delta(c0)
    finally:
        srv.close()
    # a prefill, then seven plain steps a request: six intervals each
    assert srv.reads == [False] * 14
    assert d["serve_step_reads_total"] == 12
    assert d["serve_ride_reads_total"] == 0


# --------------------------------------------------- late and dispatched

def _engine_rows(eng, prompts, n_new):
    reqs = [GenRequest(p, n_new, None, Future()) for p in prompts]
    for r in reqs:
        r.blocks = eng.pool.alloc(eng.pool.blocks_for(len(r.prompt) + n_new))
        r.out.append(eng.prefill(r))
    return reqs


def test_a_dispatch_behind_a_finished_step_is_late(model, monkeypatch):
    """Ahead of a flight the device has already finished, the dispatch
    is late and its span says so; ahead of one still running it is not;
    a dispatch from the host's tokens is not asked (it drained)."""
    eng = GenerativeEngine(*model, kv_blocks=64, warm=False)
    reqs = _engine_rows(eng, _prompts(9, 2), 8)
    asked = []
    ready = ArrayImpl.is_ready

    def is_ready(self):
        asked.append(self)
        return ready(self)

    monkeypatch.setattr(ArrayImpl, "is_ready", is_ready)
    times = _LoopTimes()
    TRACER.clear()
    TRACER.enable()
    try:
        first = eng.decode_start(reqs, _tr=TRACER, _times=times)  # drained
        assert asked == [] and times.late == 0
        first.nxt.block_until_ready()
        second = eng.decode_start(reqs, after=first, _tr=TRACER,
                                  _times=times)
        assert len(asked) == 1 and times.late == 1 and times.ahead == 1
        # a step the device is still running: nothing to wait for
        monkeypatch.setattr(ArrayImpl, "is_ready", lambda self: False)
        third = eng.decode_start(reqs, after=second, _tr=TRACER,
                                 _times=times)
        assert times.late == 1 and times.ahead == 2 and times.steps == 3
        eng.decode_read(third)
    finally:
        TRACER.disable()
        for r in reqs:
            eng.free_sequence(r)
        eng.close()
    decodes = [s for s in TRACER.completed() if s["name"] == "serve.decode"]
    TRACER.clear()
    assert [(s["args"]["ahead"], s["args"]["late"]) for s in decodes] == [
        (0, 0), (1, 1), (1, 0)]
    # every counted step spent time inside the runtime call
    assert all(s["args"]["dispatch_us"] > 0 for s in decodes)
    assert times.dispatch >= 1000 * sum(s["args"]["dispatch_us"]
                                        for s in decodes)


# -------------------------------------------------------- the wait to ride

def _ride_wait(srv, running, group):
    """The requests of ``running`` decode together; once a plain step
    over all of them has gone out ahead, those of ``group`` arrive
    together.  Returns the counters' change and the wait to ride of the
    group alone, in micros (what the loop's record and the counter held
    when the group arrived is taken off)."""
    start = srv.engine.decode_start
    counter = metrics.counter("serve_ride_wait_us_total")
    base = []

    def waited_ns():
        return 1000 * counter.value + srv.loop._times.ride_wait

    def hooked(seqs, after=None, rider=None, **kw):
        flight = start(seqs, after=after, rider=rider, **kw)
        if (after is not None and rider is None and not base
                and sum(s is not None for s in seqs) == len(running)):
            base.append(waited_ns())        # on the loop's own thread
            srv.queue.put_front(list(group))
        return flight

    srv.engine.decode_start = hooked
    try:
        c0 = _settled()
        srv.serve(running)
        for r in group:
            r.future.result(300)
        d = _delta(c0)
        return d, (waited_ns() - base[0]) / 1000
    finally:
        del srv.engine.decode_start


def test_a_prompt_that_rides_at_once_waits_nothing_and_one_behind_waits_a_step(
        monkeypatch):
    """Five rows run in a batch of eight, so a carrying step goes out
    ahead like a plain one.  Each read of a step's tokens is made to
    take 20 ms more, as a step of a model that size would on a chip:
    against it the host's own milliseconds on a shared CPU are small."""
    read = _DecodeFlight.read

    def slow(flight):
        if flight.host is None:
            time.sleep(0.02)
        return read(flight)

    monkeypatch.setattr(_DecodeFlight, "read", slow)
    prompts = _prompts(17, 7, lo=6, hi=10)
    srv = _Loop(tiny_lm(7, **dict(WIDE, max_batch=8)))
    try:
        def run(n):
            return _ride_wait(srv, _requests(prompts[:5], [24] * 5),
                              _requests(prompts[5:5 + n], [10, 10]))

        run(2)                          # compiles what both runs take
        srv.engine.drain_compiles()
        (alone, at_once), (behind, both) = run(1), run(2)
    finally:
        srv.close()
    # four of the five running rows rode in, and then the newcomers
    assert alone["serve_prefill_rides_total"] == 4 + 1
    assert behind["serve_prefill_rides_total"] == 4 + 2
    step = (alone["serve_step_interval_us_total"]
            / alone["serve_step_reads_total"])
    ride = (behind["serve_ride_interval_us_total"]
            / behind["serve_ride_reads_total"])
    assert step >= 20000
    # admitted in front of the read, a newcomer rides the step
    # dispatched in the same iteration: well under a step
    assert 0 <= at_once < 0.5 * step, (at_once, step)
    # a second one waits for the iteration after, the read of a whole
    # step among it: about one step
    waited = both - at_once
    assert 20000 <= waited <= 3 * max(step, ride), (waited, step, ride)


# ------------------------------------------------------------ metrics off

def test_metrics_off_counts_nothing_and_reads_no_clock(model, monkeypatch):
    clock = generative._clock_ns
    reads = []
    asked = []

    def counted():
        reads.append(1)
        return clock()

    def is_ready(self):
        asked.append(1)
        return True

    cfg, params = tiny_lm(7, **dict(WIDE, d_model=32, d_ff=64, n_layers=2))
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=32)
        srv.generate("g", [1, 2, 3], max_new_tokens=4).result(180)
        eng.drain_compiles()
        c0 = _settled()
        prev = _batcher.set_metrics_enabled(False)
        monkeypatch.setattr(generative, "_clock_ns", counted)
        monkeypatch.setattr(ArrayImpl, "is_ready", is_ready)
        try:
            futs = [srv.generate("g", [1, 2, 3 + i], max_new_tokens=8)
                    for i in range(3)]
            for f in futs:
                assert len(f.result(180)["tokens"]) == 8
            d = _delta(c0)
        finally:
            _batcher.set_metrics_enabled(prev)
            monkeypatch.undo()
    assert reads == [] and asked == []
    for name in NEW + MOVED:
        assert d.get(name, 0) == 0, name
