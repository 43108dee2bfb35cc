"""Failure-path machinery: RetryPolicy, FaultInjector, sender-dedup'd
round replay, durable pserver checkpoints, trainer-lease expiry, the
barrier watchdog, and (slow) full process-kill recovery runs.

Reference analogs: go/pserver/client retry + etcd re-resolution,
go/master/service.go:368 checkTimeout, listen_and_serv sync loop.
"""
import multiprocessing as mp
import os
import socket
import threading
import time

import numpy as np
import pytest

import dist_train_helpers as H
from paddle_tpu.core.scope import Scope
from paddle_tpu.distributed.resilience import (DeadlineExceeded,
                                               EndpointResolver,
                                               FaultInjector,
                                               InjectedFault, RetryPolicy,
                                               WatchdogTimeout,
                                               install_faults)
from paddle_tpu.distributed.rpc import (RPCClient, VariableServer,
                                        _dec_tensor, _enc_tensor,
                                        _pack_round_sender,
                                        _unpack_round_sender)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _clean_faults():
    """Never leak an injector (or the RPCClient singleton's step) into
    another test."""
    install_faults("")
    yield
    install_faults("")
    RPCClient.reset()


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------

def test_retry_backoff_exponential_capped_jittered():
    import random

    p = RetryPolicy(base_backoff=0.1, max_backoff=1.0, multiplier=2.0,
                    jitter=0.5, rng=random.Random(0))
    raws = [0.1, 0.2, 0.4, 0.8, 1.0, 1.0]  # capped at max_backoff
    for attempt, raw in enumerate(raws, start=1):
        b = p.backoff(attempt)
        assert 0.5 * raw <= b <= 1.5 * raw


def test_retry_classification():
    import grpc

    assert RetryPolicy.is_retryable(ConnectionError("x"))
    assert RetryPolicy.is_retryable(TimeoutError("x"))
    assert RetryPolicy.is_retryable(InjectedFault("p", "drop"))
    assert not RetryPolicy.is_retryable(
        InjectedFault("p", "error", retryable=False))
    assert not RetryPolicy.is_retryable(ValueError("x"))
    assert not RetryPolicy.is_retryable(TypeError("x"))
    # a blown deadline must not be retried by an outer policy
    assert not RetryPolicy.is_retryable(DeadlineExceeded("x"))

    class FakeRpcError(grpc.RpcError):
        def __init__(self, c):
            self._c = c

        def code(self):
            return self._c

    assert RetryPolicy.is_retryable(
        FakeRpcError(grpc.StatusCode.UNAVAILABLE))
    assert RetryPolicy.is_retryable(
        FakeRpcError(grpc.StatusCode.DEADLINE_EXCEEDED))
    assert not RetryPolicy.is_retryable(
        FakeRpcError(grpc.StatusCode.INVALID_ARGUMENT))
    assert not RetryPolicy.is_retryable(
        FakeRpcError(grpc.StatusCode.UNKNOWN))


def test_retry_run_retries_until_success():
    p = RetryPolicy(deadline=5.0, call_timeout=1.0, base_backoff=0.01,
                    max_backoff=0.02)
    calls = {"n": 0}
    retries = []

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("transient %d" % calls["n"])
        return "ok"

    assert p.run(fn, on_retry=lambda e, a: retries.append(a)) == "ok"
    assert calls["n"] == 3
    assert retries == [1, 2]


def test_retry_run_deadline_exceeded_names_operation():
    p = RetryPolicy(deadline=0.2, base_backoff=0.05, max_backoff=0.05)
    with pytest.raises(DeadlineExceeded) as ei:
        p.run(lambda: (_ for _ in ()).throw(ConnectionError("down")),
              describe="GetVariable(127.0.0.1:9)")
    assert "GetVariable(127.0.0.1:9)" in str(ei.value)
    assert ei.value.attempts >= 1
    assert isinstance(ei.value.last_error, ConnectionError)


def test_retry_run_fatal_surfaces_immediately():
    p = RetryPolicy(deadline=10.0)
    with pytest.raises(ValueError):
        p.run(lambda: (_ for _ in ()).throw(ValueError("bug")))


def test_retry_run_attempt_cap():
    p = RetryPolicy(deadline=60.0, base_backoff=0.001, max_backoff=0.001,
                    max_attempts=3)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise ConnectionError("x")

    with pytest.raises(DeadlineExceeded):
        p.run(fn)
    assert calls["n"] == 3


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------

def test_fault_spec_parse_and_limits():
    inj = FaultInjector("a:drop:1.0:2,b:delay:0.01,c:error:1.0")
    for _ in range(2):
        with pytest.raises(InjectedFault) as ei:
            inj.fire("a")
        assert ei.value.retryable
    inj.fire("a")  # limit=2 exhausted: no-op now
    t0 = time.time()
    inj.fire("b")
    assert time.time() - t0 >= 0.009
    with pytest.raises(InjectedFault) as ei:
        inj.fire("c")
    assert not ei.value.retryable
    assert inj.stats == {"a": 2, "b": 1, "c": 1}
    inj.fire("unknown_point")  # unconfigured points are free


def test_fault_spec_rejects_garbage():
    with pytest.raises(ValueError):
        FaultInjector("send_grad:drop")          # missing value
    with pytest.raises(ValueError):
        FaultInjector("send_grad:explode:1.0")   # unknown action


def test_fault_spec_probability_zero_never_fires():
    inj = FaultInjector("a:drop:0.0")
    for _ in range(50):
        inj.fire("a")
    assert inj.stats == {}


# ---------------------------------------------------------------------------
# Wire format: (round, sender) packing + read-only decode regression
# ---------------------------------------------------------------------------

def test_pack_round_sender_roundtrip_and_legacy():
    assert _unpack_round_sender(_pack_round_sender(0, 0)) == (0, 0, 0)
    assert _unpack_round_sender(
        _pack_round_sender(2**23, 0xABCDEF, 0x3FFF)) \
        == (2**23, 0xABCDEF, 0x3FFF)
    # legacy plain extras (and negatives) decode as anonymous
    assert _unpack_round_sender(5) == (5, None, 0)
    assert _unpack_round_sender(0) == (0, None, 0)
    assert _unpack_round_sender(-2) == (-2, None, 0)


def test_dec_arr_view_is_readonly_mutation_fails_loudly():
    """Regression (satellite): _dec_tensor returns a zero-copy READ-ONLY
    view over the message buffer.  A consumer that accumulates in place
    without .copy() must fail loudly, not silently corrupt the buffer."""
    wire = bytes(_enc_tensor("g", np.arange(6, dtype=np.float32)))
    _, arr, _ = _dec_tensor(wire)
    assert not arr.flags.writeable
    with pytest.raises(ValueError):
        arr += 1.0
    # the sanctioned path: copy, then mutate
    safe = np.array(arr, copy=True)
    safe += 1.0
    np.testing.assert_allclose(safe, np.arange(6) + 1.0)


def test_apply_one_aggregates_readonly_views_in_place():
    """The pserver aggregation site accumulates in place — it must copy
    the first read-only wire view before += (satellite regression)."""
    applied = []
    scope = Scope()
    srv = VariableServer(scope, {"g": 0}, applied.append, fanin=2)
    for i, val in enumerate([2.0, 4.0]):
        wire = bytes(_enc_tensor(
            "g", np.full((3,), val, np.float32),
            _pack_round_sender(0, 100 + i)))
        _, arr, extra = _dec_tensor(wire)
        with srv._cv:
            srv._pending["g"][100 + i] = arr
            assert not arr.flags.writeable
            if i == 1:
                srv._apply_one("g")
    np.testing.assert_allclose(np.asarray(scope.find_var("g")),
                               np.full((3,), 3.0))
    assert applied == [0]


# ---------------------------------------------------------------------------
# Sender-dedup'd sync protocol (replay idempotence, legacy compat)
# ---------------------------------------------------------------------------

def _start_server(scope, fanin, **kw):
    applied = []
    srv = VariableServer(scope, {"g": 0}, applied.append, fanin=fanin,
                         **kw)
    port = srv.start("127.0.0.1:0")
    return srv, applied, "127.0.0.1:%d" % port


def test_replayed_round_is_idempotent():
    """A trainer that resends its round after a reconnect (replay cache)
    must not skew the sync mean: the server dedups by (round, sender)."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=2)
    RPCClient.reset()
    a = RPCClient.instance()
    b = RPCClient()
    try:
        a.send_var(ep, "g", np.full((4,), 2.0, np.float32))
        # duplicate send + full replay — exactly what a retry does
        a.send_var(ep, "g", np.full((4,), 2.0, np.float32))
        a._replay_round(ep)
        b.send_var(ep, "g", np.full((4,), 4.0, np.float32))
        ts = [threading.Thread(target=c.send_barrier, args=([ep],))
              for c in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        got = a.get_var(ep, "g")
        # mean over TRAINERS (2), not over arrivals (4)
        np.testing.assert_allclose(np.asarray(got), np.full((4,), 3.0))
        assert applied == [0]
        assert srv._applied_round == 1
    finally:
        a.send_complete([ep])
        b.send_complete([ep])
        srv.wait()


def test_legacy_anonymous_sends_keep_append_semantics():
    """Un-flagged extras (old wire) must keep the historical behavior:
    every arrival is a distinct aggregation slot."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=1)
    RPCClient.reset()
    cli = RPCClient.instance()
    try:
        for val in (1.0, 5.0):
            cli._call(ep, "SendVariable",
                      _enc_tensor("g", np.full((2,), val, np.float32), 0),
                      timeout=10.0)
        cli._call(ep, "SendBarrier", b"", timeout=10.0)  # legacy barrier
        with srv._cv:
            ok = srv._cv.wait_for(lambda: srv._applied_round >= 1,
                                  timeout=10.0)
        assert ok
        np.testing.assert_allclose(np.asarray(scope.find_var("g")),
                                   np.full((2,), 3.0))
        assert applied == [0]
    finally:
        cli.send_complete([ep])
        srv.wait()


def test_barrier_acks_only_after_durable_checkpoint(tmp_path):
    """SendBarrier returns only once the round is applied AND (with
    checkpoint_every_n=1) durably snapshotted — so a crash at ANY point
    either loses an un-acked round (trainers replay it) or nothing."""
    d = str(tmp_path / "shard")
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=1, checkpoint_dir=d,
                                     checkpoint_every_n=1)
    RPCClient.reset()
    cli = RPCClient.instance()
    try:
        cli.send_var(ep, "g", np.full((3,), 6.0, np.float32))
        cli.send_barrier([ep])
        # the ack we just got implies the checkpoint is on disk
        assert os.path.exists(os.path.join(d, "_SUCCESS"))
        with open(os.path.join(d, "_SUCCESS")) as f:
            assert int(f.read()) == 1
        assert srv._durable_round == 1
    finally:
        cli.send_complete([ep])
        srv.wait()
    # a restarted server resumes at the applied round with the state
    scope2 = Scope()
    srv2 = VariableServer(scope2, {"g": 0}, lambda b: None, fanin=1,
                          checkpoint_dir=d)
    assert srv2._applied_round == 1
    np.testing.assert_allclose(np.asarray(scope2.find_var("g")),
                               np.full((3,), 6.0))


def test_replayed_barrier_not_acked_before_durable(tmp_path):
    """A RETRIED barrier for a round that is applied but whose
    checkpoint write is still in flight must wait for durability like
    the original did — acking it early would let trainers advance and
    wipe their replay caches while the round can still be lost to a
    crash (regression)."""
    d = str(tmp_path / "shard")
    scope = Scope()
    applied = []
    srv = VariableServer(scope, {"g": 0}, applied.append, fanin=1,
                         checkpoint_dir=d, checkpoint_every_n=1)
    ep = "127.0.0.1:%d" % srv.start("127.0.0.1:0")
    writing = threading.Event()
    orig_save = srv.save_shard

    def slow_save(dirname, snapshot=None):
        writing.set()
        time.sleep(0.6)
        orig_save(dirname, snapshot)

    srv.save_shard = slow_save
    RPCClient.reset()
    cli = RPCClient.instance()
    try:
        cli.send_var(ep, "g", np.ones((2,), np.float32))
        t = threading.Thread(target=cli.send_barrier, args=([ep],))
        t.start()
        assert writing.wait(5.0)
        # the round is applied (stale by round number) but NOT durable:
        # a replayed barrier must block until the write completes
        t0 = time.time()
        cli._call(ep, "SendBarrier", cli._barrier_payload(0),
                  timeout=10.0)
        assert srv._durable_round > 0      # ack implied durability
        assert time.time() - t0 >= 0.2     # it genuinely waited
        t.join(timeout=10.0)
        assert not t.is_alive()
    finally:
        cli.send_complete([ep])
        srv.wait()


def test_async_resend_of_applied_grad_is_dropped():
    """Async mode applies on arrival and clears pending, so round-replay
    dedup can't help a retried send: the per-sender send SEQUENCE must
    make a resend of an already-applied grad a no-op (regression: a
    lost reply + retry used to double-apply the optimizer step)."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=1, sync_mode=False)
    RPCClient.reset()
    cli = RPCClient.instance()
    try:
        cli.send_var(ep, "g", np.full((2,), 1.0, np.float32))
        assert len(applied) == 1
        # the reply was "lost": the client replays the identical send
        cli._replay_round(ep)
        cli._replay_round(ep)
        assert len(applied) == 1          # dropped, not re-applied
        # a genuinely NEW send (fresh seq) applies again
        cli.send_var(ep, "g", np.full((2,), 2.0, np.float32))
        assert len(applied) == 2
    finally:
        cli.send_complete([ep])
        srv.wait()


def test_send_complete_after_lease_expiry_single_decrement():
    """A trainer counted out by the lease whose SendComplete arrives
    later (slow teardown) must not be subtracted twice — that would
    shut the server down under trainers still mid-round (regression)."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=2, trainer_lease=0.4)
    RPCClient.reset()
    a = RPCClient.instance()
    a.retry = RetryPolicy(deadline=15.0, call_timeout=2.0)
    b = RPCClient()
    try:
        # round 0: both participate
        a.send_var(ep, "g", np.ones((2,), np.float32))
        b.send_var(ep, "g", np.ones((2,), np.float32))
        ts = [threading.Thread(target=c.send_barrier, args=([ep],))
              for c in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # round 1: B is silent -> lease expires it (alive 2 -> 1)
        a.send_var(ep, "g", np.ones((2,), np.float32))
        a.send_barrier([ep])
        assert srv._alive == 1
        # B's delayed complete must be a no-op, not a second decrement
        b.send_complete([ep])
        time.sleep(0.2)
        assert srv._alive == 1
        assert not srv._shutdown.is_set()
        # A can still run a full round
        a.send_var(ep, "g", np.full((2,), 3.0, np.float32))
        a.send_barrier([ep])
        assert srv._applied_round == 3
    finally:
        a.send_complete([ep])
        srv.wait()


def test_complete_then_silence_is_not_lease_expired():
    """The mirror ordering: a trainer that finished CLEANLY and went
    silent must not be lease-expired afterwards (second decrement)."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=2, trainer_lease=0.4)
    RPCClient.reset()
    a = RPCClient.instance()
    a.retry = RetryPolicy(deadline=15.0, call_timeout=2.0)
    b = RPCClient()
    try:
        a.send_var(ep, "g", np.ones((2,), np.float32))
        b.send_var(ep, "g", np.ones((2,), np.float32))
        ts = [threading.Thread(target=c.send_barrier, args=([ep],))
              for c in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        b.send_complete([ep])             # B done: alive 2 -> 1
        assert srv._alive == 1
        # A trains on past B's lease window; the loop must not expire B
        for val in (2.0, 3.0):
            a.send_var(ep, "g", np.full((2,), val, np.float32))
            time.sleep(0.5)               # > lease of silence from B
            a.send_barrier([ep])
        assert srv._alive == 1
        assert not srv._shutdown.is_set()
    finally:
        a.send_complete([ep])
        srv.wait()


def test_restart_from_stale_checkpoint_fast_forwards_once():
    """Trainers ahead of a server restarted from an OLD checkpoint
    (checkpoint_every_n > 1): the replayed round must be applied ONCE
    with a jump to the trainers' round — not once per missing round
    (regression: multi-applied gradients + ~call_timeout stalls)."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=1)
    RPCClient.reset()
    cli = RPCClient.instance()
    cli.retry = RetryPolicy(deadline=10.0, call_timeout=2.0)
    cli.step = 5   # trainer is at round 5; server recovered at round 0
    try:
        cli.send_var(ep, "g", np.full((2,), 4.0, np.float32))
        t0 = time.time()
        cli.send_barrier([ep])
        assert time.time() - t0 < 2.0     # no per-missing-round stalls
        assert applied == [0]             # exactly one optimizer apply
        assert srv._applied_round == 6    # jumped to the trainers' round
        got = cli.get_var(ep, "g")        # waits applied >= 6: no hang
        np.testing.assert_allclose(np.asarray(got), np.full((2,), 4.0))
    finally:
        cli.send_complete([ep])
        srv.wait()


# ---------------------------------------------------------------------------
# Watchdog: hangs become errors naming the missing peer
# ---------------------------------------------------------------------------

class _FakeOp:
    def __init__(self, attrs):
        self._attrs = attrs

    def attr(self, name, default=None):
        return self._attrs.get(name, default)


def test_watchdog_names_missing_peer_instead_of_hanging():
    """fanin=2, peer B completes round 0 then dies.  A's next barrier
    must fail with a WatchdogTimeout naming B — not hang forever."""
    from paddle_tpu.ops.distributed_ops import _send_barrier

    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=2)
    RPCClient.reset()
    a = RPCClient.instance()
    a.retry = RetryPolicy(deadline=2.0, call_timeout=0.5,
                          base_backoff=0.05, max_backoff=0.1)
    b = RPCClient()
    b.label = "trainerB@deadhost:1"
    try:
        # round 0: both participate (barriers block until applied)
        a.send_var(ep, "g", np.ones((2,), np.float32))
        b.send_var(ep, "g", np.ones((2,), np.float32))
        ts = [threading.Thread(target=c.send_barrier, args=([ep],))
              for c in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert srv._applied_round == 1
        # round 1: B is dead; A's barrier (via the host op) must time out
        a.send_var(ep, "g", np.ones((2,), np.float32))
        with pytest.raises(WatchdogTimeout) as ei:
            _send_barrier(None, _FakeOp({"endpoints": [ep]}), scope,
                          None)
        msg = str(ei.value)
        assert "trainerB@deadhost:1" in msg
        assert ep in msg
    finally:
        a.send_complete([ep])   # straggler path applies round 1
        b.send_complete([ep])
        srv.wait()


def test_trainer_lease_expires_dead_peer_and_round_completes():
    """Server-side lease (mirrors Master._check_timeouts): a trainer
    that dies mid-round is expired from the fanin after
    ``trainer_lease`` seconds of silence and the survivors' round
    applies with their contributions."""
    scope = Scope()
    srv, applied, ep = _start_server(scope, fanin=2, trainer_lease=0.6)
    RPCClient.reset()
    a = RPCClient.instance()
    a.retry = RetryPolicy(deadline=15.0, call_timeout=2.0)
    b = RPCClient()
    try:
        a.send_var(ep, "g", np.full((2,), 2.0, np.float32))
        b.send_var(ep, "g", np.full((2,), 4.0, np.float32))
        ts = [threading.Thread(target=c.send_barrier, args=([ep],))
              for c in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # round 1: only A shows up; B's lease must expire -> round
        # applies with A's grad alone and A's (blocking) barrier returns
        a.send_var(ep, "g", np.full((2,), 8.0, np.float32))
        t0 = time.time()
        a.send_barrier([ep])
        assert srv._applied_round == 2
        assert time.time() - t0 < 10.0
        np.testing.assert_allclose(np.asarray(scope.find_var("g")),
                                   np.full((2,), 8.0))
        assert srv._alive == 1
    finally:
        a.send_complete([ep])
        srv.wait()


# ---------------------------------------------------------------------------
# Endpoint re-resolution through discovery
# ---------------------------------------------------------------------------

def test_endpoint_resolver_follows_restarted_pserver(tmp_path):
    from paddle_tpu.distributed.discovery import EndpointRegistry

    reg = EndpointRegistry(str(tmp_path), ttl=30.0)
    reg.register("pserver", "127.0.0.1:6000", meta={"shard": "s0"},
                 heartbeat=False)
    reg.register("pserver", "127.0.0.1:6001", meta={"shard": "s1"},
                 heartbeat=False)
    resolver = EndpointResolver(reg, "pserver",
                                logical_eps=["127.0.0.1:6000",
                                             "127.0.0.1:6001"])
    assert resolver.resolve("127.0.0.1:6000") == "127.0.0.1:6000"
    # s0 crashes and comes back on a NEW port under the same shard id
    reg.unregister("pserver", "127.0.0.1:6000")
    reg.register("pserver", "127.0.0.1:7777", meta={"shard": "s0"},
                 heartbeat=False)
    assert resolver.resolve("127.0.0.1:6000") == "127.0.0.1:7777"
    assert resolver.resolve("127.0.0.1:6001") == "127.0.0.1:6001"
    # a shard with no live registration resolves to None (caller keeps
    # the logical endpoint and retries)
    reg.unregister("pserver", "127.0.0.1:6001")
    assert resolver.resolve("127.0.0.1:6001") is None


def test_rpc_client_reconnect_uses_resolver():
    cli = RPCClient()
    cli.set_resolver(lambda ep: "127.0.0.1:9999"
                     if ep == "127.0.0.1:1111" else ep)
    cli._reconnect("127.0.0.1:1111")
    assert cli._phys("127.0.0.1:1111") == "127.0.0.1:9999"
    # resolver returning the logical endpoint clears the redirect
    cli.set_resolver(lambda ep: ep)
    cli._reconnect("127.0.0.1:1111")
    assert cli._phys("127.0.0.1:1111") == "127.0.0.1:1111"


# ---------------------------------------------------------------------------
# Master: snapshot durability + client deadlines
# ---------------------------------------------------------------------------

def test_master_snapshot_survives_truncation(tmp_path):
    """A truncated live snapshot (torn disk, external cause) must not
    poison _recover: the .bak rotated by the previous _snapshot loads
    (satellite: tmp-file-then-rename + fallback)."""
    from paddle_tpu.distributed.master import Master

    snap = str(tmp_path / "master.json")
    m = Master(snapshot_path=snap, num_epochs=1)
    m.set_dataset(["a", "b", "c"])
    t = m.get_task()          # second snapshot -> rotates .bak
    m.task_finished(t.task_id)
    assert os.path.exists(snap + ".bak")
    with open(snap, "w") as f:
        f.write('{"todo": [{"task_id"')   # truncated JSON
    m2 = Master(snapshot_path=snap, num_epochs=1)
    c = m2.counts()
    # .bak holds the state one snapshot earlier: all three tasks live
    assert c["todo"] + c["pending"] + c["done"] == 3
    # both copies corrupt -> warn + empty start (at-least-once dispatch
    # makes a re-run safe; refusing to start is not)
    with open(snap + ".bak", "w") as f:
        f.write("not json")
    with pytest.warns(UserWarning):
        m3 = Master(snapshot_path=snap, num_epochs=1)
    assert m3.counts()["todo"] == 0


def test_master_client_deadline_instead_of_hang():
    """An RPC to a dead master fails with DeadlineExceeded after the
    retry budget — it must never hang forever."""
    from paddle_tpu.distributed.master import MasterClient

    dead = "127.0.0.1:%d" % _free_port()
    cli = MasterClient(dead, retry=RetryPolicy(
        deadline=1.0, call_timeout=0.3, base_backoff=0.05,
        max_backoff=0.1))
    t0 = time.time()
    with pytest.raises(DeadlineExceeded) as ei:
        cli.counts()
    assert time.time() - t0 < 10.0
    assert dead in str(ei.value)


def test_master_client_rides_through_injected_drops():
    from paddle_tpu.distributed.master import (Master, MasterClient,
                                               MasterServer)

    srv = MasterServer(Master(num_epochs=1))
    port = srv.start("127.0.0.1:0")
    inj = install_faults("master_rpc:drop:1.0:3")
    try:
        cli = MasterClient("127.0.0.1:%d" % port, retry=RetryPolicy(
            deadline=20.0, call_timeout=2.0, base_backoff=0.01,
            max_backoff=0.05))
        cli.set_dataset(["x"])
        t = cli.get_task()
        assert t.payload == "x"
        assert cli.task_finished(t.task_id)
        assert inj.stats["master_rpc"] == 3   # all three drops absorbed
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Slow: real processes, injected faults, SIGKILL + restart
# ---------------------------------------------------------------------------

def _spawn_ctx():
    # tests run on the CPU: spawned children inherit this
    os.environ["JAX_PLATFORMS"] = "cpu"
    return mp.get_context("spawn")


def _baseline_to_queue(steps, kind, queue):
    queue.put(H.run_local_baseline(steps, kind))


def _collect(ctx, q, n_trainers, timeout=300):
    results = {}
    for _ in range(n_trainers):
        tid, losses = q.get(timeout=timeout)
        results[tid] = losses
    return results


def _baseline(ctx, steps, kind="softmax"):
    """The e2e parity reference.  Plain runs compare against the local
    single-process trajectory; with FLAGS_dist_compress exported
    (tools/fault_matrix.py 'compressed' preset) the reference is
    instead a FAULT-FREE distributed run under the same codec — the
    parity claim becomes 'faults + replays on the compressed wire are
    invisible to the math', which is exactly the idempotence guarantee
    compression must not break (a lossy codec can never match the
    uncompressed local baseline)."""
    if os.environ.get("FLAGS_dist_compress", "").strip():
        return _dist_reference(ctx, steps, kind)
    bq = ctx.Queue()
    bp = ctx.Process(target=_baseline_to_queue, args=(steps, kind, bq))
    bp.start()
    local = bq.get(timeout=240)
    bp.join(timeout=60)
    return local


def _dist_reference(ctx, steps, kind="softmax"):
    """A fault-free 2x2 distributed run (same topology as the e2e
    tests), fault injection explicitly CLEARED in every child."""
    eps = ["127.0.0.1:%d" % _free_port() for _ in range(2)]
    pservers = ",".join(eps)
    clean = {"FLAGS_fastwire_port_offset": "0", "FLAGS_fault_spec": ""}
    ps_procs = [ctx.Process(target=H.run_pserver,
                            args=(ep, pservers, 2, kind, True, clean))
                for ep in eps]
    for p in ps_procs:
        p.start()
    q = ctx.Queue()
    tr_procs = [ctx.Process(target=H.run_trainer,
                            args=(tid, pservers, 2, steps, q, kind,
                                  True, clean))
                for tid in range(2)]
    for p in tr_procs:
        p.start()
    results = _collect(ctx, q, 2)
    for p in tr_procs + ps_procs:
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
    np.testing.assert_allclose(results[0], results[1], rtol=1e-6)
    return results[0]


def _merged_spec(base):
    """Combine the test's own fault spec with an externally exported
    FLAGS_fault_spec (tools/fault_matrix.py presets), so the matrix
    runner genuinely varies the stress level of these e2e tests."""
    extra = os.environ.get("FLAGS_fault_spec", "").strip()
    return ",".join(s for s in (base, extra) if s)


@pytest.mark.slow
def test_dist_train_survives_injected_faults():
    """Sync-SGD under dropped sends, dropped gets, delayed gets, and
    dropped barriers must converge to EXACTLY the fault-free losses:
    the retry + (round, sender)-dedup'd replay protocol makes every
    recovery path invisible to the math."""
    ctx = _spawn_ctx()
    eps = ["127.0.0.1:%d" % _free_port() for _ in range(2)]
    pservers = ",".join(eps)
    n_trainers, steps = 2, 8
    ps_env = {"FLAGS_fastwire_port_offset": "0"}
    tr_env = {
        "FLAGS_fastwire_port_offset": "0",
        "FLAGS_fault_spec": _merged_spec(
            "send_grad:drop:0.3:8,get_param:drop:0.3:8,"
            "get_param:delay:0.05:6,send_barrier:drop:0.5:4"),
        "FLAGS_rpc_deadline": "240",
        "FLAGS_rpc_call_timeout": "10",
        "FLAGS_rpc_retry_backoff": "0.05",
    }
    ps_procs = [ctx.Process(target=H.run_pserver,
                            args=(ep, pservers, n_trainers, "softmax",
                                  True, ps_env))
                for ep in eps]
    for p in ps_procs:
        p.start()
    q = ctx.Queue()
    tr_procs = [ctx.Process(target=H.run_trainer,
                            args=(tid, pservers, n_trainers, steps, q,
                                  "softmax", True, tr_env))
                for tid in range(n_trainers)]
    for p in tr_procs:
        p.start()
    results = _collect(ctx, q, n_trainers)
    for p in tr_procs:
        p.join(timeout=60)
    for p in ps_procs:
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
            pytest.fail("pserver did not shut down after SendComplete")
    local = _baseline(ctx, steps)
    for tid in range(n_trainers):
        np.testing.assert_allclose(results[tid], local, rtol=1e-4,
                                   atol=1e-5)
    assert local[-1] < local[0] * 0.8   # and it actually learned


@pytest.mark.slow
def test_pserver_sigkill_restart_mid_training_recovers(tmp_path):
    """One pserver is SIGKILLed mid-training and restarted on the same
    endpoint with its checkpoint dir: durable-ack checkpoints (every
    round) + trainer-side round replay make the final losses match the
    fault-free run exactly."""
    ctx = _spawn_ctx()
    eps = ["127.0.0.1:%d" % _free_port() for _ in range(2)]
    pservers = ",".join(eps)
    n_trainers, steps = 2, 10
    ckpt_root = str(tmp_path / "shards")
    ps_env = {
        "FLAGS_fastwire_port_offset": "0",
        "FLAGS_pserver_checkpoint_root": ckpt_root,
        "FLAGS_pserver_checkpoint_every_n": "1",
    }
    tr_env = {
        "FLAGS_fastwire_port_offset": "0",
        # pace the rounds so the kill lands mid-training
        "FLAGS_fault_spec": _merged_spec("get_param:delay:0.1"),
        "FLAGS_rpc_deadline": "240",
        "FLAGS_rpc_call_timeout": "5",
    }
    ps_procs = [ctx.Process(target=H.run_pserver,
                            args=(ep, pservers, n_trainers, "softmax",
                                  True, ps_env))
                for ep in eps]
    for p in ps_procs:
        p.start()
    q = ctx.Queue()
    tr_procs = [ctx.Process(target=H.run_trainer,
                            args=(tid, pservers, n_trainers, steps, q,
                                  "softmax", True, tr_env))
                for tid in range(n_trainers)]
    for p in tr_procs:
        p.start()

    time.sleep(2.5)                 # mid-training (>=0.2s per round)
    assert q.empty(), "training finished before the kill landed"
    ps_procs[0].kill()              # SIGKILL: no cleanup, no goodbyes
    ps_procs[0].join(timeout=30)
    restarted = ctx.Process(target=H.run_pserver,
                            args=(eps[0], pservers, n_trainers,
                                  "softmax", True, ps_env))
    restarted.start()

    results = _collect(ctx, q, n_trainers)
    for p in tr_procs:
        p.join(timeout=60)
    for p in (ps_procs[1], restarted):
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
    local = _baseline(ctx, steps)
    for tid in range(n_trainers):
        np.testing.assert_allclose(results[tid], local, rtol=1e-4,
                                   atol=1e-5)
