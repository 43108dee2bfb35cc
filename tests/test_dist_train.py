"""End-to-end pserver training on localhost (reference test_dist_train.py):
2 trainers x 2 pservers over gRPC, compared against the single-process
run — constant inits + identical batches make sync-SGD losses match
exactly (up to float accumulation order).

The emb_sparse variant drives the full distributed SelectedRows path:
lookup_table_grad -> send row-range split -> gRPC sparse wire format
(kind=1) -> pserver sparse mean aggregation -> sparse sgd apply.
"""
import multiprocessing as mp
import socket

import numpy as np
import pytest

import dist_train_helpers as H


def _baseline_to_queue(steps, kind, queue):
    queue.put(H.run_local_baseline(steps, kind))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_dist(kind, steps=8, sync_mode=True):
    import os

    # tests run on the CPU: spawned pservers/trainers inherit this
    os.environ["JAX_PLATFORMS"] = "cpu"

    ctx = mp.get_context("spawn")
    eps = ["127.0.0.1:%d" % _free_port() for _ in range(2)]
    pservers = ",".join(eps)
    n_trainers = 2

    ps_procs = [ctx.Process(target=H.run_pserver,
                            args=(ep, pservers, n_trainers, kind,
                                  sync_mode))
                for ep in eps]
    for p in ps_procs:
        p.start()

    q = ctx.Queue()
    tr_procs = [ctx.Process(target=H.run_trainer,
                            args=(tid, pservers, n_trainers, steps, q,
                                  kind, sync_mode))
                for tid in range(n_trainers)]
    for p in tr_procs:
        p.start()

    results = {}
    for _ in range(n_trainers):
        tid, losses = q.get(timeout=240)
        results[tid] = losses
    for p in tr_procs:
        p.join(timeout=60)
    for p in ps_procs:
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
            pytest.fail("pserver did not shut down after SendComplete")

    # baseline in a spawned child too, so both sides of the comparison
    # start from the same fresh-process jax state
    bq = ctx.Queue()
    bp = ctx.Process(target=_baseline_to_queue, args=(steps, kind, bq))
    bp.start()
    local = bq.get(timeout=240)
    bp.join(timeout=60)
    if sync_mode:
        for tid in range(n_trainers):
            np.testing.assert_allclose(results[tid], local, rtol=1e-4,
                                       atol=1e-5)
        return local
    return results, local


def test_dist_train_matches_local():
    local = _run_dist("softmax")
    assert local[-1] < local[0] * 0.8  # actually learning


def test_dist_train_distributed_lookup_table():
    """embedding(is_distributed=True): the table lives ONLY on the
    pservers (sharded by rows); trainers prefetch rows over RPC in the
    forward and ship sparse grads back.  Must match the local run."""
    local = _run_dist("emb_dist")
    assert local[-1] < local[0]


def test_dist_train_sparse_embedding():
    """Distributed SelectedRows: sparse grads travel the wire split by
    row range and the pserver applies them; must match the local run."""
    local = _run_dist("emb_sparse")
    assert local[-1] < local[0]  # embedding actually moved


def test_large_shard_over_the_wire():
    """A parameter shard well past gRPC's 4MB default message cap must
    roundtrip (regression: GRPC_OPTIONS unlimited sizes — a 100MB fc
    shard used to fail with 'Received message larger than max')."""
    import numpy as np

    from paddle_tpu.core.scope import Scope
    from paddle_tpu.distributed.rpc import RPCClient, VariableServer

    big = np.random.RandomState(0).rand(1200, 2048).astype(np.float32)
    scope = Scope()
    scope.set("w", big)                       # ~9.8 MB
    applied = []
    srv = VariableServer(scope, {"w@GRAD": 0}, applied.append, fanin=1)
    port = srv.start("127.0.0.1:0")
    ep = "127.0.0.1:%d" % port
    # the singleton's step counter may have advanced in earlier tests;
    # a fresh server starts at round 0 and sync get_vars would wait
    # forever on a higher round
    RPCClient.reset()
    cli = RPCClient.instance()
    try:
        cli.send_var(ep, "w@GRAD", big * 0.5)  # >4MB up
        cli.send_barrier([ep])
        got, = cli.get_vars([(ep, "w")])       # >4MB down
        np.testing.assert_array_equal(np.asarray(got), big)
        assert applied == [0]
    finally:
        cli.send_complete([ep])
        srv.wait()


def test_dist_train_async_mode():
    """Async pserver (reference listen_and_serv RunAsyncLoop): no
    barriers, grads applied on arrival.  Losses cannot match the sync
    baseline exactly; both trainers must still converge."""
    results, local = _run_dist("softmax", steps=12, sync_mode=False)
    for tid, losses in results.items():
        assert len(losses) == 12
        assert np.isfinite(losses).all()
        # async interleaving is nondeterministic: a trainer can regress
        # transiently on the LAST few steps, so gate on the best post-
        # warmup loss rather than the tail mean
        assert np.min(losses[4:]) < losses[0] * 0.85, (tid, losses)
