"""Multi-host data parallelism: ParallelExecutor(num_trainers=2) over
jax.distributed — the reference's "nccl2 mode"
(parallel_executor.cc:84-95, platform/nccl_helper.h:81,
operators/gen_nccl_id_op.cc).

Two spawned localhost processes x 4 forced host devices each join one
collective world through the PADDLE_TRAINER_ENDPOINTS env contract
(distributed/collective.py — the gen_nccl_id analog); each feeds its
local half of a fixed global batch.  Losses must match a single-process
8-device SPMD run of the same program bit-for-bit-ish (gloo float
reductions: 1e-5)."""
import multiprocessing as mp
import os
import socket

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _child_env:
    """Temporarily mutate os.environ so spawned children are BORN with
    the right platform config (the device count is read when jax first
    initializes, before worker code could set it)."""

    def __init__(self, **kv):
        self.kv = kv
        self.saved = {}

    def __enter__(self):
        for k, v in self.kv.items():
            self.saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def __exit__(self, *exc):
        for k, old in self.saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


@pytest.mark.timeout(300)
def test_two_process_pe_matches_single_process():
    from tests import multihost_helpers as H

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = []

    with _child_env(JAX_PLATFORMS="cpu",
                    XLA_FLAGS="--xla_force_host_platform_device_count=8",
                    PADDLE_TRAINER_ENDPOINTS=None,
                    PADDLE_TRAINER_ID=None):
        procs.append(ctx.Process(target=H.baseline_worker, args=(q,)))
        procs[-1].start()

    port = _free_port()
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (port, port + 1)
    for i in range(2):
        with _child_env(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PADDLE_TRAINER_ENDPOINTS=eps,
                PADDLE_TRAINER_ID=str(i)):
            procs.append(ctx.Process(target=H.trainer_worker, args=(i, q)))
            procs[-1].start()

    try:
        results = {}
        for _ in range(3):
            tag, losses, ndev = q.get(timeout=240)
            results[tag] = (losses, ndev)
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()

    for tag, (losses, _) in results.items():
        assert not isinstance(losses, str), (tag, losses)

    base, nb = results["baseline"]
    assert nb == 8
    # both trainers saw the union of devices (the bootstrap smoke:
    # init_collective_env really joined one world)
    assert results["trainer0"][1] == 8
    assert results["trainer1"][1] == 8
    # identical loss trajectory: same global batch, same deterministic
    # init, psum-of-local == global mean
    t0, t1 = results["trainer0"][0], results["trainer1"][0]
    assert np.allclose(t0, t1, atol=1e-6), (t0, t1)
    assert np.allclose(base, t0, atol=1e-5), (base, t0)
    # and training actually trains
    assert base[-1] < base[0]


@pytest.mark.timeout(300)
def test_two_process_pe_with_tensor_parallel_params():
    """dp=2 x tp=4 mesh spanning two processes: TENSOR-PARALLEL weight
    shards cross the host boundary — each process materializes its
    addressable shards from the full deterministic init
    (executor_impl._put global-value semantics).  Losses must match a
    single-process run of the same mesh."""
    from tests import multihost_helpers as H

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = []

    with _child_env(JAX_PLATFORMS="cpu",
                    XLA_FLAGS="--xla_force_host_platform_device_count=8",
                    PADDLE_TRAINER_ENDPOINTS=None,
                    PADDLE_TRAINER_ID=None):
        procs.append(ctx.Process(target=H.baseline_worker_tp, args=(q,)))
        procs[-1].start()

    port = _free_port()
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (port, port + 1)
    for i in range(2):
        with _child_env(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PADDLE_TRAINER_ENDPOINTS=eps,
                PADDLE_TRAINER_ID=str(i)):
            procs.append(ctx.Process(target=H.trainer_worker_tp,
                                     args=(i, q)))
            procs[-1].start()

    try:
        results = {}
        for _ in range(3):
            tag, losses, ndev = q.get(timeout=240)
            results[tag] = (losses, ndev)
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()

    for tag, (losses, _) in results.items():
        assert not isinstance(losses, str), (tag, losses)
    base = results["tpbase"][0]
    t0, t1 = results["tp0"][0], results["tp1"][0]
    assert np.allclose(t0, t1, atol=1e-6), (t0, t1)
    assert np.allclose(base, t0, atol=1e-5), (base, t0)


@pytest.mark.timeout(300)
def test_two_process_pe_with_reader_chain(tmp_path):
    """Each trainer reads its own recordio shard through program-level
    reader ops; the global loss is the mean over BOTH shards — wrong
    (halved/duplicated) assembly of the scope-resident batches would
    change the value."""
    import paddle_tpu.fluid as fluid

    data_dir = str(tmp_path)
    vals = {}
    for i in range(2):
        rows = np.full((8, 4), float(i + 1), np.float32)  # shard i: i+1
        def reader(rows=rows):
            for r in rows:
                yield (r,)
        fluid.recordio_writer.convert_reader_to_recordio_file(
            "%s/shard%d.recordio" % (data_dir, i), reader)
        vals[i] = rows.mean()
    expect = (vals[0] + vals[1]) / 2.0  # 1.5

    from tests import multihost_helpers as H

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = []
    port = _free_port()
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (port, port + 1)
    for i in range(2):
        with _child_env(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PADDLE_TRAINER_ENDPOINTS=eps,
                PADDLE_TRAINER_ID=str(i)):
            procs.append(ctx.Process(target=H.trainer_worker_reader,
                                     args=(i, q, data_dir)))
            procs[-1].start()
    try:
        results = {}
        for _ in range(2):
            tag, val, ndev = q.get(timeout=240)
            results[tag] = (val, ndev)
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    for tag, (val, _) in results.items():
        assert not isinstance(val, str), (tag, val)
    assert abs(results["reader0"][0] - expect) < 1e-6, results
    assert abs(results["reader1"][0] - expect) < 1e-6, results
