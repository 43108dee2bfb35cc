"""Spawn-able workers for the localhost pserver training test
(reference test_dist_train.py forks pservers with multiprocessing and
connects over localhost gRPC).  Top-level functions so the 'spawn' start
method can pickle them."""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
# tests run on the CPU (forced, not defaulted: a worker must never
# take a chip)
os.environ["JAX_PLATFORMS"] = "cpu"

# spawn children start with a fresh sys.path that lacks the repo root
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np

N_FEAT = 48
N_CLS = 10
LR = 0.5


def build_model(kind="softmax"):
    import paddle_tpu.fluid as fluid

    # zero init everywhere -> every process starts from identical params,
    # so sync-SGD losses must match the single-process run exactly
    zinit = fluid.initializer.ConstantInitializer(0.0)
    if kind in ("emb_sparse", "emb_dense", "emb_dist"):
        ids = fluid.layers.data(name="ids", shape=[4], dtype="int64")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        # NON-zero constant inits (still identical across processes):
        # with emb_w=fc_w=0 both grads vanish identically and the test
        # could not distinguish a broken sparse path from a working one
        emb = fluid.layers.embedding(
            ids, size=[50, 8],
            is_sparse=(kind in ("emb_sparse", "emb_dist")),
            is_distributed=(kind == "emb_dist"),
            param_attr=fluid.ParamAttr(
                name="emb_w",
                initializer=fluid.initializer.ConstantInitializer(0.02)))
        pooled = fluid.layers.reduce_mean(emb, dim=1)   # [N, 8]
        pred = fluid.layers.fc(
            input=pooled, size=1,
            param_attr=fluid.ParamAttr(
                name="fc_w",
                initializer=fluid.initializer.ConstantInitializer(0.1)),
            bias_attr=fluid.ParamAttr(name="fc_b", initializer=zinit))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=LR).minimize(loss)
        return loss
    img = fluid.layers.data(name="img", shape=[N_FEAT], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    pred = fluid.layers.fc(
        input=img, size=N_CLS, act="softmax",
        param_attr=fluid.ParamAttr(name="fc_w", initializer=zinit),
        bias_attr=fluid.ParamAttr(name="fc_b", initializer=zinit))
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.SGD(learning_rate=LR).minimize(loss)
    return loss


def make_batch(step, kind="softmax"):
    rng = np.random.RandomState(1234 + step)
    if kind in ("emb_sparse", "emb_dense", "emb_dist"):
        # one FIXED batch (step-independent): squared loss on a linear
        # model then descends monotonically, a clean learning signal
        rng = np.random.RandomState(1234)
        ids = rng.randint(0, 50, (32, 4)).astype(np.int64)
        y = (np.sin(ids).sum(1, keepdims=True) * 0.1).astype(np.float32)
        return {"ids": ids, "y": y}
    x = rng.randn(64, N_FEAT).astype(np.float32)
    proj = np.random.RandomState(7).randn(N_FEAT, N_CLS)
    y = np.argmax(x @ proj, axis=1).astype(np.int64)[:, None]
    return {"img": x, "label": y}


def run_local_baseline(steps, kind="softmax"):
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                loss = build_model(kind)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = []
        for s in range(steps):
            l, = exe.run(main, feed=make_batch(s, kind),
                         fetch_list=[loss])
            losses.append(float(np.ravel(l)[0]))
    return losses


def _transpile(trainer_id, pservers, trainers, kind="softmax",
               sync_mode=True):
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                loss = build_model(kind)
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=trainer_id, program=main,
                startup_program=startup, pservers=pservers,
                trainers=trainers, min_block_size=64,
                sync_mode=sync_mode)
    return t, main, startup, scope, loss


def _apply_env(env):
    """Install per-worker env BEFORE the first paddle/flag import reads
    it (spawn children import this module fresh): fault specs, retry
    knobs, and checkpoint roots all ride environment variables."""
    if env:
        os.environ.update(env)


def _dump_telemetry():
    """Explicit per-process trace dump (FLAGS_telemetry_dump_dir):
    spawned workers should not rely on atexit ordering to leave their
    half of a merged distributed trace."""
    try:
        from paddle_tpu.observability.trace import TRACER
        TRACER.dump_if_configured()
    except Exception:
        pass


def run_pserver(endpoint, pservers, trainers, kind="softmax",
                sync_mode=True, env=None):
    _apply_env(env)
    import paddle_tpu.fluid as fluid

    t, main, startup, scope, loss = _transpile(0, pservers, trainers,
                                               kind, sync_mode)
    ps_prog = t.get_pserver_program(endpoint)
    ps_startup = t.get_startup_program(endpoint, ps_prog)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(ps_startup)
        exe.run(ps_prog)   # blocks until all trainers SendComplete
    _dump_telemetry()


def run_trainer(trainer_id, pservers, trainers, steps, queue,
                kind="softmax", sync_mode=True, env=None):
    _apply_env(env)
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed.rpc import RPCClient

    t, main, startup, scope, loss = _transpile(trainer_id, pservers,
                                               trainers, kind,
                                               sync_mode)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for s in range(steps):
            # both trainers feed the SAME batch: the pserver's grad mean
            # then equals the single-process grad, so losses must match
            l, = exe.run(t.get_trainer_program(),
                         feed=make_batch(s, kind), fetch_list=[loss])
            losses.append(float(np.ravel(l)[0]))
    RPCClient.instance().send_complete(t.pserver_endpoints)
    _dump_telemetry()
    queue.put((trainer_id, losses))
