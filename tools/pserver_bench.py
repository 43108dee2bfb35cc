"""Pserver throughput microbenchmark (round-3 VERDICT weak #3;
planet-scale sparse tier in ISSUE 10).

The reference's C++ ParameterServer2 (paddle/pserver/ParameterServer2.h)
was a performance component: sharded updates, zero-copy sockets.  Its
replacement here is the fastwire pserver (distributed/rpc.py) behind
the distribute transpiler.  This tool measures what that pserver
actually sustains on localhost, end to end through the REAL training
path (transpiled programs, 2 trainers, sync mode):

  dense  — one ~100 MB fc parameter: full grad up + param down every
           round; reports rounds/sec and the aggregate wire MB/s the
           server moved.  A compression sweep re-runs it per
           FLAGS_dist_compress codec and reports wire bytes/round +
           the effective compression ratio from the wire counters.
  sparse — a 1M-row x 64 embedding with is_sparse=True: per-step
           SelectedRows updates; reports touched rows/sec.
  ctr    — the production-recommender shape (ISSUE 10): a
           multi-ten-million-row DISTRIBUTED embedding
           (distributed_lookup prefetch, table never leaves the
           pservers) under power-law (zipf) row access, measured twice
           — flat sync, and scaled with hierarchical aggregation +
           bounded-staleness async + int8/rows compression.

Run:  python tools/pserver_bench.py  (writes one JSON line to stdout)

The JSON includes `fraction_of_chip_step`: with the measured round
time, the share of a 100 ms accelerator step (the ResNet-50 headline's
step time) a synchronous round would consume if overlapped 1:1 — the
"can this pserver feed one chip" statement the VERDICT asked for.
"""
import json
import multiprocessing as mp
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")
# FORCE cpu (not setdefault): the pserver bench is a host-path benchmark
# by definition, and its spawned workers must never take a chip
os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np

# dense: 4096 x 6400 f32 = 104.9 MB parameter.  Env-overridable (not
# argv): spawn children re-import this module fresh, so the quick-mode
# dims must travel through the environment to reach them.
DENSE_IN = int(os.environ.get("PSB_DENSE_IN", "4096"))
DENSE_OUT = int(os.environ.get("PSB_DENSE_OUT", "6400"))
DENSE_BATCH = 8
# sparse: 1M x 64 embedding, 1024 samples x 4 ids per step
VOCAB = int(os.environ.get("PSB_VOCAB", "1000000"))
EMB_DIM = 64
SPARSE_BATCH = int(os.environ.get("PSB_SPARSE_BATCH", "1024"))
IDS_PER_SAMPLE = 4
# ctr: 20M x 16 sharded table, 32k samples x 16 ids, zipf row access
# (hash-feature dims are narrow in production CTR; batch sized so the
# ~570k distinct rows a step touches amortize the round's fixed costs)
CTR_VOCAB = int(os.environ.get("PSB_CTR_VOCAB", "20000000"))
CTR_DIM = int(os.environ.get("PSB_CTR_DIM", "16"))
CTR_BATCH = int(os.environ.get("PSB_CTR_BATCH", "32768"))
CTR_IDS = int(os.environ.get("PSB_CTR_IDS", "16"))
CTR_ZIPF = float(os.environ.get("PSB_CTR_ZIPF", "1.05"))


def build_model(kind):
    import paddle_tpu.fluid as fluid

    zinit = fluid.initializer.ConstantInitializer(0.0)
    if kind in ("sparse", "ctr"):
        vocab = VOCAB if kind == "sparse" else CTR_VOCAB
        dim = EMB_DIM if kind == "sparse" else CTR_DIM
        ids_n = IDS_PER_SAMPLE if kind == "sparse" else CTR_IDS
        ids = fluid.layers.data(name="ids", shape=[ids_n],
                                dtype="int64")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        # distributed lookup table (the DeepFM-style workload SURVEY
        # §2.5 keeps the pserver path FOR): trainers prefetch only the
        # batch's rows and push SelectedRows updates — no full-table
        # sync per round.  The ctr shape never materializes the table
        # off the pservers at all (2.6 GB f32 at the default dims).
        emb = fluid.layers.embedding(
            ids, size=[vocab, dim], is_sparse=True,
            is_distributed=True,
            param_attr=fluid.ParamAttr(
                name="emb_w",
                initializer=fluid.initializer.ConstantInitializer(0.02)))
        pooled = fluid.layers.reduce_mean(emb, dim=1)
        pred = fluid.layers.fc(
            input=pooled, size=1,
            param_attr=fluid.ParamAttr(name="fc_w",
                                       initializer=zinit),
            bias_attr=fluid.ParamAttr(name="fc_b", initializer=zinit))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
    else:
        x = fluid.layers.data(name="x", shape=[DENSE_IN],
                              dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(
            input=x, size=DENSE_OUT,
            param_attr=fluid.ParamAttr(name="big_w", initializer=zinit),
            bias_attr=False)
        pred = fluid.layers.fc(
            input=h, size=1,
            param_attr=fluid.ParamAttr(name="head_w",
                                       initializer=zinit),
            bias_attr=False)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return loss


def make_batch(step, kind, trainer_id=0):
    rng = np.random.RandomState(1000 * step + trainer_id)
    if kind == "ctr":
        # power-law (zipf) row access: the head ids dominate like real
        # CTR traffic, the tail still sweeps the multi-ten-million-row
        # table
        ids = ((rng.zipf(CTR_ZIPF, (CTR_BATCH, CTR_IDS)) - 1)
               % CTR_VOCAB).astype(np.int64)
        return {"ids": ids,
                "y": rng.rand(CTR_BATCH, 1).astype(np.float32)}
    if kind == "sparse":
        return {
            "ids": rng.randint(0, VOCAB,
                               (SPARSE_BATCH, IDS_PER_SAMPLE)
                               ).astype(np.int64),
            "y": rng.rand(SPARSE_BATCH, 1).astype(np.float32),
        }
    return {
        "x": rng.rand(DENSE_BATCH, DENSE_IN).astype(np.float32),
        "y": rng.rand(DENSE_BATCH, 1).astype(np.float32),
    }


def distinct_rows_per_step(kind, steps, n_trainers=2):
    """Mean count of DISTINCT table rows the trainers touch per step —
    the numerator of rows/s (batches are deterministic per (step,
    trainer), so the parent recomputes them exactly)."""
    counts = []
    for s in range(1, steps + 1):
        ids = np.concatenate([
            make_batch(s, kind, t)["ids"].reshape(-1)
            for t in range(n_trainers)])
        counts.append(len(np.unique(ids)))
    return float(np.mean(counts))


def _apply_env(env):
    if env:
        os.environ.update(env)


def _transpile(trainer_id, pservers, trainers, kind):
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
                trainers=trainers, sync_mode=True)
    return t, main, startup, scope, loss


def run_pserver(endpoint, pservers, trainers, kind, env=None):
    _apply_env(env)
    import paddle_tpu.fluid as fluid

    t, main, startup, scope, loss = _transpile(0, pservers, trainers,
                                               kind)
    ps_prog = t.get_pserver_program(endpoint)
    ps_startup = t.get_startup_program(endpoint, ps_prog)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(ps_startup)
        exe.run(ps_prog)


def run_trainer(trainer_id, pservers, trainers, steps, queue, kind,
                env=None):
    _apply_env(env)
    # hierarchy leader election + telemetry labels key off the id
    os.environ["PADDLE_TRAINER_ID"] = str(trainer_id)
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed.rpc import RPCClient
    from paddle_tpu.observability import metrics as obs_metrics

    t, main, startup, scope, loss = _transpile(trainer_id, pservers,
                                               trainers, kind)
    exe = fluid.Executor(fluid.CPUPlace())
    # feeds are pre-generated OUTSIDE the timed loop: zipf rejection
    # sampling costs ~45 ms per 16k x 16 batch — bench harness cost,
    # not data-plane throughput
    feeds = [make_batch(s, kind, trainer_id) for s in range(steps + 1)]
    with fluid.scope_guard(scope):
        exe.run(startup)
        prog = t.get_trainer_program()
        exe.run(prog, feed=feeds[0], fetch_list=[loss])  # warm/compile
        t0 = time.time()
        for s in range(1, steps + 1):
            exe.run(prog, feed=feeds[s], fetch_list=[loss])
        dt = time.time() - t0
    RPCClient.instance().send_complete(t.pserver_endpoints)
    snap = obs_metrics.snapshot()

    def _val(name):
        return (snap.get(name) or {}).get("value", 0)

    queue.put((trainer_id, dt, steps, {
        "wire_bytes_raw_total": _val("wire_bytes_raw_total"),
        "wire_bytes_compressed_total": _val(
            "wire_bytes_compressed_total"),
        "rpc_bytes_sent_total": _val("rpc_bytes_sent_total"),
        "rpc_bytes_recv_total": _val("rpc_bytes_recv_total"),
    }))


def bench(kind, steps, n_pservers=2, n_trainers=2, base_port=19310,
          env=None):
    """One 2x2 run; returns (rounds_per_sec, per-trainer metric dicts).
    ``env`` is exported into every child — the FLAGS_dist_* knobs
    (compress/staleness/hier) travel this way."""
    ctx = mp.get_context("spawn")
    eps = ["127.0.0.1:%d" % (base_port + i) for i in range(n_pservers)]
    pservers = ",".join(eps)
    ps_procs = [ctx.Process(target=run_pserver,
                            args=(ep, pservers, n_trainers, kind, env))
                for ep in eps]
    tr_procs = []
    try:
        for p in ps_procs:
            p.start()
        time.sleep(2.0)
        q = ctx.Queue()
        tr_procs = [ctx.Process(target=run_trainer,
                                args=(i, pservers, n_trainers, steps, q,
                                      kind, env))
                    for i in range(n_trainers)]
        for p in tr_procs:
            p.start()
        results = [q.get(timeout=900) for _ in tr_procs]
        for p in tr_procs + ps_procs:
            p.join(timeout=120)
    finally:
        # a crashed child must not leave non-daemon orphans holding the
        # ports (and blocking interpreter exit)
        for p in tr_procs + ps_procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
    dt = max(r[1] for r in results)  # rounds complete at the slowest
    return steps / dt, [r[3] for r in results]


def compress_sweep(steps, base_port):
    """Re-run the dense bench per codec and report rounds/s, wire
    bytes/round, and the effective compression ratio straight from the
    trainers' wire counters (warmup round included in the divisor)."""
    out = {}
    for i, mode in enumerate(("", "fp16", "int8", "topk")):
        env = {"FLAGS_dist_compress": mode}
        rps, mets = bench("dense", steps, base_port=base_port + 40 * i,
                          env=env)
        raw = sum(m["wire_bytes_raw_total"] for m in mets)
        comp = sum(m["wire_bytes_compressed_total"] for m in mets)
        rounds = (steps + 1) * len(mets)   # +1: the warmup round
        out[mode or "raw"] = {
            "rounds_per_sec": round(rps, 2),
            "grad_bytes_per_round": int(comp / rounds),
            "compression_ratio": round(raw / comp, 2) if comp else 1.0,
        }
    return out


def ctr_bench(steps, base_port):
    """The CTR-shaped scenario, flat vs scaled (hierarchical
    aggregation + bounded-staleness async + int8/rows compression).
    Quick-mode sizing arrives via the PSB_CTR_* env knobs, like every
    other scenario."""
    distinct = distinct_rows_per_step("ctr", max(3, steps))
    out = {"vocab": CTR_VOCAB, "emb_dim": CTR_DIM,
           "batch": CTR_BATCH, "ids_per_sample": CTR_IDS,
           "zipf_a": CTR_ZIPF,
           "distinct_rows_per_step": int(distinct)}
    runs = [("flat_sync", {})]
    scaled_env = {"FLAGS_dist_compress": "int8",
                  "FLAGS_dist_staleness": "2",
                  "FLAGS_dist_hier_local": "2",
                  "FLAGS_dist_hier_port": str(base_port + 700)}
    runs.append(("hier_async_int8", scaled_env))
    for i, (name, env) in enumerate(runs):
        rps, mets = bench("ctr", steps, base_port=base_port + 40 * i,
                          env=env)
        raw = sum(m["wire_bytes_raw_total"] for m in mets)
        comp = sum(m["wire_bytes_compressed_total"] for m in mets)
        out[name] = {
            "steps_per_sec": round(rps, 2),
            "rows_per_sec": int(rps * distinct),
            "compression_ratio": round(raw / comp, 2) if comp else 1.0,
            "staleness": int(env.get("FLAGS_dist_staleness", "0")),
            "hier_local": int(env.get("FLAGS_dist_hier_local", "0")),
        }
    return out


def component_floor():
    """Measure the round's component floors on THIS machine: the
    fastwire echo (wire both ways), the batched frame encode+decode,
    and the server's aggregate+SGD — so the headline number comes with
    its decomposition instead of a guess."""
    from paddle_tpu.distributed import fastwire
    from paddle_tpu.distributed.rpc import (_dec_tensor,
                                            _enc_tensor_parts,
                                            _iter_batch, _enc_batch_parts,
                                            _aligned_empty)

    floor = {}
    param = np.ones((DENSE_IN, DENSE_OUT), np.float32)
    mb = param.nbytes / 1e6

    # batched frame encode (parts, no join) + zero-copy decode over a
    # received-style buffer.  The join below happens OUTSIDE the timer:
    # the wire never pays it (vectored send / recv-into-one-buffer) —
    # this floor is the actual per-round framing overhead
    parts = _enc_batch_parts([_enc_tensor_parts("w", param)])
    joined = b"".join(bytes(p) if isinstance(p, bytes) else p.tobytes()
                      for p in parts)
    view = memoryview(joined)
    t0 = time.perf_counter()
    _enc_batch_parts([_enc_tensor_parts("w", param)])
    for frame in _iter_batch(view):
        _dec_tensor(frame)
    floor["enc_dec_%dmb_s" % round(mb)] = round(
        time.perf_counter() - t0, 4)

    # codec floor: int8 encode+decode of the same dense param — the
    # per-round cost compression adds before the wire saves 4x
    from paddle_tpu.distributed import compress as czip
    t0 = time.perf_counter()
    c = czip.compress(param, "int8")
    czip.decompress(c)
    floor["int8_codec_%dmb_s" % round(mb)] = round(
        time.perf_counter() - t0, 4)

    if fastwire.native_available():
        import socket as _s
        s = _s.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        srv = fastwire.FastServer(port, {"SendVariable": lambda req: req},
                                  addr="127.0.0.1")
        pool = fastwire.FastConnPool(0)
        conn = pool.checkout("127.0.0.1:%d" % port)
        if conn is not None:
            payload = _enc_tensor_parts("w", param)
            conn.call("SendVariable", payload)      # warm
            t0 = time.perf_counter()
            conn.call("SendVariable", payload)
            dt = time.perf_counter() - t0
            floor["echo_roundtrip_%dmb_s" % round(mb)] = round(dt, 3)
            floor["echo_mb_per_sec_both_ways"] = round(2 * mb / dt, 0)
            pool.discard(conn)
        srv.stop()

    # server aggregate (2-trainer mean into an aligned buffer) + SGD
    import jax
    g0, g1 = param, param
    w = jax.device_put(param).block_until_ready()
    sgd = jax.jit(lambda w, g: w - 0.01 * g)
    sgd(w, param).block_until_ready()               # warm/compile
    t0 = time.perf_counter()
    agg = _aligned_empty(param.shape, param.dtype)
    np.add(g0, g1, out=agg)
    agg *= 0.5
    sgd(w, agg).block_until_ready()
    floor["server_aggregate_plus_sgd_s"] = round(
        time.perf_counter() - t0, 3)
    return floor


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="pserver round-throughput benchmark "
                    "(2x2 localhost, real transpiled programs)")
    ap.add_argument("--quick", action="store_true",
                    help="small param + few rounds: a seconds-scale "
                    "smoke of the full data plane (CI tier-1)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the JSON line to PATH")
    ap.add_argument("--no-floor", action="store_true",
                    help="skip the component-floor measurements")
    ap.add_argument("--no-ctr", action="store_true",
                    help="skip the CTR-shaped scenario")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the dense compression-codec sweep")
    ap.add_argument("--sentinel", action="store_true",
                    help="gate this run against PERF_TRAJECTORY.json "
                         "via tools/perf_sentinel.py (rc 3 on a >15%% "
                         "regression vs the recorded floor; quick "
                         "runs only compare against quick floors).  "
                         "ROADMAP: always pass this")
    args = ap.parse_args(argv)

    if args.quick:
        # must be exported BEFORE bench() spawns: children re-import
        # this module and re-derive the model dims from the env
        os.environ.setdefault("PSB_DENSE_IN", "1024")
        os.environ.setdefault("PSB_DENSE_OUT", "1600")
        os.environ.setdefault("PSB_VOCAB", "50000")
        os.environ.setdefault("PSB_SPARSE_BATCH", "256")
        os.environ.setdefault("PSB_DENSE_STEPS", "3")
        os.environ.setdefault("PSB_SPARSE_STEPS", "3")
        os.environ.setdefault("PSB_CTR_STEPS", "3")
        os.environ.setdefault("PSB_CTR_VOCAB", "200000")
        os.environ.setdefault("PSB_CTR_BATCH", "512")
        global DENSE_IN, DENSE_OUT, VOCAB, SPARSE_BATCH
        global CTR_VOCAB, CTR_BATCH
        DENSE_IN = int(os.environ["PSB_DENSE_IN"])
        DENSE_OUT = int(os.environ["PSB_DENSE_OUT"])
        VOCAB = int(os.environ["PSB_VOCAB"])
        SPARSE_BATCH = int(os.environ["PSB_SPARSE_BATCH"])
        CTR_VOCAB = int(os.environ["PSB_CTR_VOCAB"])
        CTR_BATCH = int(os.environ["PSB_CTR_BATCH"])
    dense_steps = int(os.environ.get("PSB_DENSE_STEPS", "20"))
    sparse_steps = int(os.environ.get("PSB_SPARSE_STEPS", "50"))
    ctr_steps = int(os.environ.get("PSB_CTR_STEPS", "12"))

    # the headline dense/sparse numbers stay codec-free (comparable
    # round over round); the sweep and the CTR scenario carry the
    # ISSUE 10 knobs explicitly
    base_env = {"FLAGS_dist_compress":
                os.environ.get("FLAGS_dist_compress", "")}
    dense_rps, _ = bench("dense", dense_steps, base_port=19310,
                         env=base_env)
    sparse_rps, _ = bench("sparse", sparse_steps, base_port=19330,
                          env=base_env)

    dense_mb = DENSE_IN * DENSE_OUT * 4 / 1e6
    # per sync round the server side moves, per trainer: grad up +
    # fresh param down; aggregate wire traffic = 2 trainers x 2 dirs
    wire_mb_s = dense_rps * dense_mb * 2 * 2
    # distinct rows actually touched per step (2 trainers' batches)
    distinct = distinct_rows_per_step("sparse", min(8, sparse_steps))
    rows_s = sparse_rps * distinct
    round_ms = 1000.0 / dense_rps
    out = {
        "metric": "pserver_bench",
        "quick": bool(args.quick),
        "dense_param_mb": round(dense_mb, 1),
        "dense_rounds_per_sec": round(dense_rps, 2),
        "dense_wire_mb_per_sec": round(wire_mb_s, 1),
        "dense_round_ms": round(round_ms, 1),
        "sparse_rows_per_sec": round(rows_s, 0),
        "sparse_steps_per_sec": round(sparse_rps, 2),
        "pservers": 2,
        "trainers": 2,
        # the "can it feed one chip" statement: a 100 ms accelerator
        # step overlapped 1:1 with a sync round of this 100 MB model
        "fraction_of_chip_step": round(round_ms / 100.0, 2),
    }
    if not args.no_sweep:
        try:
            out["dense_compress"] = compress_sweep(
                max(3, dense_steps // 3), base_port=19400)
        except Exception as e:
            out["dense_compress_error"] = str(e)[:200]
    if not args.no_ctr:
        try:
            out["ctr"] = ctr_bench(ctr_steps, base_port=19600)
        except Exception as e:
            out["ctr_error"] = str(e)[:200]
    if not args.no_floor:
        try:
            out["component_floor"] = component_floor()
        except Exception as e:   # floors are evidence, not the metric
            out["component_floor_error"] = str(e)[:200]
    line = json.dumps(out)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")
    if args.sentinel:
        # perf sentinel (ISSUE 13): rc 3 when a measured metric
        # regresses >15% against its recorded PERF_TRAJECTORY floor
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from perf_sentinel import sentinel_gate

        return sentinel_gate(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
