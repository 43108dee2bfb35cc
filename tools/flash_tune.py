"""Flash-attention kernel tuner at the secondary-bench shape.

Measures fwd+bwd wall time of the Pallas flash kernels on the real chip
at the transformer-LM bench shape (B=16, H=16, T=2048, D=64, causal) for
a grid of (block_q, block_k) and input dtypes, with the microbench traps
handled (varying inputs chained on device via lax.scan, one final d2h
drain — see .claude/skills/verify/SKILL.md).

``--ring`` sweeps the ISSUE 15 ring-attention CHUNK tiles instead: the
per-ring-step fwd+bwd pair at the longctx shard shape (one Q shard
against one K/V block, online-softmax carry threaded), recording
``ring_attention``-keyed entries the ring lowering resolves through
(kernels/flash_attention.resolve_chunk_blocks).

Usage: python tools/flash_tune.py [steps] [--ring]
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels.flash_attention import flash_attention  # noqa: E402

B, H, T, D = 16, 8, 2048, 128   # the secondary-bench shape
# the longctx ring shard shape: 64k tokens over an 8-wide sp axis
RING_B, RING_H, RING_SQ, RING_D = 1, 8, 8192, 128
_args = [a for a in sys.argv[1:] if not a.startswith("-")]
RING = "--ring" in sys.argv[1:]
STEPS = int(_args[0]) if _args else 12

# causal fwd+bwd analytic useful FLOPs (fwd 4*BHT^2*D, bwd 2.5x, /2 causal)
FLOPS = 0.5 * (4 + 10) * B * H * T * T * D


def bench(dtype, block_q, block_k, force_xla=False,
          block_q_bwd=0, block_k_bwd=0, block_q_dkv=0, block_k_dkv=0):
    # NO lax.scan: kernels inside a while loop measured ~2x slower than
    # the identical kernels in the bench's straight-line step —
    # unroll over distinct pre-staged inputs instead,
    # which matches how the model invokes them.
    rng = np.random.RandomState(0)
    base = [(jnp.asarray(rng.randn(B, H, T, D), dtype),
             jnp.asarray(rng.randn(B, H, T, D), dtype),
             jnp.asarray(rng.randn(B, H, T, D), dtype))
            for _ in range(STEPS)]

    bqb, bkb = (block_q_bwd or None), (block_k_bwd or None)
    bqd, bkd = (block_q_dkv or None), (block_k_dkv or None)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=block_q,
                            block_k=block_k, force_xla=force_xla,
                            block_q_bwd=bqb, block_k_bwd=bkb,
                            block_q_dkv=bqd, block_k_dkv=bkd)
        return (o.astype(jnp.float32) ** 2).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def run(ops):
        acc = 0.0
        for q, k, v in ops:      # unrolled: STEPS independent fwd+bwd
            dq, dk, dv = grad(q, k, v)
            acc = acc + dq[0, 0, 0, 0].astype(jnp.float32) + \
                dk[0, 0, 0, 0].astype(jnp.float32)
        return acc

    r = run(base)
    float(np.asarray(r))              # warm-up + compile, full drain
    t0 = time.time()
    r = run(base)
    float(np.asarray(r))              # d2h drain is the only true sync
    dt = (time.time() - t0) / STEPS
    return dt


def _record(kernel, shape, cfg, best_sec, source):
    """Persist a sweep winner into the shape-keyed autotune cache
    (FLAGS_autotune_cache_dir; no-op when unset) — the kernels'
    lowerings pick it up at the next compile (ISSUE 7).  The ONE
    persist-and-report path for every sweep in this tool."""
    from paddle_tpu import tuning

    ok = tuning.record(kernel, shape, "bfloat16", cfg,
                       ms=best_sec * 1e3, source=source)
    if ok:
        print("autotune cache <- %s %s (%s)"
              % (kernel, cfg, tuning.cache_path()))
    else:
        print("autotune cache unset (FLAGS_autotune_cache_dir) — "
              "winner not persisted")


def _record_best(best_cfg, best_sec):
    bq, bk, bqb, bkb, bqd, bkd = best_cfg
    cfg = {"block_q": bq, "block_k": bk}
    for key, val in (("block_q_bwd", bqb), ("block_k_bwd", bkb),
                     ("block_q_dkv", bqd), ("block_k_dkv", bkd)):
        if val:
            cfg[key] = val
    _record("flash_attention", (B, H, T, D, T), cfg, best_sec,
            "flash_tune")


def bench_ring_chunk(dtype, block_q, block_k, steps):
    """fwd+bwd wall of ONE ring chunk update (the per-ring-step inner
    compute): fold a K/V block into the carry, finalize, backprop
    through the chunk pair — the unit the ring loop repeats p times."""
    from paddle_tpu.kernels.flash_attention import (
        NEG_INF, chunk_finalize, flash_attention_chunk,
        flash_attention_chunk_bwd)

    rng = np.random.RandomState(0)
    base = [tuple(jnp.asarray(rng.randn(RING_B, RING_H, RING_SQ, RING_D),
                              dtype) for _ in range(3))
            for _ in range(steps)]

    def one(q, k, v):
        m = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
        l = jnp.zeros(q.shape[:3], jnp.float32)
        acc = jnp.zeros(q.shape, jnp.float32)
        m, l, acc = flash_attention_chunk(
            q, k, v, m, l, acc, causal=True, block_q=block_q,
            block_k=block_k)
        out, lse = chunk_finalize(m, l, acc, q.dtype)
        do = out  # any cotangent of the right shape/dtype
        delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
        dq, dk, dv = flash_attention_chunk_bwd(
            q, k, v, do, lse, delta, causal=True, block_q=block_q,
            block_k=block_k)
        return dq[0, 0, 0, 0].astype(jnp.float32) + \
            dk[0, 0, 0, 0].astype(jnp.float32)

    @jax.jit
    def run(ops):
        acc = 0.0
        for q, k, v in ops:      # unrolled, like bench()
            acc = acc + one(q, k, v)
        return acc

    float(np.asarray(run(base)))      # warm-up + compile
    t0 = time.time()
    float(np.asarray(run(base)))
    return (time.time() - t0) / steps


def main_ring():
    print("ring chunk shape B=%d H=%d Sq=Sk=%d D=%d causal diag, "
          "%d chained steps" % (RING_B, RING_H, RING_SQ, RING_D, STEPS))
    # causal diag fwd+bwd useful FLOPs of one chunk (/2 causal diag)
    flops = 0.5 * (4 + 10) * RING_B * RING_H * RING_SQ * RING_SQ * RING_D
    configs = [(1024, 1024), (512, 1024), (1024, 512), (512, 512),
               (2048, 1024), (1024, 2048), (256, 1024), (2048, 2048)]
    best_cfg, best_sec = None, None
    for bq, bk in configs:
        try:
            sec = bench_ring_chunk(jnp.bfloat16, bq, bk, STEPS)
            print("bf16 (%4d,%4d)  %9.2f ms  %7.1f TF/s"
                  % (bq, bk, sec * 1e3, flops / sec / 1e12))
            if best_sec is None or sec < best_sec:
                best_cfg, best_sec = (bq, bk), sec
        except Exception as exc:  # noqa: BLE001 — tuning survey
            print("bf16 (%4d,%4d)  FAILED: %s" % (bq, bk,
                                                  str(exc)[:80]))
    if best_cfg is None:
        return
    _record("ring_attention",
            (RING_B, RING_H, RING_SQ, RING_D, RING_SQ),
            {"block_q": best_cfg[0], "block_k": best_cfg[1]},
            best_sec, "flash_tune --ring")


def main():
    if RING:
        return main_ring()
    print("shape B=%d H=%d T=%d D=%d causal, %d chained steps" %
          (B, H, T, D, STEPS))
    print("%-10s %6s %6s %9s %9s" % ("dtype", "bq", "bk", "ms/step",
                                     "TFLOP/s"))
    # (fwd_bq, fwd_bk, bwd_bq, bwd_bk, dkv_bq, dkv_bk); 0 = default —
    # bwd tiles cover dQ, the dkv pair overrides the transpose-free
    # dK/dV kernel alone (its [bk, bq] tiles stream the Q axis, so its
    # optimum can differ from dQ's; VERDICT r5 weak #2)
    configs = [
        (1024, 1024, 0, 0, 0, 0),      # current defaults (bwd capped 512)
        (1024, 1024, 512, 1024, 0, 0),
        (1024, 1024, 1024, 512, 0, 0),
        (1024, 1024, 256, 512, 0, 0),
        (1024, 1024, 512, 256, 0, 0),
        (1024, 1024, 256, 1024, 0, 0),
        (512, 1024, 0, 0, 0, 0),
        (512, 512, 0, 0, 0, 0),
        (1024, 2048, 0, 0, 0, 0),
        (1024, 2048, 512, 2048, 0, 0),
        # dkv-only sweeps at the best dq configuration
        (1024, 1024, 512, 1024, 1024, 512),
        (1024, 1024, 512, 1024, 2048, 512),
        (1024, 1024, 512, 1024, 512, 512),
        (1024, 1024, 512, 1024, 256, 1024),
        (1024, 1024, 512, 1024, 1024, 1024),
    ]
    best_cfg, best_sec = None, None
    for bq, bk, bqb, bkb, bqd, bkd in configs:
        try:
            sec = bench(jnp.bfloat16, bq, bk, False, bqb, bkb, bqd, bkd)
            print("bf16 fwd(%4d,%4d) bwd(%4s,%4s) dkv(%4s,%4s) "
                  "%9.2f ms  %7.1f TF/s" %
                  (bq, bk, bqb or "cap", bkb or "cap", bqd or "=bwd",
                   bkd or "=bwd", sec * 1e3, FLOPS / sec / 1e12))
            if best_sec is None or sec < best_sec:
                best_cfg, best_sec = (bq, bk, bqb, bkb, bqd, bkd), sec
        except Exception as exc:  # noqa: BLE001 — tuning survey
            print("bf16 fwd(%4d,%4d) bwd(%4s,%4s) dkv(%4s,%4s)  "
                  "FAILED: %s" %
                  (bq, bk, bqb or "cap", bkb or "cap", bqd or "=bwd",
                   bkd or "=bwd", str(exc)[:80]))
    if best_cfg is not None:
        _record_best(best_cfg, best_sec)


if __name__ == "__main__":
    main()
