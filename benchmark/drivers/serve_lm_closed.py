"""Driver ``serve_lm_closed``: a language model served through
``InferenceServer.load_generative`` / ``generate`` to a CLOSED loop of
callers — ``clients`` threads, each sending its next request the moment
its last one returns, no think time.

Requests come from ``lib.traffic.RequestStream`` (the mix's fixed set of
lengths in the seed's order, ids from the seed).  Set-up loads the tenant
with the seed's weights and runs this same traffic until the compile
meter has been quiet; the window then opens on the running system, and
closes after ``--seconds``: callers stop sending, every request in
flight is waited for.  A token's time is its request's enqueue time plus
the ``ttft_ms`` and ``itl_ms`` the future returned (the program's host
clock, stamped after the step has drained).
"""
import contextlib
import gc
import threading
import time

import numpy as np

from benchmark.lib import check, counts, reference
from benchmark.lib.reduce import percentile
from benchmark.lib.stages import StageClock

TENANT = "lm"
REQUEST_TIMEOUT_S = 120.0
FAILED_MS = 1e3 * REQUEST_TIMEOUT_S     # what a failed request counts as
QUIET_S = 3.0       # warm-up ends once nothing has compiled for so long


def engine_params(params, sizes):
    """The reference's stacked leaves (host arrays) in the layout
    ``GenerativeEngine`` takes: per-layer ``l<i>.`` names, q/k/v side
    by side in one ``wqkv``."""
    out = {"embed": params["embed"], "pos": params["pos"],
           "lnf.scale": params["lnf_scale"], "lnf.bias": params["lnf_bias"],
           "lm_head": params["head"]}
    for l in range(sizes["n_layers"]):
        p = "l%d." % l
        out[p + "wqkv"] = np.concatenate(
            [params[k][l] for k in ("wq", "wk", "wv")], axis=1)
        for ours, theirs in (("wo", "wo"), ("w1", "w1"), ("w2", "w2"),
                             ("ln1_scale", "ln1.scale"),
                             ("ln1_bias", "ln1.bias"),
                             ("ln2_scale", "ln2.scale"),
                             ("ln2_bias", "ln2.bias")):
            out[p + theirs] = params[ours][l]
    return out


def _no_span(name):
    return contextlib.nullcontext()


class Driver:
    def __init__(self, config, mix, seed, devices, peaks, meter, limits,
                 log):
        self.config, self.mix, self.seed = config, mix, seed
        self.sizes = config["sizes"]
        self.model = config["program"]
        self.device = devices[0]
        self.peaks, self.meter, self.log = peaks, meter, log
        self.limits = limits
        self.records = []
        self._lock = threading.Lock()
        self._next = 0
        self._closing = threading.Event()
        self._span = _no_span

    # -- what a test may break underneath -------------------------------
    def _generate(self, prompt, n_out):
        """One request through the served path; returns the future's
        result."""
        fut = self.srv.generate(TENANT, prompt, max_new_tokens=n_out)
        return fut.result(REQUEST_TIMEOUT_S)

    # -- the callers --------------------------------------------------------
    def _client(self):
        while not self._closing.is_set():
            with self._lock:
                i = self._next
                self._next += 1
            prompt, n_out = self.stream.request(i)
            rec = {"i": i, "prompt": prompt, "n_out": n_out,
                   "t_submit": time.perf_counter()}
            try:
                with self._span("client_wait"):
                    res = self._generate(prompt, n_out)
                rec["tokens"] = [int(t) for t in res["tokens"]]
                rec["ttft_ms"] = float(res["ttft_ms"])
                rec["itl_ms"] = [float(x) for x in res["itl_ms"]]
            except Exception as e:      # a failed request is a reading
                rec["error"] = "%s: %s" % (type(e).__name__, e)
            rec["t_done"] = time.perf_counter()
            with self._lock:
                self.records.append(rec)

    # -- set-up -------------------------------------------------------------
    def setup(self):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.observability import metrics as program_metrics
        from paddle_tpu.serving import InferenceServer

        from benchmark.lib.traffic import RequestStream

        s, m = self.sizes, self.model
        stage = StageClock(self.log)
        self._program_metrics = program_metrics
        place = (fluid.TPUPlace() if self.device.platform == "tpu"
                 else fluid.CPUPlace())
        dev = reference.init_params(self.seed, s, biases=False)
        host = {k: np.asarray(v) for k, v in dev.items()}
        del dev
        params = engine_params(host, s)
        del host
        stage("seed's weights, to the host")
        self.srv = InferenceServer(place=place)
        lm = dict(vocab=s["vocab_size"], d_model=s["d_model"],
                  n_heads=s["n_heads"], n_layers=s["n_layers"],
                  d_ff=s["d_ff"], block_size=m["block_size"],
                  max_blocks=m["max_blocks"], max_batch=m["max_batch"])
        if lm["block_size"] * lm["max_blocks"] != s["n_positions"]:
            raise ValueError("block_size x max_blocks must be n_positions")
        self.engine = self.srv.load_generative(
            TENANT, lm, params, quant=m["quant"], kv_blocks=m["kv_blocks"])
        del params
        gc.collect()
        stage("tenant loaded")
        self._warm_decode_buckets(lm)
        stage("decode buckets warmed")
        self.stream = RequestStream(self.mix, self.seed, s["vocab_size"])
        self.threads = [threading.Thread(target=self._client, daemon=True,
                                         name="bench-client-%d" % i)
                        for i in range(int(self.mix["clients"]))]
        t0 = time.monotonic()
        for t in self.threads:
            t.start()
        # the same traffic until nothing has compiled for a while
        quiet, least = QUIET_S, self.mix["warmup_seconds"]
        while True:
            time.sleep(0.25)
            now = time.monotonic()
            if now - t0 >= least and now - max(self.meter.last, t0) >= quiet:
                break
            if now - t0 > 600:
                raise RuntimeError("warm-up never went quiet")
        self.engine.drain_compiles()
        print("warm-up: %.1f s, %d requests" % (
            time.monotonic() - t0, len(self.records)), file=self.log)

    def _warm_decode_buckets(self, lm):
        """Every (batch, block-count) decode bucket the mix's lengths can
        reach with all callers running, compiled before any traffic: one
        raw decode step per bucket on the pool's scratch block (the
        engine compiles a missed bucket in the background; joined here),
        so that no window meets a cold one."""
        def blocks(tokens):
            return 1 << (-(-tokens // lm["block_size"]) - 1).bit_length()

        mix, n = self.mix, int(self.mix["clients"])
        lo = blocks(mix["prompt_len"]["lo"] + 1)
        hi = blocks(mix["prompt_len"]["hi"] + mix["output_len"]["hi"])
        nb = lo
        while nb <= hi:
            self.engine.decode_step([[0] * nb] * n, [0] * n, [0] * n)
            self.engine.drain_compiles()
            nb *= 2

    def _counters(self):
        snap = self._program_metrics.snapshot()
        return {k: v["value"] for k, v in snap.items()
                if v.get("type") == "counter" and k.startswith("serve_")}

    # -- the window ---------------------------------------------------------
    def measure(self, seconds, tracer):
        tracer.start()
        mark = self.meter.mark()
        buckets = set(self.engine.warm_decode_buckets)
        self._span = tracer.span
        c0 = self._counters()
        t0 = time.perf_counter()
        with tracer.span("window"):
            time.sleep(seconds)
        t1 = time.perf_counter()
        c1 = self._counters()
        # read before the callers stop: as the batch drains, the engine
        # compiles the narrower buckets it then needs
        since = self.meter.since(mark)
        new_buckets = sorted(set(self.engine.warm_decode_buckets) - buckets)
        self._closing.set()
        self._span = _no_span
        tracer.stop()
        compiles = since["compiles"]
        if compiles:
            print("compiled inside the window: %s, decode buckets %s" % (
                since["names"], new_buckets), file=self.log)
        for t in self.threads:
            t.join(REQUEST_TIMEOUT_S + 60.0)
        hung = sum(t.is_alive() for t in self.threads)
        return self._reduce(t0, t1, compiles, hung,
                            {k: c1[k] - c0.get(k, 0) for k in c1})

    def _reduce(self, t0, t1, compiles, hung, counters):
        s = self.sizes
        window = t1 - t0
        with self._lock:
            records = list(self.records)
        tokens = flops = page_bytes = page_flops = held = 0
        block = int(self.model["block_size"])
        ttft, itl, done, failed = [], [], [], hung
        for r in records:
            in_window = t0 <= r["t_done"] <= t1
            if "error" in r or len(r.get("tokens", ())) != r["n_out"]:
                if in_window or r["t_done"] > t1:
                    failed += 1
                    ttft.append(FAILED_MS)
                continue
            n = len(r["prompt"])
            t = r["t_submit"] + r["ttft_ms"] / 1e3
            if t0 <= t <= t1:
                tokens += 1
                ttft.append(r["ttft_ms"])
                flops += counts.prefill_flops(s, n)
            for j, gap in enumerate(r["itl_ms"]):
                t += gap / 1e3
                if t0 <= t <= t1:
                    tokens += 1
                    itl.append(gap)
                    ctx = n + j + 1
                    flops += counts.decode_flops(s, ctx)
                    page_bytes += counts.paged_decode_bytes(s, ctx)
                    page_flops += 4 * ctx * s["d_model"] * s["n_layers"]
                    held += -(-ctx // block)
            if in_window:
                done.append(r)
        self.done = done
        counters["compiles_in_window"] = compiles
        print("serve window: %.3f s, %d requests done, %d failed, %d tokens, "
              "%d compiles" % (window, len(done), failed, tokens, compiles),
              file=self.log)
        steps = counters.get("serve_decode_steps_total", 0)
        if steps:
            # blocks under the rows of a decode step, summed over the
            # window's decode tokens, a step: what the traffic holds of
            # the pool that the configuration reserves
            print("page pool: %.0f of %d blocks held at a decode step "
                  "(mean), %.1f%%" % (
                      held / steps, self.model["kv_blocks"],
                      100.0 * held / steps / self.model["kv_blocks"]),
                  file=self.log)
        e2e = {"serve_tokens_per_s": tokens / window}
        if itl:
            e2e["itl_p95_ms"] = percentile(itl, 95)
        return {"attempted": len(done) + failed, "failed": failed,
                "end_to_end": e2e,
                "facts": {"window_s": window, "tokens": tokens,
                          "n_layers": s["n_layers"],
                          "model_flops": flops,
                          "kernel_work": {"paged_decode": {
                              "flops": page_flops, "bytes": page_bytes}},
                          "counters": counters,
                          "requests_done": len(done),
                          "ttft_ms": ttft, "itl_ms": itl}}

    # -- after the window ---------------------------------------------------
    def release(self):
        import jax

        self.srv.close()
        self.srv = self.engine = None
        gc.collect()
        jax.clear_caches()
        stats = self.device.memory_stats() or {}
        print("released: %.2f GB still in use" % (
            stats.get("bytes_in_use", 0) / 1e9), file=self.log)

    def sample(self):
        """The requests the check reads: the longest one finished in the
        window and ``check_requests - 1`` more drawn from the seed."""
        done = sorted(self.done, key=lambda r: r["i"])
        if not done:
            return []
        longest = max(done, key=lambda r: len(r["prompt"]) + r["n_out"])
        rest = [r for r in done if r is not longest]
        k = min(len(rest), int(self.mix["check_requests"]) - 1)
        pick = np.random.default_rng([self.seed, 4]).choice(
            len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[int(i)] for i in pick]

    def check(self, record, control=None):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled requests, and the mean gap of
        all their tokens (it counts how often and how far the served
        token is not the reference's first, and over some thousands of
        tokens it is steady from seed to seed); and how many requests
        came back with another number of tokens than asked.  ``control``
        (``controls.py``, never a benchmark run) names a lower precision
        whose own first choices are read in the served tokens' place."""
        sample = self.sample()
        values = {"logit_gap": float("inf"), "logit_gap_mean": float("inf"),
                  "failed_requests": float(record["failed"])}
        if sample:
            s, mix = self.sizes, self.mix
            params = reference.init_params(self.seed, s, biases=False)
            longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
            gaps = [reference.served_gaps(
                params, r["prompt"], r["tokens"],
                reference.padded(len(r["prompt"]) + r["n_out"], longest),
                mix["output_len"]["hi"], s["n_heads"], self.model["act"],
                control=control)
                for r in sample]
            values["logit_gap"] = float(max(g.max() for g in gaps))
            values["logit_gap_mean"] = float(np.concatenate(gaps).mean())
            print("checked %d requests, %d served tokens" % (
                len(sample), sum(len(g) for g in gaps)), file=self.log)
        return check.verdicts(values, self.limits)
