"""Driver ``serve_jamba_closed``: a ``jamba`` model with dense
feed-forwards (Mamba-1 and multi-query attention layers, a gated SiLU
feed-forward in each, the head tied: ``paddle_tpu/serving/jamba.py``)
served whole through ``InferenceServer.load_generative`` / ``generate``
to a closed loop of callers.

``serve_lm_closed`` with another model behind it, as
``serve_nemotron_h_closed`` is: the callers, the window, the sample and
the release are that driver's.  The weights come from
``lib/reference_jamba.py`` (made on the device leaf by leaf, 6.06 GB in
bfloat16: they never visit the host), the counts from
``lib/counts_jamba.py``, and the comparison walks the reference layer
by layer after the program's state is freed.  What the decode steps
counted (bucket rows, prompt tokens scanned) becomes the work the two
kernels' roofline shares are read against.

The model's module is imported here, where the driver is loaded: a
checkout without it fails at once, before any set-up.
"""
import gc
import threading
import time

import numpy as np

from benchmark.drivers.serve_lm_closed import FAILED_MS, QUIET_S, TENANT
from benchmark.drivers.serve_lm_closed import Driver as ClosedLoop
from benchmark.lib import check, counts_jamba
from benchmark.lib import reference_jamba as reference
from benchmark.lib.reduce import percentile
from benchmark.lib.stages import StageClock
from benchmark.lib.traffic import RequestStream
from paddle_tpu.serving.jamba import JambaConfig


def model_sizes(cfg):
    """The reference's sizes from the configuration's file."""
    s = counts_jamba.shape(cfg)
    return dict(
        vocab=s["vocab"], hidden=s["d"], pattern=s["pattern"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        d_inner=s["d_inner"], state=s["state"], dt_rank=s["dt_rank"],
        conv_kernel=s["conv_kernel"], ff=s["ff"],
        eps=float(cfg["rms_norm_eps"]), **cfg["init"])


class Driver(ClosedLoop):
    def __init__(self, config, **kw):
        super().__init__(config=config, **kw)
        self.model_sizes = model_sizes(config)
        self.shape = counts_jamba.shape(config)
        if config["num_experts"] != 1 or not config["tie_word_embeddings"]:
            raise ValueError("the served jamba has one dense feed-forward "
                             "and a tied head")

    # -- set-up -------------------------------------------------------------
    def setup(self):
        import jax

        import paddle_tpu.fluid as fluid
        from paddle_tpu.observability import metrics as program_metrics
        from paddle_tpu.serving import InferenceServer

        c, m, sizes = self.config, self.model, self.model_sizes
        stage = StageClock(self.log)
        self._program_metrics = program_metrics
        place = (fluid.TPUPlace() if self.device.platform == "tpu"
                 else fluid.CPUPlace())
        with jax.default_device(self.device):
            params = reference.stacked_params(self.seed, sizes)
        jax.block_until_ready(params)
        stage("seed's weights, on the device")
        served = JambaConfig(
            vocab=sizes["vocab"], hidden=sizes["hidden"],
            n_layers=c["num_hidden_layers"],
            attn_offset=c["attn_layer_offset"],
            attn_period=c["attn_layer_period"], n_heads=sizes["n_heads"],
            n_kv_heads=sizes["n_kv_heads"], d_inner=sizes["d_inner"],
            state=sizes["state"], dt_rank=sizes["dt_rank"],
            conv_kernel=sizes["conv_kernel"], ff=sizes["ff"],
            eps=sizes["eps"], block_size=m["block_size"],
            max_blocks=m["max_blocks"], max_batch=m["max_batch"])
        self.srv = InferenceServer(place=place)
        self.engine = self.srv.load_generative(
            TENANT, served, params, kv_blocks=m["kv_blocks"])
        del params
        gc.collect()
        stage("tenant loaded")
        self._warm_decode_buckets({"block_size": m["block_size"]})
        stage("decode buckets warmed")
        self.stream = RequestStream(self.mix, self.seed, sizes["vocab"])
        self.threads = [threading.Thread(target=self._client, daemon=True,
                                         name="bench-client-%d" % i)
                        for i in range(int(self.mix["clients"]))]
        t0 = time.monotonic()
        for t in self.threads:
            t.start()
        # the same traffic until nothing has compiled for a while
        while True:
            time.sleep(0.25)
            now = time.monotonic()
            if now - t0 >= self.mix["warmup_seconds"] and \
                    now - max(self.meter.last, t0) >= QUIET_S:
                break
            if now - t0 > 600:
                raise RuntimeError("warm-up never went quiet")
        self.engine.drain_compiles()
        print("warm-up: %.1f s, %d requests" % (
            time.monotonic() - t0, len(self.records)), file=self.log)

    def _warm_decode_buckets(self, lm):
        """``serve_lm_closed``'s, with the ladder of block counts ending
        at the table's width: ``max_blocks`` (192) is the top bucket and
        no power of two."""
        top, n = self.model["max_blocks"], int(self.mix["clients"])
        mix = self.mix
        need = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"])
                 // lm["block_size"])
        nb = 1 << (-(-(mix["prompt_len"]["lo"] + 1) // lm["block_size"])
                   - 1).bit_length()
        while True:
            self.engine.decode_step([[0] * min(nb, top)] * n, [0] * n,
                                    [0] * n)
            self.engine.drain_compiles()
            if nb >= min(need, top):
                break
            nb *= 2

    # -- the window ---------------------------------------------------------
    def _reduce(self, t0, t1, compiles, hung, counters):
        s = self.shape
        window = t1 - t0
        with self._lock:
            records = list(self.records)
        tokens = flops = 0
        ttft, itl, done, failed = [], [], [], hung
        for r in records:
            in_window = t0 <= r["t_done"] <= t1
            if "error" in r or len(r.get("tokens", ())) != r["n_out"]:
                if in_window or r["t_done"] > t1:
                    failed += 1
                    ttft.append(FAILED_MS)
                    print("failed request %d: %s" % (
                        r["i"], r.get("error", "short")), file=self.log)
                continue
            n = len(r["prompt"])
            t = r["t_submit"] + r["ttft_ms"] / 1e3
            if t0 <= t <= t1:
                tokens += 1
                ttft.append(r["ttft_ms"])
                flops += counts_jamba.prefill_flops(s, n)
            for j, gap in enumerate(r["itl_ms"]):
                t += gap / 1e3
                if t0 <= t <= t1:
                    tokens += 1
                    itl.append(gap)
                    flops += counts_jamba.decode_flops(s, n + j + 1)
            if in_window:
                done.append(r)
        self.done = done
        counters["compiles_in_window"] = compiles
        print("serve window: %.3f s, %d requests done, %d failed, %d tokens, "
              "%d compiles" % (window, len(done), failed, tokens, compiles),
              file=self.log)
        # what the steps themselves counted, as the two kernels' work:
        # the rows whose state the decode update read and wrote, and the
        # real prompt tokens the scan walked
        rows = counters.get("serve_decode_slots_total", 0)
        scanned = counters.get("serve_scan_tokens_total", 0)
        work = {
            "mamba1_decode": {
                "bytes": rows * counts_jamba.state_bytes_per_row(s),
                "flops": rows * counts_jamba.scan_flops_per_token(s)},
            "selective_scan": {
                "bytes": scanned * counts_jamba.scan_bytes_per_token(s),
                "flops": scanned * counts_jamba.scan_flops_per_token(s)}}
        e2e = {"serve_tokens_per_s": tokens / window}
        if itl:
            e2e["itl_p95_ms"] = percentile(itl, 95)
        return {"attempted": len(done) + failed, "failed": failed,
                "end_to_end": e2e,
                "facts": {"window_s": window, "tokens": tokens,
                          "n_layers": len(s["pattern"]),
                          "model_flops": flops, "kernel_work": work,
                          "counters": counters,
                          "requests_done": len(done),
                          "ttft_ms": ttft, "itl_ms": itl}}

    # -- after the window ---------------------------------------------------
    def check(self, record, control=None):
        """``serve_lm_closed``'s three numbers against this model's
        reference: the widest and the mean gap by which a served token's
        logit lies below the reference's best, over the sampled
        requests, and the requests that failed.  ``control``
        (``controls.py`` only): one of the reference's ``CONTROLS``."""
        sample = self.sample()
        values = {"logit_gap": float("inf"), "logit_gap_mean": float("inf"),
                  "failed_requests": float(record["failed"])}
        if sample:
            gaps = reference.served_gaps(
                self.seed, self.model_sizes,
                [(r["prompt"], r["tokens"]) for r in sample],
                control=control)
            values["logit_gap"] = float(max(g.max() for g in gaps))
            values["logit_gap_mean"] = float(np.concatenate(gaps).mean())
            print("checked %d requests, %d served tokens" % (
                len(sample), sum(len(g) for g in gaps)), file=self.log)
        return check.verdicts(values, self.limits)
