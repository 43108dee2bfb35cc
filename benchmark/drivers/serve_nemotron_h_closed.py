"""Driver ``serve_nemotron_h_closed``: a ``nemotron_h`` stage (Mamba-2,
latent experts, grouped-query attention: ``paddle_tpu/serving/
nemotron_h.py``) served through ``InferenceServer.load_generative`` /
``generate`` to a closed loop of callers.

``serve_lm_closed`` with another model behind it: the callers, the
window, the sample and the release are that driver's.  What differs is
what a model with its own configuration needs: the weights come from
``lib/reference_nemotron_h.py`` (made on the device leaf by leaf, 9.3 GB
in bfloat16: they never visit the host), the counts from
``lib/counts_nemotron_h.py``, and the comparison walks the reference
layer by layer after the program's state is freed.  The decode steps'
own counters (experts hit, pairs, bucket rows) become the work the two
kernels' roofline shares are read against.
"""
import gc
import threading
import time

import numpy as np

from benchmark.drivers.serve_lm_closed import FAILED_MS, QUIET_S, TENANT
from benchmark.drivers.serve_lm_closed import Driver as ClosedLoop
from benchmark.lib import check, counts_nemotron_h
from benchmark.lib import reference_nemotron_h as reference
from benchmark.lib.reduce import percentile
from benchmark.lib.stages import StageClock
from benchmark.lib.traffic import RequestStream


def model_sizes(cfg):
    """The reference's and the served model's sizes from the
    configuration's file: published keys, and ``stage`` for which
    blocks, experts and ids are here."""
    stage = cfg["stage"]
    held = stage["experts_held"]
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        pattern=stage["pattern"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        state=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"],
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        experts_held=range(held[0], held[1] + 1),
        latent=cfg["moe_latent_size"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_shared_expert_intermediate_size"],
        scaling=float(cfg["routed_scaling_factor"]),
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"])


class Driver(ClosedLoop):
    def __init__(self, config, **kw):
        super().__init__(config=config, **kw)
        self.model_sizes = model_sizes(config)
        self.shape = counts_nemotron_h.shape(config)
        if len(config["stage"]["pattern"]) != config["num_hidden_layers"] \
                or self.shape["held"] != config["n_routed_experts"]:
            raise ValueError("stage and the reduced keys disagree")

    # -- set-up -------------------------------------------------------------
    def setup(self):
        import jax

        import paddle_tpu.fluid as fluid
        from paddle_tpu.observability import metrics as program_metrics
        from paddle_tpu.serving import InferenceServer
        from paddle_tpu.serving.nemotron_h import NemotronHConfig

        m, sizes = self.model, self.model_sizes
        stage = StageClock(self.log)
        self._program_metrics = program_metrics
        place = (fluid.TPUPlace() if self.device.platform == "tpu"
                 else fluid.CPUPlace())
        with jax.default_device(self.device):
            params = reference.stacked_params(self.seed, sizes)
        jax.block_until_ready(params)
        stage("seed's weights, on the device")
        served = NemotronHConfig(
            block_size=m["block_size"], max_blocks=m["max_blocks"],
            max_batch=m["max_batch"],
            **{k: v for k, v in sizes.items()
               if not k.startswith("time_step")})
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
                flops += counts_nemotron_h.prefill_flops(s, n)
            for j, gap in enumerate(r["itl_ms"]):
                t += gap / 1e3
                if t0 <= t <= t1:
                    tokens += 1
                    itl.append(gap)
                    flops += counts_nemotron_h.decode_flops(s, n + j + 1)
            if in_window:
                done.append(r)
        self.done = done
        counters["compiles_in_window"] = compiles
        print("serve window: %.3f s, %d requests done, %d failed, %d tokens, "
              "%d compiles" % (window, len(done), failed, tokens, compiles),
              file=self.log)
        # what the decode steps themselves counted, as the two kernels'
        # work: the experts whose weights were read and the pairs
        # computed; the rows whose state was read and written
        hit = counters.get("serve_moe_experts_hit_total", 0)
        pairs = counters.get("serve_moe_pairs_total", 0)
        rows = counters.get("serve_decode_slots_total", 0)
        work = {
            "moe_decode": {
                "bytes": hit * counts_nemotron_h.expert_bytes(s),
                "flops": pairs * counts_nemotron_h.expert_flops(s)},
            "ssm_decode": {
                "bytes": rows * counts_nemotron_h.state_bytes_per_row(s),
                "flops": rows * counts_nemotron_h.scan_flops_per_token(s)}}
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
