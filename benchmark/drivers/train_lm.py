"""Driver ``train_lm``: a language model trained through the program's
own hot path — ``models.transformer.get_model`` under bf16 AMP
(``Float16Transpiler``), ``fluid.Executor.prepare`` and ``run_prepared``
back to back on one batch staged on the device.

Set-up builds ONE prepared program with its state, fills it with the
seed's weights (``lib.reference.init_params``), drives it through its
first ``check_steps`` steps (recording what the check compares), warms
up, and hands that same object to the window.  The reference follows
the same steps after the window, once the program's state is freed.
"""
import gc
import time

import numpy as np

from benchmark.lib import check, counts, reference, traffic
from benchmark.lib.stages import StageClock


def program_leaves(sizes):
    """The reference's leaves in the order the program creates its
    parameters: (leaf, layer index or None)."""
    order = [("embed", None), ("pos", None)]
    for l in range(sizes["n_layers"]):
        order += [("ln1_scale", l), ("ln1_bias", l), ("wq", l), ("wk", l),
                  ("wv", l), ("wo", l), ("bo", l), ("ln2_scale", l),
                  ("ln2_bias", l), ("w1", l), ("b1", l), ("w2", l),
                  ("b2", l)]
    return order + [("lnf_scale", None), ("lnf_bias", None),
                    ("head", None), ("head_b", None)]


def _as_tree(named, sizes):
    """[(leaf, layer), value] pairs of per-parameter readings (a norm,
    or a row of sampled elements) -> the reference's layout: {leaf:
    value, or one value per layer}."""
    out = {}
    for (leaf, l), v in named:
        if l is None:
            out[leaf] = v
        else:
            out.setdefault(leaf, [None] * sizes["n_layers"])[l] = v
    return out


class Driver:
    def __init__(self, config, mix, seed, devices, peaks, meter, limits,
                 log):
        self.config, self.mix, self.seed = config, mix, seed
        self.sizes = config["sizes"]
        self.model = config["program"]
        self.device = devices[0]
        self.peaks, self.meter, self.log = peaks, meter, log
        self.limits = limits
        self.batch, self.seq = int(mix["batch"]), int(mix["seq_len"])
        self.check_steps = int(mix["check_steps"])
        # how far the host may run ahead of the device: deep enough that
        # a host that is descheduled for some hundreds of milliseconds
        # (it shares its cores) does not idle the chip
        self.in_flight = int(mix["steps_in_flight"])
        if self.seq != self.sizes["n_positions"]:
            raise ValueError("the program's position table is seq_len long")

    # -- what a test may break underneath -------------------------------
    def _feed_arrays(self):
        """The batch the program is fed (the reference always sees the
        whole batch)."""
        return self.tokens, self.labels

    def _step(self):
        return self.prepared.run_prepared(self.feed)

    # -- set-up -----------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.fluid as fluid
        from paddle_tpu.models import transformer

        s = self.sizes
        stage = StageClock(self.log)
        place = (fluid.TPUPlace() if self.device.platform == "tpu"
                 else fluid.CPUPlace())
        self.tokens, self.labels = traffic.lm_batch(
            self.seed, self.batch, self.seq, s["vocab_size"])
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup), \
                fluid.unique_name.guard():
            avg_cost, (src, label), _ = transformer.get_model(
                vocab_size=s["vocab_size"], seq_len=self.seq,
                d_model=s["d_model"], n_head=s["n_heads"],
                n_layers=s["n_layers"], d_ff=s["d_ff"],
                learning_rate=self.model["learning_rate"])
        if self.model["amp"] == "bf16":
            fluid.transpiler.Float16Transpiler().transpile(main_prog)
        self.scope = fluid.Scope()
        self._guard = fluid.scope_guard(self.scope)
        self._guard.__enter__()
        exe = fluid.Executor(place)
        exe.run(startup)

        # the seed's weights into the program, parameter by parameter
        params = main_prog.global_block().all_parameters()
        leaves = program_leaves(s)
        if len(params) != len(leaves):
            raise RuntimeError("the program has %d parameters, the "
                               "reference %d" % (len(params), len(leaves)))
        self.names = [p.name for p in params]
        stage("program built, startup run")
        self._fill(leaves)
        stage("seed's weights filled in")

        moments, beta1 = {}, None
        for op in main_prog.global_block().ops:
            if op.type == "adam":
                moments[op.input("Param")[0]] = op.input("Moment1")[0]
                beta1 = op.attr("beta1")
        toks, labs = self._feed_arrays()
        self.feed = {src.name: jax.device_put(toks, self.device),
                     label.name: jax.device_put(labs, self.device)}
        self.prepared = exe.prepare(main_prog, feed_specs=self.feed,
                                    fetch_list=[avg_cost])

        # the first steps, through the window's own call and feed
        @jax.jit
        def norms(xs):
            return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                    for x in xs]

        losses = []
        for t in range(1, self.check_steps + 1):
            losses.append(float(np.asarray(self._step()[0]).reshape(-1)[0]))
            if t == 1:
                # the first gradient as the optimizer got it: Adam's
                # first moment after one step is (1 - beta1) * g
                m1 = [self.scope.find_var(moments[n]) for n in self.names]
                idx = [reference.sample_index(self.seed, leaf, l, x.size)
                       for (leaf, l), x in zip(leaves, m1)]
                picked = jax.jit(lambda xs, ids: [
                    jnp.take(x.reshape(-1), i) for x, i in zip(xs, ids)])(
                        m1, idx)
                grad = [float(x) / (1.0 - beta1) for x in norms(m1)]
                elems = [np.asarray(x, np.float32) / (1.0 - beta1)
                         for x in picked]
        start = self._reference_leaves(leaves)
        now = [self.scope.find_var(n) for n in self.names]
        change = jax.jit(lambda a, b: norms([x - y for x, y in zip(a, b)]))(
            now, start)
        change = [float(x) for x in change]
        del start, now, m1, picked
        stage("prepare + first %d steps" % self.check_steps)
        self.got = {"losses": losses,
                    "grad_norms": _as_tree(zip(leaves, grad), s),
                    "grad_samples": _as_tree(zip(leaves, elems), s),
                    "change_norms": _as_tree(zip(leaves, change), s)}
        # warm-up: the steps after a scope read re-stage once
        for _ in range(int(self.mix["warmup_steps"])):
            out = self._step()
        jax.block_until_ready(out)
        stage("warm-up steps")
        self.tokens_per_step = self.batch * self.seq

    def _reference_leaves(self, leaves):
        ref = reference.init_params(self.seed, self.sizes, biases=True)
        out = [ref[leaf] if l is None else ref[leaf][l] for leaf, l in leaves]
        del ref
        return out

    def _fill(self, leaves):
        for name, value in zip(self.names, self._reference_leaves(leaves)):
            have = self.scope.find_var(name)
            if tuple(have.shape) != tuple(value.shape):
                raise RuntimeError("parameter %s is %s in the program, %s "
                                   "in the reference" % (
                                       name, have.shape, value.shape))
            self.scope.set(name, value)

    # -- the window -------------------------------------------------------
    def measure(self, seconds, tracer):
        import jax

        mark = self.meter.mark()
        call_s, steps, pending, done_at = 0.0, 0, [], []
        tracer.start()
        t0 = time.perf_counter()
        with tracer.span("window"):
            while time.perf_counter() - t0 < seconds:
                c0 = time.perf_counter()
                with tracer.span("step_call"):
                    out = self._step()
                call_s += time.perf_counter() - c0
                steps += 1
                pending.append(out[0])
                if len(pending) > self.in_flight:
                    with tracer.span("throttle"):
                        jax.block_until_ready(pending.pop(0))
                    done_at.append(time.perf_counter())
            with tracer.span("drain"):
                jax.block_until_ready(pending)
        window = time.perf_counter() - t0
        tracer.stop()
        since = self.meter.since(mark)
        compiles = since["compiles"]
        if compiles:
            print("compiled inside the window: %s" % since["names"],
                  file=self.log)
        last = float(np.asarray(pending[-1]).reshape(-1)[0])
        tokens = steps * self.tokens_per_step
        flops = tokens * counts.train_flops_per_token(self.sizes, self.seq)
        fl, by = counts.flash_train_work(self.sizes, self.batch, self.seq)
        print("train window: %d steps in %.3f s, last loss %.4f, %d compiles"
              % (steps, window, last, compiles), file=self.log)
        gaps = sorted(np.diff(done_at))
        if gaps:        # a step that ended late: the chip waited for the host
            print("between steps ending: median %.1f ms, longest %.1f ms, "
                  "%d over twice the median" % (
                      1e3 * gaps[len(gaps) // 2], 1e3 * gaps[-1],
                      sum(g > 2 * gaps[len(gaps) // 2] for g in gaps)),
                  file=self.log)
        return {
            "attempted": steps,
            "failed": 0 if np.isfinite(last) and compiles == 0 else steps,
            "end_to_end": {"train_tokens_per_s": tokens / window},
            "facts": {"window_s": window, "steps": steps, "tokens": tokens,
                      "n_layers": self.sizes["n_layers"],
                      "model_flops": flops,
                      "kernel_work": {"flash_train": {
                          "flops": fl * steps, "bytes": by * steps}},
                      "span_s": {"step_call": call_s},
                      "span_n": {"step_call": steps},
                      "counters": {"compiles_in_window": compiles}}}

    # -- after the window -------------------------------------------------
    def release(self):
        import jax

        self._guard.__exit__(None, None, None)
        for attr in ("prepared", "scope", "feed", "_guard"):
            setattr(self, attr, None)
        gc.collect()
        jax.clear_caches()
        stats = self.device.memory_stats() or {}
        print("released: %.2f GB still in use" % (
            stats.get("bytes_in_use", 0) / 1e9), file=self.log)

    def check(self, record, control=None):
        """The plain reference over the same first steps.  ``control``
        (``controls.py``, never a benchmark run) names a lower precision:
        the reference computed in it then stands in the program's place
        and is held to the same limits."""
        import jax.numpy as jnp

        args = (
            self.seed, self.sizes, True, jnp.asarray(self.tokens, jnp.int32),
            jnp.asarray(self.labels[..., 0], jnp.int32), self.model["act"],
            self.model["learning_rate"], self.check_steps)
        ref = reference.train_readings(*args)
        got = self.got
        if control:
            got = reference.train_readings(*args, precision=control)
        return check.verdicts(compare(got, ref), self.limits)


def compare(got, ref):
    """The numbers of a training cell: every step's loss; the first
    gradient's and the three-step change's worst leaf by its norm (what
    a planted fault moves); and the first gradient element by element
    on a sample of every leaf, by the median leaf and by the worst
    (what a lower precision moves: a gap of norms is second order in
    rounding, this is first order)."""
    grad, g_leaf = check.worst_norm_gap(got["grad_norms"], ref["grad_norms"])
    still = check.still_leaves(ref["grad_norms"])
    change, c_leaf = check.worst_norm_gap(
        got["change_norms"], ref["change_norms"], leave_out=still)
    elem, elem_worst, _ = check.sample_gap(got["grad_samples"],
                                           ref["grad_samples"])
    return {"loss_gap": check.loss_gap(got["losses"], ref["losses"]),
            "grad_norm_gap": grad, "change_norm_gap": change,
            "grad_elem_gap": elem, "grad_elem_worst": elem_worst}
