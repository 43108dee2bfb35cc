#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the chips this
machine holds:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set up and warm up (``setup_s``), measure for ``--seconds``, read the
peak memory, free the program, compare what the timed path produced
with the plain reference, and print the result as the last line of
standard output.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result; a device kind missing from
``lib/peaks.py`` is an error.  With ``--trace 1`` the window (capped by
the mix's ``trace_seconds``) runs under the profiler and the line
carries the cell's per-layer metrics, ``busy_s``/``window_s`` and a
``breakdown``; with ``--trace 0`` its end-to-end metrics.
"""
import time

_T_START = time.perf_counter()      # setup_s counts from here

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


def find_devices(chips):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip("the cell needs %d TPU chip(s); jax found %r"
                     % (chips, devices))
    return devices


class Tracer:
    """The profiler around the measured window (``--trace 1``), host
    spans through ``span``; without tracing both cost nothing."""

    def __init__(self, on, logdir):
        self.on = bool(on)
        self.logdir = logdir

    def start(self):
        if not self.on:
            return
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        # starting the profiler stalls the process's other threads for a
        # moment: let what they then catch up on pass before the window
        time.sleep(0.5)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def span(self, name):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


class GcPauses:
    """How often and for how long Python's collector ran (it stops every
    thread of the process while it does)."""

    def __init__(self):
        self.n, self.longest, self._t = 0, 0.0, None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)
        return False


def run_cell(spec, workload, seed, seconds, trace, devices, t_start=None,
             trace_dir=TRACE_DIR, log=sys.stderr, peaks=None, controls=()):
    """Drive one run of ``workload`` and return the result object.
    ``devices`` are the jax devices to run on (the caller has decided
    that they will do: ``main`` insists on TPUs; a test on the CPU also
    hands in a ``peaks`` row, since no table has one for it).
    ``controls`` (``controls.py`` only) names lower precisions: each is
    then put in the program's place after the run's own comparison,
    held to the same limits, and reported under ``controls``."""
    from benchmark.lib import reduce as _reduce
    from benchmark.lib.compile_meter import CompileMeter
    from benchmark.lib.peaks import device_peaks

    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.cell(workload)
    config = spec.config(cell)
    mix = spec.traffic(cell)
    peaks = peaks or device_peaks(devices[0].device_kind)
    driver = spec.driver(mix["driver"]).Driver(
        config=config, mix=mix, seed=int(seed), devices=devices[:cell["chips"]],
        peaks=peaks, meter=CompileMeter(), limits=spec.limits(cell), log=log)
    if trace:
        seconds = min(float(seconds), float(mix.get("trace_seconds", seconds)))
    tracer = Tracer(trace, trace_dir)

    driver.setup()
    # what set-up built (programs, traces, executables: some millions of
    # objects) is collected once here and then left out of the collector's
    # passes, so that none of them stops every thread for a large part of
    # a second inside the window
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    t_window = time.perf_counter()
    with pauses:
        record = driver.measure(float(seconds), tracer)
    print("collector in the window: %d passes, longest %.1f ms" % (
        pauses.n, 1e3 * pauses.longest), file=log)
    gc.unfreeze()
    record["end_to_end"]["setup_s"] = t_window - t_start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    driver.release()
    t_check = time.perf_counter()
    compared = driver.check(record)
    print("reference and comparison: %.1f s" % (time.perf_counter() - t_check),
          file=log)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": int(peak)}
    result = {"correct": all(c["ok"] for c in compared),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"])}
    if trace:
        tr = _reduce.Trace(_reduce.load(_reduce.find_xplane(trace_dir)))
        metrics = {}
        for m in spec.per_layer(cell):
            mf = spec.metric_file(m["name"])
            value = spec.reader(mf["reader"]).read(
                trace=tr, facts=record["facts"], peaks=peaks,
                **mf.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.longest_gaps(5)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {}
        for m in spec.end_to_end(cell):
            if m["name"] not in record["end_to_end"]:
                raise RuntimeError("driver %s reported no %s"
                                   % (mix["driver"], m["name"]))
            metrics[m["name"]] = {
                "value": float(record["end_to_end"][m["name"]]),
                "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = breakdown
    if controls:
        result["controls"] = {}
    for control in controls:
        held = driver.check(record, control=control)
        result["controls"][control] = {
            "correct": all(c["ok"] for c in held),
            "compared": {c["name"]: c["value"] for c in held}}
    result["compared"] = [
        {"name": c["name"], "value": c["value"], "limit": c["limit"]}
        for c in compared]
    for c in compared:
        print("compared %-28s %.6g  limit %.6g  %s" % (
            c["name"], c["value"], c["limit"], "ok" if c["ok"] else "FAIL"),
            file=log)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    # the persistent compile cache at a fixed path inside the checkout,
    # unless the machine names one; the program's own setter then sets
    # nothing (core/flags.ensure_compile_cache)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.lib.spec import Spec

    spec = Spec(ROOT, BENCH_DIR)
    cell = spec.cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = find_devices(cell["chips"])
    except NoChip as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 3
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      args.trace, devices, t_start=_T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
