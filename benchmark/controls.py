#!/usr/bin/env python3
"""Take a cell's readings again: what the limits in ``limits/<cell>.json``
were set from, at the cell's own size, on the chip.

    python3 benchmark/controls.py --workload <cell> --seeds 1,2,3 \\
        --seconds <window> --controls bf16 --control-seeds 3

For each seed it drives one run of the cell through ``run.run_cell`` (the
same set-up, window, release and comparison as a benchmark run; a short
window will do, long enough to finish as many requests as a run
compares) and prints one JSON line: the numbers the program reads (the
lower readings) and, on the first ``--control-seeds`` seeds, the numbers
each control reads when it is put in the program's place, with the
verdict of the harness's own comparison, which has to be false (the
upper readings).  It is never part of a benchmark run.  The precisions a
driver's ``check`` takes are ``lib/reference.py``'s: bf16, fp8, int8.
"""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="", help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import run
    from benchmark.lib.spec import Spec

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", run.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = Spec(ROOT, BENCH_DIR)
    cell = spec.cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = run.find_devices(cell["chips"])
    except run.NoChip as e:
        print("controls: %s" % e, file=sys.stderr)
        return 3
    controls = [c for c in args.controls.split(",") if c]
    unsound = 0
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res = run.run_cell(
            spec, args.workload, seed, args.seconds, 0, devices,
            controls=controls if i < args.control_seeds else ())
        line = {"seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "compared": {c["name"]: c["value"] for c in res["compared"]},
                "limits": {c["name"]: c["limit"] for c in res["compared"]},
                "controls": res.get("controls", {}),
                "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(line), flush=True)
        unsound += (not res["correct"]) + sum(
            c["correct"] for c in line["controls"].values())
    # 0 only where every run was correct and every control was not
    return 1 if unsound else 0


if __name__ == "__main__":
    sys.exit(main())
