"""A kernel's share of its roofline: the least time the chip could take
for the work the driver counted under ``work`` (flops over the bf16
peak against bytes over the HBM peak, the larger), over the summed
device time of the trace events whose name matches ``events``.  Finds
nothing to read (returns None) where no event matches or no work was
counted.

Until the kernels carry names of their own the events are found by the
HLO instruction, which would match any further Mosaic call as well.  So
every whole run of an executable matching ``modules`` has to hold
exactly ``calls_per_layer`` x the model's layers of them: with any
other count the time is not this kernel's alone, the reader says so and
returns None, and the harness leaves the metric out."""
import sys

from benchmark.lib.counts import least_time


def read(trace, facts, peaks, work, events, modules, calls_per_layer):
    done = facts.get("kernel_work", {}).get(work)
    seconds, n = trace.summed(events)
    if not done or n == 0 or seconds <= 0:
        return None
    want = int(calls_per_layer) * int(facts["n_layers"])
    found = trace.calls_per_run(modules, events)
    if found != {want}:
        print("kernel_roofline %s: a run of %r holds %s calls matching %r, "
              "not %d: not read" % (work, modules, sorted(found), events,
                                    want), file=sys.stderr)
        return None
    floor, _ = least_time(done["flops"], done["bytes"], peaks)
    return 100.0 * floor / seconds
