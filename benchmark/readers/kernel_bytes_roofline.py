"""A kernel's share of its roofline inside one executable: the least
time the chip could take for the work the driver counted under ``work``
(flops over the bf16 peak against bytes over the HBM peak, the larger)
over the summed device time of the events whose name matches ``events``
and that lie inside a run of an executable matching ``modules``.

The kernel is found by its own name (the ``name=`` it passes to
``pallas_call``: its HLO instruction is ``%<name>.N``), and only inside
the decode step, because the work is counted from what the decode steps
themselves reported (experts hit, rows of state moved): the same work
whatever implements it.  Finds nothing to read (returns None) where no
event matches or no work was counted, as at a parent without the
kernel."""
import bisect
import re

from benchmark.lib.counts import least_time


def read(trace, facts, peaks, work, events, modules):
    done = facts.get("kernel_work", {}).get(work)
    if not done or not (done.get("bytes") or done.get("flops")):
        return None
    pat, mod = re.compile(events), re.compile(modules)
    t0, t1 = trace.window
    total = n = 0
    for plane, evs in trace.devices.items():
        runs = sorted((a, b) for name, a, b in trace.modules.get(plane, ())
                      if mod.search(name))
        starts = [a for a, _ in runs]
        for name, a, b in evs:
            if not pat.search(name) or a < t0 or b > t1:
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= runs[i][1]:
                total += b - a
                n += 1
    if not n or total <= 0:
        return None
    seconds = total / max(1, len(trace.devices)) / 1e9
    floor, _ = least_time(done.get("flops", 0), done.get("bytes", 0), peaks)
    return 100.0 * floor / seconds
