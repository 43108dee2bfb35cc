"""From a profiler trace (.xplane.pb) to the numbers the readers use:
the device's busy union and idle share, the summed device time of the
events a regex names, the operations that took most time, and the
longest idle gaps labelled by what the benchmark's own host span was
doing.  Reads the trace with jax's own ``ProfileData`` (nothing of the
program).  Times are seconds; trace clocks are nanoseconds.
"""
import bisect
import collections
import glob
import math
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"      # one event for each run of an executable
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def percentile(values, p):
    """Nearest-rank percentile ``p`` of all ``values``; None of none."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals), max(1, math.ceil(p / 100.0 * len(vals)))) - 1]


def find_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def short_name(name):
    """A device event is named by its whole HLO instruction; keep the
    instruction's name, its kind and the start of its output shape:
    ``%fusion.12 fusion f32[2048,8192]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:96]
    kind = re.search(r"\s([a-z][a-z\-]*)\(", rest)
    shape = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return " ".join(x for x in (head, kind and kind.group(1),
                                shape and shape.group(0)) if x)[:96]


class Trace:
    """``devices``: {plane name: [(name, start_ns, end_ns)] sorted by
    start} from each device plane's op line, ``modules`` the same from
    its line of executables; ``spans``: the benchmark's own host spans
    [(name, start_ns, end_ns)]; ``window``: (t0, t1) of the
    ``bench.window`` span, else the extent of the device events."""

    def __init__(self, profile):
        self.devices = {}
        self.modules = {}
        self.spans = []
        for plane in profile.planes:
            if re.search(DEVICE_PLANE, plane.name):
                for line_name, into in ((OPS_LINE, self.devices),
                                        (MODULES_LINE, self.modules)):
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for line in plane.lines if line.name == line_name
                           for e in line.events]
                    evs.sort(key=lambda x: x[1])
                    into[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.append(
                                (e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
        self.spans.sort(key=lambda x: x[1])
        marks = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if marks:
            self.window = (marks[0][1], marks[0][2])
        else:
            starts = [e[0][1] for e in self.devices.values() if e]
            ends = [max(x[2] for x in e) for e in self.devices.values() if e]
            self.window = (min(starts), max(ends)) if starts else (0, 0)

    # -- per device ---------------------------------------------------
    def _clipped(self, evs):
        t0, t1 = self.window
        for name, a, b in evs:
            a, b = max(a, t0), min(b, t1)
            if b > a:
                yield name, a, b

    def _busy_intervals(self, evs):
        """Merged [a, b] intervals in which some op ran."""
        out = []
        for _, a, b in self._clipped(evs):
            if out and a <= out[-1][1]:
                if b > out[-1][1]:
                    out[-1][1] = b
            else:
                out.append([a, b])
        return out

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self):
        """Seconds in which an op ran, averaged over the device planes."""
        if not self.devices:
            return 0.0
        tot = [sum(b - a for a, b in self._busy_intervals(evs))
               for evs in self.devices.values()]
        return sum(tot) / len(tot) / 1e9

    def idle_share(self):
        w = self.window_s
        return None if w <= 0 or not self.devices else 1.0 - self.busy_s() / w

    def summed(self, regex):
        """(seconds, events) of the ops whose name matches, averaged
        over the device planes."""
        pat = re.compile(regex)
        secs = n = 0
        for evs in self.devices.values():
            for name, a, b in self._clipped(evs):
                if pat.search(name):
                    secs += b - a
                    n += 1
        k = max(1, len(self.devices))
        return secs / k / 1e9, n // k

    def calls_per_run(self, modules, events):
        """How many ops whose name matches ``events`` each whole run of
        an executable whose name matches ``modules`` holds: the set of
        the counts over every run that lies wholly inside the window
        (one number where every run holds as many)."""
        mod, pat = re.compile(modules), re.compile(events)
        t0, t1 = self.window
        counts = set()
        for plane, runs in self.modules.items():
            starts = sorted(a for name, a, _ in self.devices.get(plane, ())
                            if pat.search(name))
            for name, a, b in runs:
                if mod.search(name) and a >= t0 and b <= t1:
                    counts.add(bisect.bisect_left(starts, b)
                               - bisect.bisect_left(starts, a))
        return counts

    def top_ops(self, n=10):
        tot = collections.Counter()
        for evs in self.devices.values():
            for name, a, b in self._clipped(evs):
                tot[name] += b - a
        k = max(1, len(self.devices))
        return [[short_name(name), ns / k / 1e9]
                for name, ns in tot.most_common(n)]

    def longest_gaps(self, n=5):
        """The longest idle gaps of the first device, each labelled by
        the benchmark span (other than the window's) that covers most
        of it, or ``unspanned``."""
        if not self.devices:
            return []
        evs = self.devices[sorted(self.devices)[0]]
        busy = self._busy_intervals(evs)
        t0, t1 = self.window
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = [s for s in self.spans if s[0] != WINDOW_SPAN]
        out = []
        for a, b in gaps[:n]:
            cover = collections.Counter()
            for name, sa, sb in spans:
                ov = min(b, sb) - max(a, sa)
                if ov > 0:
                    cover[name[len(SPAN_PREFIX):]] += ov
            label = cover.most_common(1)[0][0] if cover else "unspanned"
            out.append([label, (b - a) / 1e9])
        return out
