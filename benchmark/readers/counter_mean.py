"""scale * delta(num) / delta(den) of two counters across the window:
a mean per event (``scale`` 0.001 turns microseconds into
milliseconds).  Finds nothing to read (returns None) where the
denominator did not move, or the program has no such counter."""


def read(trace, facts, peaks, num, den, scale=1.0):
    c = facts.get("counters", {})
    if not c.get(den):
        return None
    return float(scale) * c.get(num, 0) / c[den]
