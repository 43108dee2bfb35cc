"""100 * delta(num) / delta(den) of two counters across the window."""


def read(trace, facts, peaks, num, den):
    c = facts.get("counters", {})
    if not c.get(den):
        return None
    return 100.0 * c.get(num, 0) / c[den]
