"""delta of one counter across the window (a count: 0 is a reading)."""


def read(trace, facts, peaks, counter):
    c = facts.get("counters", {})
    return None if counter not in c else float(c[counter])
