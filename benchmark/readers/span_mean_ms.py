"""Mean length of the benchmark's own span ``span`` over the window,
from the driver's host clock (summed over many calls)."""


def read(trace, facts, peaks, span):
    n = facts.get("span_n", {}).get(span)
    if not n:
        return None
    return 1e3 * facts["span_s"][span] / n
