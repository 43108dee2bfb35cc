"""1 - (union of the device's op intervals) / traced window."""


def read(trace, facts, peaks):
    share = trace.idle_share()
    return None if share is None else 100.0 * share
