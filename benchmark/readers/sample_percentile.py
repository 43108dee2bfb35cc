"""Nearest-rank percentile ``p`` of the samples the driver kept under
``samples`` (every one of the window, none left out)."""
from benchmark.lib.reduce import percentile


def read(trace, facts, peaks, samples, p):
    value = percentile(facts.get(samples) or (), p)
    return None if value is None else float(value)
