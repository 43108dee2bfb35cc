"""The arithmetic that decides ``correct``: gaps between what the timed
path produced and what the plain reference gives, each held to a limit
of its own (``limits/<cell>.json``).  Nothing here knows a program."""
import statistics

import numpy as np


def flat_leaves(norms):
    """{leaf: scalar or per-layer array} -> {'leaf' or 'leaf[i]': float}"""
    out = {}
    for k, v in norms.items():
        a = np.asarray(v, np.float64)
        if a.ndim == 0:
            out[k] = float(a)
        else:
            for i, x in enumerate(a.reshape(-1)):
                out["%s[%d]" % (k, i)] = float(x)
    return out


def worst_norm_gap(got, ref, leave_out=()):
    """The worst leaf's gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Returns (gap, leaf)."""
    got, ref = flat_leaves(got), flat_leaves(ref)
    if set(got) != set(ref):
        raise ValueError("leaves differ: %s" % sorted(set(got) ^ set(ref)))
    keys = [k for k in ref if k not in leave_out]
    med = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, None
    for k in keys:
        gap = abs(got[k] - ref[k]) / max(ref[k], med)
        if not gap <= worst:            # a NaN gap is the worst there is
            worst, where = gap, k
            if gap != gap:
                return float("inf"), k
    return worst, where


def sample_gap(got, ref):
    """Element by element, on the sampled positions of every leaf: the
    root mean square of (got - ref) over the reference's own on that
    sample (or the median leaf's, whichever is larger).  First order in
    the rounding of the arithmetic, where a gap of norms is second
    order.  Returns (the median leaf's, the worst leaf's, which)."""
    diff, size = {}, {}
    for k in ref:
        g = np.asarray(got[k], np.float64)
        r = np.asarray(ref[k], np.float64)
        if g.shape != r.shape:
            raise ValueError("samples of %s differ in shape" % k)
        rows = [(k, g, r)] if r.ndim == 1 else [
            ("%s[%d]" % (k, i), g[i], r[i]) for i in range(len(r))]
        for name, gi, ri in rows:
            diff[name] = float(np.sqrt(np.mean(np.square(gi - ri))))
            size[name] = float(np.sqrt(np.mean(np.square(ri))))
    med = statistics.median(size.values())
    gaps = {k: diff[k] / max(size[k], med) for k in diff}
    gaps = {k: v if v == v else float("inf") for k, v in gaps.items()}
    worst = max(gaps, key=gaps.get)
    return statistics.median(gaps.values()), gaps[worst], worst


def still_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding (under
    ``share`` of the median leaf's): Adam moves them by round-off alone,
    so they are left out of the change comparison."""
    ref = flat_leaves(ref_grad_norms)
    med = statistics.median(ref.values())
    return {k for k, v in ref.items() if v < share * med}


def loss_gap(got, ref):
    """Largest relative gap over the steps."""
    return max(abs(a - b) / abs(b) if b == b and a == a else float("inf")
               for a, b in zip(got, ref))


def verdicts(values, limits):
    """[{name, value, limit, ok}] for every compared number; a number
    without a limit, or a limit without its number, is an error."""
    if set(values) != set(limits):
        raise ValueError("compared %s but limits name %s"
                         % (sorted(values), sorted(limits)))
    return [{"name": k, "value": float(values[k]), "limit": float(limits[k]),
             "ok": bool(values[k] <= limits[k])} for k in sorted(values)]
