"""Percentiles, copied from the program's ``repro.obs.percentiles`` so the
yardstick stays fixed: linear interpolation between closest ranks."""


def quantile(samples, q: float) -> float:
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("quantile() needs at least one sample")
    pos = (q / 100) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
