"""The one request-stream generator: a traffic mix is a data file of its
parameters (``bench/traffic/<mix>.json``), read here.

A mix draws a fixed sequence of distinct deployment requests from
``--seed``. Keys of a mix file:

``clients``        closed-loop clients issuing the sequence concurrently.
``max_requests``   length of the drawn sequence; the window never needs more.
``quality_sample`` the first this many requests make up the run's fixed
                   plan-quality sample (answered after the window where the
                   window did not reach them).
``trace_seconds``  a ``--trace 1`` run traces this many seconds from the
                   window's start (the window itself runs its full length).
``vary``           how each request differs from the configuration's base
                   request, one entry per request field:
                   ``{"pool": [lo, hi]}`` integers lo..hi-1, or
                   ``{"grid": [lo, hi, k]}`` the k midpoints of [lo, hi]
                   split in k equal parts. Each request takes the next value
                   of a permutation of the pool drawn from the seed, so no
                   two requests of a run share a value, and every run draws
                   from the same set.

Set-up warms its programs on values outside every pool (see
``warmup_fields``), so a warm-up never answers a request of the window.
"""
from __future__ import annotations

import numpy as np


def pool(spec) -> np.ndarray:
    if "pool" in spec:
        lo, hi = spec["pool"]
        return np.arange(int(lo), int(hi))
    lo, hi, k = spec["grid"]
    step = (float(hi) - float(lo)) / int(k)
    return np.round(float(lo) + step * (np.arange(int(k)) + 0.5), 12)


def _outside(spec):
    """A value of the field's type that no request takes."""
    if "pool" in spec:
        return int(spec["pool"][1])             # one past the pool
    lo, hi, _ = spec["grid"]
    return float(lo) + (float(hi) - float(lo)) / 2.0   # a grid boundary
                                                       # (k even) or centre


def draw(mix: dict, seed: int) -> list:
    """The field overrides of each request of the stream, in order."""
    rng = np.random.default_rng(int(seed) % 2 ** 63)
    n = int(mix["max_requests"])
    perms = {field: rng.permutation(pool(spec))
             for field, spec in sorted(mix["vary"].items())}
    if n > min(len(p) for p in perms.values()):
        raise ValueError(f"mix asks for {n} requests; widen its pools")
    return [{f: p[k].item() for f, p in perms.items()} for k in range(n)]


def warmup_fields(mix: dict) -> dict:
    """Field overrides of the set-up warm-up request: values outside every
    pool."""
    return {f: _outside(spec) for f, spec in sorted(mix["vary"].items())}
