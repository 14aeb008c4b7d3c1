"""Plain reference for a Spikformer training deployment on a 2-D mesh NoC.

Independent of the code under test: it builds the logical graph a
deployment request asks for (Spikformer unit list -> per-unit training cost
profile -> balanced partition onto the fabric's cores -> edges from each unit's
producers) and scores a placement as bytes x XY-routed hops in float64. The
model is Spikformer (Zhou et al., ICLR 2023, arXiv:2209.15425); the cost and
traffic rules are the paper arXiv:2411.19430's (§4.2, §5.1), written here
straight from the definitions, with no caching or batching:

* units: the four SPS convs, ``rpe``; per block Q, K, V, the attention,
  proj, fc1, fc2; the classifier. Q, K, V, proj, fc1 and fc2 are 1x1 units
  over the N = H·W tokens; the attention holds no weights, does
  2·N²·D multiply-accumulates (Q·Kᵀ, then ·V) and is split by heads;
* a unit's forward ops are accumulates on the firing fraction, backward and
  weight-gradient passes dense; its output travels as one spike bit per
  element, or one byte where it is a residual-stream sum (``rpe``, proj,
  fc2), plus FP16 gradients when training;
* edges: ``full`` (every producer slice sends its shard to every consumer
  slice) or ``aligned`` (producer slice i sends to consumer slice j the
  producer's bytes x the overlap of their channel ranges, as fractions of
  each unit's channels).

A configuration names this module in its ``reference`` key; the harness
calls :func:`n_cores`, :func:`graph`, :func:`comm_cost` and :func:`zigzag`.
The balanced allocation, the mesh and the cost come from ``snn_mesh``.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference.snn_mesh import (balanced_alloc, comm_cost,  # noqa: F401
                                      n_cores, zigzag)


def units(model: dict):
    """[(name, kind, cin, cout, k, tokens, residual, producers)] in profile
    order. ``tokens`` is H·W at the unit; ``producers`` lists
    (unit name, "full" | "aligned"); none means the previous unit, full."""
    d, heads = model["dim"], model["heads"]
    n_pools = int(math.log2(model["patch"]))
    chans = [model["in_ch"], d // 8, d // 4, d // 2, d]
    h = model["in_res"]
    out = []
    for i in range(4):
        out.append((f"sps{i}", "conv", chans[i], chans[i + 1], 3, h * h,
                    False, ()))
        if i >= 4 - n_pools:                  # 2x2 max pool after this conv
            h = math.ceil(h / 2)
    n = h * h
    out.append(("rpe", "conv", d, d, 3, n, True, ()))
    x = "rpe"                                 # the residual stream's unit
    hidden = d * model["mlp_ratio"]
    for b in range(model["depth"]):
        p = f"b{b}"
        for name in ("q", "k", "v"):
            out.append((p + name, "conv", d, d, 1, n, False, ((x, "full"),)))
        out.append((p + "attn", "attn", d, heads, 0, n, False,
                    tuple((p + name, "aligned") for name in ("q", "k", "v"))))
        out.append((p + "proj", "conv", d, d, 1, n, True,
                    ((p + "attn", "full"), (x, "aligned"))))
        out.append((p + "fc1", "conv", d, hidden, 1, n, False,
                    ((p + "proj", "full"),)))
        out.append((p + "fc2", "conv", hidden, d, 1, n, True,
                    ((p + "fc1", "full"), (p + "proj", "aligned"))))
        x = p + "fc2"
    out.append(("head", "fc", d, model["n_classes"], 0, 1, False, ()))
    return out


def profile(model: dict, batch: int, spike_density: float, training: bool):
    """Per-unit (flops, weight_bytes, out_bytes, c_out) for one training
    step of ``batch`` samples over ``T`` time steps."""
    T = model["T"]
    rows = []
    for _, kind, cin, cout, k, n, residual, _ in units(model):
        if kind == "fc":
            flops = 2.0 * cin * cout * T * batch * (3 if training else 1)
            rows.append((flops, cin * cout * 2.0, cout * 2.0 * T * batch,
                         cout))
            continue
        if kind == "attn":                    # cin = D, cout = heads
            macs, weights, elems = 2 * n * n * cin, 0.0, n * cin
        else:
            macs, weights, elems = n * cin * cout * k * k, \
                k * k * cin * cout * 2.0, n * cout
        flops = 2.0 * macs * spike_density + (4.0 * macs if training else 0.0)
        out_bytes = elems * 1.0 if residual else elems / 8.0
        if training:
            out_bytes += elems * 2.0
        rows.append((flops * T * batch, weights, out_bytes * T * batch, cout))
    return rows


def _bounds(c_out: int, k: int):
    """Channel ranges [lo, hi) of an even K-split of ``c_out`` into ``k``."""
    base, extra = divmod(c_out, k)
    out, lo = [], 0
    for s in range(k):
        hi = lo + base + (1 if s < extra else 0)
        out.append((lo, hi))
        lo = hi
    return out


def graph(config: dict, fields: dict):
    """Logical graph of a request: ``(n, src, dst, vol)``, edges in
    row-major (src, dst) order. ``fields`` are the request's own values of
    ``batch``, ``spike_density`` and ``training``."""
    model = config["model"]
    us = units(model)
    rows = profile(model, fields["batch"], fields["spike_density"],
                   fields["training"])
    alloc = balanced_alloc(rows, n_cores(config), config["core"])
    index = {u[0]: i for i, u in enumerate(us)}
    nodes, start = [], 0
    for k in alloc:
        nodes.append(list(range(start, start + k)))
        start += k
    vol: dict = {}
    for b, u in enumerate(us):
        prods = u[7] or (((us[b - 1][0], "full"),) if b else ())
        for name, kind in prods:
            a = index[name]
            out_bytes, ca, cb = rows[a][2], rows[a][3], rows[b][3]
            pb, qb = _bounds(ca, alloc[a]), _bounds(cb, alloc[b])
            for i, (lo, hi) in zip(nodes[a], pb):
                for j, (lo2, hi2) in zip(nodes[b], qb):
                    if kind == "full":
                        v = out_bytes * ((hi - lo) / ca)
                    else:
                        num = min(hi * cb, hi2 * ca) - max(lo * cb, lo2 * ca)
                        v = out_bytes * num / (ca * cb) if num > 0 else 0.0
                    if v:
                        vol[(i, j)] = vol.get((i, j), 0.0) + v
    keys = sorted(vol)
    return (start, np.asarray([i for i, _ in keys], np.int64),
            np.asarray([j for _, j in keys], np.int64),
            np.asarray([vol[key] for key in keys], np.float64))
