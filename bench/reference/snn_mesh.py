"""Plain reference for an SNN training deployment on a 2-D mesh NoC.

Independent of the code under test: it builds the logical graph a
deployment request asks for (spiking ResNet layer list -> per-layer training
cost profile -> balanced partition onto the fabric's cores -> edges between
consecutive layers' slices) and scores a placement as bytes x XY-routed hops
in float64. It follows the semantics of the paper (arXiv:2411.19430 §4.2,
§5.1), written straight from the definitions, with no caching or batching.

A configuration names this module in its ``reference`` key; the harness
calls :func:`n_cores`, :func:`graph`, :func:`comm_cost` and :func:`zigzag`.
The fabric is the configuration's spec string, ``mesh:RxC[,key=value...]``;
only its grid matters here.
"""
from __future__ import annotations

import math

import numpy as np

# ---- model: the layer units a spiking ResNet is partitioned by ------------
# (name, cin, cout, k, stride, hw_in) per conv unit, in profile order: the
# body convs of a residual block, then its 1x1 downsample; the classifier
# last. Residual adds, BN and pooling carry no unit of their own.

_STAGES = {"spike_resnet18": ([2, 2, 2, 2], False),
           "spike_resnet50": ([3, 4, 6, 3], True)}
_WIDTHS = (64, 128, 256, 512)


def _units(family: str, n_classes: int, in_res: int, in_ch: int,
           width_mult: float):
    """[(kind, name, cin, cout, k, stride, h_in, w_in)] for the network."""
    plan, bottleneck = _STAGES[family]

    def w(c):
        return max(int(c * width_mult), 8)

    out = []
    h = in_res
    out.append(("conv", "stem", in_ch, w(64), 7, 2, h))
    h = math.ceil(h / 2)                      # stem stride
    h = math.ceil(h / 2)                      # 3x3/2 max pool
    cin = w(64)
    for si, (n_blocks, width) in enumerate(zip(plan, _WIDTHS)):
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            cout = w(width) * (4 if bottleneck else 1)
            if bottleneck:
                body = [(cin, w(width), 1, stride), (w(width), w(width), 3, 1),
                        (w(width), cout, 1, 1)]
            else:
                body = [(cin, cout, 3, stride), (cout, cout, 3, 1)]
            hh = h
            for ci, (a, b, k, s) in enumerate(body):
                out.append(("conv", f"s{si}b{bi}c{ci + 1}", a, b, k, s, hh))
                hh = math.ceil(hh / s)
            if stride != 1 or cin != cout:
                out.append(("conv", f"s{si}b{bi}down", cin, cout, 1, stride,
                            h))
            h, cin = hh, cout
    out.append(("fc", "fc", cin, n_classes, 1, 1, 1))
    return out


def profile(model: dict, batch: int, spike_density: float, training: bool):
    """Per-unit (flops, weight_bytes, out_bytes, c_out) for one training
    step of ``batch`` samples over ``T`` time steps: spiking forward convs
    are accumulates on the firing fraction, backward and weight-gradient
    passes are dense; forward traffic is one spike bit per neuron per step,
    plus FP16 gradients flowing back when training."""
    T = model["T"]
    rows = []
    for kind, _, cin, cout, k, s, h in _units(
            model["family"], model["n_classes"], model["in_res"],
            model["in_ch"], model["width_mult"]):
        if kind == "fc":
            flops = 2.0 * cin * cout * T * batch * (3 if training else 1)
            rows.append((flops, cin * cout * 2.0, cout * 2.0 * T * batch,
                         cout))
            continue
        ho = math.ceil(h / s)
        macs = ho * ho * cin * cout * k * k
        flops = 2.0 * macs * spike_density + (4.0 * macs if training else 0.0)
        out_bytes = ho * ho * cout / 8.0 + (ho * ho * cout * 2.0
                                            if training else 0.0)
        rows.append((flops * T * batch, k * k * cin * cout * 2.0,
                     out_bytes * T * batch, cout))
    return rows


# ---- balanced partition ------------------------------------------------------

def _latency(flops, wbytes, core):
    """Modeled time of one slice: compute, plus weights that spill past the
    core's SRAM streamed in."""
    return (flops / core["flops_per_s"]
            + max(wbytes - core["sram_bytes"], 0.0) / core["stream_bw"])


def _slice_fracs(c_out: int, k: int):
    """Even K-split of ``c_out`` channels into ``k`` slices."""
    base, extra = divmod(c_out, k)
    return [(base + (1 if s < extra else 0)) / max(c_out, 1)
            for s in range(k)]


def _layer_worst(row, k, core):
    flops, wbytes, _, c_out = row
    return max(_latency(flops * f, wbytes * f, core)
               for f in _slice_fracs(c_out, k))


def balanced_alloc(rows, n_cores: int, core) -> list:
    """Cores per layer: largest-remainder split of the cores by modeled
    latency (at least one each), then greedy moves of one core from the layer
    that loses least to the layer holding the slowest slice, while that
    lowers the slowest slice (at most 256 moves)."""
    n = len(rows)
    if n > n_cores:
        raise ValueError(f"{n} layers on {n_cores} cores: grouping layers is "
                         "outside this reference")
    wts = [max(_latency(r[0], r[1], core), 1e-30) for r in rows]
    ideal = [x / sum(wts) * n_cores for x in wts]
    alloc = [max(int(math.floor(x)), 1) for x in ideal]
    while sum(alloc) > n_cores:
        over = [a - x if a > 1 else -math.inf for a, x in zip(alloc, ideal)]
        i = over.index(max(over))
        if alloc[i] <= 1:
            break
        alloc[i] -= 1
    rem = np.asarray([x - a for x, a in zip(ideal, alloc)])
    order = np.argsort(-rem)
    k = 0
    while sum(alloc) < n_cores:
        alloc[int(order[k % n])] += 1
        k += 1
    for _ in range(256):
        lat = [_layer_worst(r, a, core) for r, a in zip(rows, alloc)]
        worst = lat.index(max(lat))
        best_gain, donor = 0.0, -1
        for li in range(n):
            if li == worst or alloc[li] <= 1:
                continue
            trial = list(alloc)
            trial[li] -= 1
            trial[worst] += 1
            new_max = max(_layer_worst(r, a, core)
                          for r, a in zip(rows, trial))
            gain = max(lat) - new_max
            if gain > best_gain + 1e-15:
                best_gain, donor = gain, li
        if donor < 0:
            break
        alloc[donor] -= 1
        alloc[worst] += 1
    return alloc


def mesh(config: dict) -> tuple:
    """``(rows, cols)`` of the configuration's ``mesh:RxC,...`` fabric."""
    head = config["fabric"].split(",")[0]
    kind, grid = head.split(":")
    if kind != "mesh":
        raise ValueError(f"{config['fabric']!r} is not a mesh")
    rows, cols = grid.lower().split("x")
    return int(rows), int(cols)


def n_cores(config: dict) -> int:
    rows, cols = mesh(config)
    return rows * cols


def graph(config: dict, fields: dict):
    """Logical graph of a request: ``(n, src, dst, vol)``. ``fields`` are the
    request's own values of ``batch``, ``spike_density`` and ``training``;
    every slice of one layer sends its output shard to every slice of the
    next layer in profile order."""
    rows = profile(config["model"], fields["batch"], fields["spike_density"],
                   fields["training"])
    alloc = balanced_alloc(rows, n_cores(config), config["core"])
    layer_nodes, out_bytes = [], []
    n = 0
    for row, k in zip(rows, alloc):
        layer_nodes.append(list(range(n, n + k)))
        out_bytes.extend(row[2] * f for f in _slice_fracs(row[3], k))
        n += k
    src, dst, vol = [], [], []
    for a, b in zip(layer_nodes[:-1], layer_nodes[1:]):
        for i in a:
            for j in b:
                src.append(i)
                dst.append(j)
                vol.append(out_bytes[i])
    return (n, np.asarray(src, np.int64), np.asarray(dst, np.int64),
            np.asarray(vol, np.float64))


def hops(config: dict, a, b):
    """XY-routed hop count between cores ``a`` and ``b`` (row-major ids) on
    the configuration's mesh: the Manhattan distance."""
    cols = mesh(config)[1]
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a // cols - b // cols) + np.abs(a % cols - b % cols)


def comm_cost(config: dict, g, placement, dtype=np.float64) -> float:
    """Σ over edges of bytes x hops, accumulated in ``dtype`` (float64 is
    the reference; a lower precision is the control)."""
    _, src, dst, vol = g
    p = np.asarray(placement, np.int64)
    terms = vol.astype(dtype) * hops(config, p[src], p[dst]).astype(dtype)
    total = dtype(0)
    for t in terms:
        total = dtype(total + t)
    return float(total)


def zigzag(g):
    """Row-major deployment from the first core: node i on core i."""
    return np.arange(g[0])
