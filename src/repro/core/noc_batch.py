"""Batched, table-driven NoC evaluation — the repo's hottest path, vectorized.

``Topology.evaluate`` re-derives routes edge-by-edge in Python on every call,
and every placement optimizer (`ppo`, `policy_baseline`, the `baselines` and
`population` searches) calls it once per candidate placement, thousands of
times per run. This module precomputes, once per topology (any
:class:`repro.core.topology.Topology` — flat ``NoC`` grids and
``HierarchicalMesh`` multi-chip systems alike):

* ``hops[n, n]``                  — all-pairs hop distances (== route lengths, since
  the deterministic routes are shortest paths);
* ``route_links[n, n, max_hops]`` — the deterministic route of every (src, dst)
  pair as padded directed-link ids, built by replaying the topology's
  reference router, so tie-breaks (clockwise on even tori) match bit-for-bit;
* ``link_dst[n_links]``           — destination core of every directed link;
* per-link attribute vectors (``inv_bw``, summed route latencies,
  ``energy_per_byte``, the inter-chip mask) when the topology is non-uniform.

For grids a directed link is identified as ``src_core * 4 + direction`` with
directions L/R/U/D = 0/1/2/3, the ordering of ``GridTopology.directional_cdv``.
Every metric of :class:`repro.core.topology.NoCMetrics` then becomes gather +
segment-sum over these tables, batched over a population axis:

* **numpy backend** — float64; reproduces the reference loop exactly on
  integer-volume graphs (sum of exactly-representable products), which is why it
  is the default *scoring* backend: optimizers keep their seed-for-seed results
  while scoring whole populations per call;
* **jax backend** — ``jax.jit`` + ``jax.vmap`` (float32 unless x64 is enabled),
  an explicit opt-in for accelerator hosts and large populations
  (``backend="auto"`` picks numpy: exact, and faster on CPU-only hosts);
* **pallas backend** — the jax path with per-link traffic computed by the
  tiled one-hot-matmul segment-sum kernel ``repro.kernels.noc_segsum``
  (interpret mode on CPU, Mosaic on TPU). Link/core traffic accumulates in
  float32 (the MXU's accumulation dtype) even when jax x64 is enabled —
  use the numpy or jax backend when float64 traffic totals matter.

Entry points: :func:`evaluate_batch`, :func:`comm_cost_batch`,
:func:`directional_cdv_batch`, and :func:`make_scorer` (the scoring closure
the optimizers use — comm-cost by default, any :mod:`repro.deploy.objective`
spec via ``objective=``). :meth:`BatchedNoC.make_fused_scorer` builds fused
jax/pallas scorers for non-comm objectives (``max_link``/``energy``/...)
that return [B] scores in one device dispatch without materializing the full
:class:`BatchMetrics`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .graph import LogicalGraph
from .topology import Topology

# JAX is only needed for backend="jax"; detect cheaply, import lazily so that
# `import repro.core` (and the default numpy scoring path) stays light.
import importlib.util

HAS_JAX = importlib.util.find_spec("jax") is not None
jax = None
jnp = None


def _import_jax():
    global jax, jnp
    if jax is None:  # pragma: no branch - trivial memoization
        import jax as _jax
        import jax.numpy as _jnp
        jax, jnp = _jax, _jnp
    return jax, jnp


def _mm(a, b):
    """Matmul over traffic volumes at full float32 precision: a TPU's default
    single bf16 MXU pass would round them (CPU computes f32 exactly anyway)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _jx_float():
    """float64 when x64 is enabled (reference-grade precision; summation
    order can still differ in the last ulp), else float32."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


# Soft cap on elements materialized per numpy scatter chunk (memory guard).
_CHUNK_ELEMS = 20_000_000


# ---------------------------------------------------------------------------
# Topology tables
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NoCTables:
    """Per-topology routing tensors.

    ``uniform`` marks an all-links-equal topology (flat NoC): the per-link
    attribute fields are None and evaluation takes the historical scalar
    paths bit-for-bit. Non-uniform topologies carry per-link inverse
    bandwidths, the [n, n] summed route latencies, and (optionally) per-link
    energies and the inter-chip mask.
    """
    rows: int
    cols: int
    torus: bool
    hops: np.ndarray          # [n, n] int32 shortest hop distance
    route_links: np.ndarray   # [n, n, max_hops] int32 link ids, padded with n_links
    link_dst: np.ndarray      # [n_links] int32 destination core of each link
    cdv_in_ids: np.ndarray | None   # [n_links] int32 (grids only)
    max_hops: int
    uniform: bool = True
    inv_bw: np.ndarray | None = None          # [n_links] 1/bytes-per-s
    route_lat: np.ndarray | None = None       # [n, n] summed route latency (s)
    energy_per_byte: np.ndarray | None = None  # [n_links] J/byte
    interchip: np.ndarray | None = None        # [n_links] bool

    @property
    def n_cores(self) -> int:
        return self.rows * self.cols

    @property
    def n_links(self) -> int:
        return int(self.link_dst.size)


def build_tables(topo: Topology) -> NoCTables:
    """Replay the topology's router over all (src, dst) pairs into dense
    tables, plus its per-link attribute vectors when non-uniform."""
    n = topo.n_cores
    hops = topo.hops_matrix()
    max_hops = int(hops.max()) if n else 0
    n_links = topo.n_links

    route_links = np.full((n, n, max_hops), n_links, dtype=np.int32)
    for s in range(n):
        for d in range(n):
            if s == d:
                continue
            ids = topo.route_ids(s, d)
            route_links[s, d, :len(ids)] = ids

    link_dst = np.asarray(topo.link_dst_array(), dtype=np.int32)
    cdv_in_ids = (np.asarray(topo.cdv_in_ids(), dtype=np.int32)
                  if hasattr(topo, "cdv_in_ids") else None)

    bw = topo.link_bandwidth()
    lat = topo.link_latency()
    uniform = bw is None and lat is None
    inv_bw = route_lat = None
    if not uniform:
        inv_bw = 1.0 / (np.full(n_links, topo.link_bw)
                        if bw is None else np.asarray(bw, np.float64))
        lat_arr = (np.full(n_links, topo.hop_latency)
                   if lat is None else np.asarray(lat, np.float64))
        lat_pad = np.append(lat_arr, 0.0)       # padding id n_links -> 0 s
        route_lat = (lat_pad[route_links].sum(axis=2) if max_hops
                     else np.zeros((n, n)))
    eb = topo.link_energy_per_byte()
    ic = topo.interchip_mask()
    rows, cols = topo.grid_shape
    return NoCTables(rows, cols, bool(getattr(topo, "torus", False)), hops,
                     route_links, link_dst, cdv_in_ids, max_hops,
                     uniform=uniform, inv_bw=inv_bw, route_lat=route_lat,
                     energy_per_byte=(None if eb is None
                                      else np.asarray(eb, np.float64)),
                     interchip=(None if ic is None
                                else np.asarray(ic, bool)))


def _check_placements(placements, n_nodes: int, n_cores: int | None):
    """Coerce to [B, n] int64; validate range + injectivity when ``n_cores``
    is given (the checks ``Topology.evaluate`` performs)."""
    P = np.asarray(placements, dtype=np.int64)
    if P.ndim == 1:
        P = P[None, :]
    if P.ndim != 2 or P.shape[1] != n_nodes:
        raise ValueError(f"placements must be [B, {n_nodes}], got {P.shape}")
    if n_cores is not None and P.size:
        if P.min() < 0 or P.max() >= n_cores:
            raise ValueError("placement out of range")
        s = np.sort(P, axis=1)
        if np.any(s[:, 1:] == s[:, :-1]):
            raise ValueError("placement must map nodes to distinct cores")
    return P


# ---------------------------------------------------------------------------
# Incident-edge tables (O(degree) delta-cost evaluation)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IncidentTables:
    """Per-node incident-edge tables of one :class:`LogicalGraph`, padded
    dense — the graph-side companion of :class:`NoCTables` (which is
    per-topology; incident edges depend on the graph, so they are built per
    graph next to the route tables rather than inside them).

    Row ``u`` lists every directed edge touching node ``u`` (as source or
    destination). Row ``n`` is the all-padding sentinel row a free-slot swap
    index resolves to, so gathering by a clamped node id is always safe.
    Padding entries use ``other == n`` with ``vol == 0`` — they contribute
    exactly zero to any delta. Self-edges are dropped (``hops[c, c] == 0``
    for every routing, so they can never change a comm cost).

    A pairwise swap of two placement slots only perturbs the edges incident
    to the (at most two) moved nodes, so incremental evaluation through these
    tables is O(degree) instead of O(E) — see :func:`delta_comm_cost` (exact
    numpy reference) and :mod:`repro.core.placement.device_search` (the
    jax/pallas kernels used inside the scanned SA step).
    """
    other: np.ndarray    # [n+1, D] int32 other endpoint (pad: n)
    vol: np.ndarray      # [n+1, D] float64 edge volume (pad: 0)
    is_src: np.ndarray   # [n+1, D] bool — node is the edge's source
    degree: np.ndarray   # [n+1] int64 valid entries per row

    @property
    def max_degree(self) -> int:
        return int(self.other.shape[1])


def build_incident_tables(graph: LogicalGraph) -> IncidentTables:
    """Build the padded per-node incident-edge tables of ``graph``."""
    src, dst, vol = graph.edge_arrays()
    keep = src != dst                  # self-edges never move a comm cost
    src, dst, vol = src[keep], dst[keep], vol[keep]
    n = graph.n
    nodes = np.concatenate([src, dst])
    others = np.concatenate([dst, src])
    vols = np.concatenate([vol, vol])
    is_src = np.concatenate([np.ones(src.size, bool), np.zeros(dst.size, bool)])
    degree = np.zeros(n + 1, dtype=np.int64)
    if nodes.size:
        degree[:n] = np.bincount(nodes, minlength=n)
    D = max(int(degree.max()), 1)
    other_t = np.full((n + 1, D), n, dtype=np.int32)
    vol_t = np.zeros((n + 1, D), dtype=np.float64)
    src_t = np.zeros((n + 1, D), dtype=bool)
    if nodes.size:
        order = np.argsort(nodes, kind="stable")
        sorted_nodes = nodes[order]
        first = np.searchsorted(sorted_nodes, np.arange(n + 1))
        pos = np.arange(sorted_nodes.size) - first[sorted_nodes]
        other_t[sorted_nodes, pos] = others[order]
        vol_t[sorted_nodes, pos] = vols[order]
        src_t[sorted_nodes, pos] = is_src[order]
    return IncidentTables(other=other_t, vol=vol_t, is_src=src_t,
                          degree=degree)


def delta_comm_cost(noc: Topology, graph: LogicalGraph, slots, i: int, j: int,
                    tables: IncidentTables | None = None) -> float:
    """Exact comm-cost change of swapping ``slots[i]`` and ``slots[j]``.

    ``slots`` is a placement extended with free cores (the SA slots array:
    entries ``[0, graph.n)`` are placed nodes, the rest free cores). On
    integer-volume graphs the result equals
    ``comm_cost(after) - comm_cost(before)`` *bit-exactly* (every term is an
    exactly-representable integer product), in O(degree) instead of O(E) —
    the numpy reference the jax/pallas delta kernels are validated against.
    Routing direction is respected (``is_src``), so asymmetric detour routes
    on degraded topologies are handled too.
    """
    if i == j:
        return 0.0
    if tables is None:
        tables = build_incident_tables(graph)
    hops = batched_noc(noc).tables.hops
    slots = np.asarray(slots, dtype=np.int64)
    n = graph.n
    a = i if i < n else n                  # n == free-slot sentinel row
    b = j if j < n else n
    ci, cj = int(slots[i]), int(slots[j])
    p_pad = np.append(slots[:n], 0)        # sentinel gathers core 0, vol 0
    delta = 0.0
    # (node, its core before, its core after, other-endpoint id to skip)
    for u, cu_before, cu_after, skip in ((a, ci, cj, -1), (b, cj, ci, a)):
        if u == n:
            continue
        others = tables.other[u].astype(np.int64)
        vols = tables.vol[u]
        if skip >= 0:                      # a<->b edges already counted via a
            vols = np.where(others == skip, 0.0, vols)
        is_src = tables.is_src[u]
        oc_before = p_pad[others]
        oc_after = np.where(others == a, cj,
                            np.where(others == b, ci, oc_before))
        src_b = np.where(is_src, cu_before, oc_before)
        dst_b = np.where(is_src, oc_before, cu_before)
        src_a = np.where(is_src, cu_after, oc_after)
        dst_a = np.where(is_src, oc_after, cu_after)
        delta += float((vols * (hops[src_a, dst_a].astype(np.float64)
                                - hops[src_b, dst_b])).sum())
    return delta


# ---------------------------------------------------------------------------
# Batched metrics container
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchMetrics:
    """Population-axis counterpart of :class:`NoCMetrics` (arrays over B)."""
    comm_cost: np.ndarray     # [B] Σ bytes × hops
    mean_hops: np.ndarray     # [B] traffic-weighted mean hop distance
    max_hops: np.ndarray      # [B] longest routed path (int)
    max_link: np.ndarray      # [B] hottest link bytes
    latency: np.ndarray       # [B] analytic makespan (s)
    throughput: np.ndarray    # [B] 1 / latency
    core_traffic: np.ndarray  # [B, rows, cols] bytes routed through each core
    link_traffic: np.ndarray  # [B, n_links] bytes per directed link (core*4+dir)


# ---------------------------------------------------------------------------
# The batched evaluator
# ---------------------------------------------------------------------------

class BatchedNoC:
    """Vectorized evaluator for one :class:`repro.core.topology.Topology`.

    Tables are built once at construction (one Python pass over all core pairs)
    and reused for every graph/population scored afterwards. Use the module
    cache :func:`batched_noc` rather than constructing directly.
    """

    def __init__(self, noc: Topology):
        self.noc = noc
        self.tables = build_tables(noc)
        self._jax_fns: dict = {}

    # ---- inputs ------------------------------------------------------------
    def edge_arrays(self, graph: LogicalGraph):
        """(src, dst, vol, compute) in the same order as ``graph.edges``."""
        src, dst, vol = graph.edge_arrays()
        return (src, dst, vol, np.asarray(graph.compute, np.float64))

    def _placements(self, placements, n_nodes: int, validate: bool):
        if validate:
            # full Topology.evaluate semantics, the dropped-core rejection
            # of degraded topologies included
            return validate_placements(self.noc, placements, n_nodes)
        return _check_placements(placements, n_nodes, None)

    def _resolve(self, backend: str) -> str:
        if backend == "auto":
            # The numpy path is float64-exact and faster on CPU-only hosts
            # (scatter-heavy jnp ops lose to np.bincount there); jax is an
            # explicit opt-in for accelerator hosts.
            return "numpy"
        if backend in ("numpy", "batch"):
            return "numpy"
        if backend in ("jax", "pallas"):
            if not HAS_JAX:
                raise RuntimeError(f"backend={backend!r} requested but jax is "
                                   "not importable; use 'numpy' or 'auto'")
            return backend
        if backend == "reference":
            raise ValueError("backend='reference' is the sequential "
                             "Topology.evaluate loop; call noc.evaluate "
                             "directly or use make_scorer(noc, graph, "
                             "'reference')")
        raise ValueError(f"unknown backend {backend!r}; "
                         "choose 'auto' | 'jax' | 'pallas' | 'numpy' | 'batch'")

    # ---- comm cost only (the optimizer scoring path) -----------------------
    def comm_cost(self, graph: LogicalGraph, placements,
                  backend: str = "auto", validate: bool = True) -> np.ndarray:
        src, dst, vol, _ = self.edge_arrays(graph)
        P = self._placements(placements, graph.n, validate)
        if src.size == 0 or P.shape[0] == 0:
            return np.zeros(P.shape[0])
        if self._resolve(backend) in ("jax", "pallas"):
            # comm_cost is gather-only (no segment-sum); pallas == jax here
            f = self._get_jax_fn("comm")
            return np.asarray(f(jnp.asarray(P), jnp.asarray(src),
                                jnp.asarray(dst),
                                jnp.asarray(vol, _jx_float())), np.float64)
        h = self.tables.hops[P[:, src], P[:, dst]]          # [B, E]
        return (h * vol[None, :]).sum(axis=1)

    # ---- full metrics ------------------------------------------------------
    def evaluate(self, graph: LogicalGraph, placements,
                 backend: str = "auto", validate: bool = True) -> BatchMetrics:
        t, noc = self.tables, self.noc
        src, dst, vol, compute = self.edge_arrays(graph)
        P = self._placements(placements, graph.n, validate)
        B = P.shape[0]
        if src.size == 0:
            comp = np.zeros((B, t.n_cores))
            if P.size:
                comp[np.arange(B)[:, None], P] = compute[None, :] / noc.core_flops
            latency = comp.max(axis=1) if graph.n else np.zeros(B)
            return BatchMetrics(
                comm_cost=np.zeros(B), mean_hops=np.zeros(B),
                max_hops=np.zeros(B, int), max_link=np.zeros(B),
                latency=latency,
                throughput=np.where(latency > 0, 1.0 / np.maximum(latency, 1e-300),
                                    np.inf),
                core_traffic=np.zeros((B, t.rows, t.cols)),
                link_traffic=np.zeros((B, t.n_links)))
        resolved = self._resolve(backend)
        path_lat = None
        if resolved in ("jax", "pallas"):
            f = self._get_jax_fn("full_pallas" if resolved == "pallas"
                                 else "full")
            out = f(jnp.asarray(P), jnp.asarray(src), jnp.asarray(dst),
                    jnp.asarray(vol, _jx_float()),
                    jnp.asarray(compute / noc.core_flops, _jx_float()))
            if t.uniform:
                cc, h_max, lt, core_tr, per_core_max = out
            else:
                cc, h_max, lt, core_tr, per_core_max, path_lat = out
                path_lat = np.asarray(path_lat, np.float64)
            cc = np.asarray(cc, np.float64)
            h_max = np.asarray(h_max, np.int64)
            lt = np.asarray(lt, np.float64)
            core_tr = np.asarray(core_tr, np.float64)
            per_core_max = np.asarray(per_core_max, np.float64)
        else:
            cc, h_max, lt, core_tr, per_core_max, path_lat = self._numpy_full(
                P, src, dst, vol, compute)
        total = vol.sum()
        latency = per_core_max + (h_max * noc.hop_latency if path_lat is None
                                  else path_lat)
        return BatchMetrics(
            comm_cost=cc,
            mean_hops=cc / total if total else np.zeros(B),
            max_hops=h_max,
            max_link=lt.max(axis=1),
            latency=latency,
            throughput=np.where(latency > 0, 1.0 / np.maximum(latency, 1e-300),
                                np.inf),
            core_traffic=core_tr.reshape(B, t.rows, t.cols),
            link_traffic=lt)

    def _numpy_full(self, P, src, dst, vol, compute):
        t, noc = self.tables, self.noc
        B, E = P.shape[0], src.size
        n, n_links, mh = t.n_cores, t.n_links, max(t.max_hops, 1)
        cc = np.empty(B)
        h_max = np.empty(B, dtype=np.int64)
        lt = np.empty((B, n_links))
        core_tr = np.empty((B, n))
        per_core_max = np.empty(B)
        path_lat = None if t.uniform else np.empty(B)
        chunk = max(1, _CHUNK_ELEMS // max(E * mh, 1))
        for b0 in range(0, B, chunk):
            Pb = P[b0:b0 + chunk]
            bsz = Pb.shape[0]
            s, d = Pb[:, src], Pb[:, dst]                    # [b, E]
            h = t.hops[s, d]
            cc[b0:b0 + bsz] = (h * vol[None, :]).sum(axis=1)
            h_max[b0:b0 + bsz] = h.max(axis=1)
            ids = t.route_links[s, d].astype(np.int64)       # [b, E, max_hops]
            ids += (np.arange(bsz) * (n_links + 1))[:, None, None]
            w = np.broadcast_to(vol[None, :, None], ids.shape)
            ltb = np.bincount(ids.ravel(), weights=w.ravel(),
                              minlength=bsz * (n_links + 1))
            ltb = ltb.reshape(bsz, n_links + 1)[:, :n_links]
            lt[b0:b0 + bsz] = ltb
            dst_flat = (t.link_dst.astype(np.int64)[None, :]
                        + (np.arange(bsz) * n)[:, None])
            ctb = np.bincount(dst_flat.ravel(), weights=ltb.ravel(),
                              minlength=bsz * n).reshape(bsz, n)
            core_tr[b0:b0 + bsz] = ctb
            comp = np.zeros((bsz, n))
            comp[np.arange(bsz)[:, None], Pb] = compute[None, :] / noc.core_flops
            if t.uniform:
                per_core_max[b0:b0 + bsz] = (comp + ctb / noc.link_bw).max(axis=1)
            else:
                # per-core serialization at each incoming link's own bandwidth
                wct = np.bincount(dst_flat.ravel(),
                                  weights=(ltb * t.inv_bw[None, :]).ravel(),
                                  minlength=bsz * n).reshape(bsz, n)
                per_core_max[b0:b0 + bsz] = (comp + wct).max(axis=1)
                path_lat[b0:b0 + bsz] = t.route_lat[s, d].max(axis=1)
        return cc, h_max, lt, core_tr, per_core_max, path_lat

    # ---- directional CDV (paper Eq. 4 terms) -------------------------------
    def directional_cdv(self, graph: LogicalGraph, placements,
                        backend: str = "auto",
                        validate: bool = True) -> np.ndarray:
        """[B, rows, cols, 4] bytes crossing each L/R/U/D link of every core."""
        t = self.tables
        if t.cdv_in_ids is None:
            raise ValueError("directional CDV is defined for grid topologies "
                             f"only; {type(self.noc).__name__} has no L/R/U/D "
                             "link structure")
        lt = self.evaluate(graph, placements, backend=backend,
                           validate=validate).link_traffic
        B = lt.shape[0]
        cdv = lt.copy()
        np.add.at(cdv, (np.arange(B)[:, None],
                        t.cdv_in_ids.astype(np.int64)[None, :]), lt)
        return cdv.reshape(B, t.rows, t.cols, 4)

    # ---- jitted kernels ----------------------------------------------------
    def _get_jax_fn(self, kind: str):
        fn = self._jax_fns.get(kind)
        if fn is not None:
            return fn
        _import_jax()
        t = self.tables
        hops = jnp.asarray(t.hops)
        flat_routes = jnp.asarray(
            t.route_links.reshape(t.n_cores * t.n_cores, t.max_hops)
            if t.max_hops else
            t.route_links.reshape(t.n_cores * t.n_cores, 0))
        link_dst = jnp.asarray(t.link_dst.astype(np.int32))
        n, n_links = t.n_cores, t.n_links
        inv_bw_l = None if t.uniform else jnp.asarray(t.inv_bw)
        route_lat_flat = (None if t.uniform else
                          jnp.asarray(t.route_lat.reshape(-1)))

        if kind == "comm":
            @jax.jit
            def fn(P, src, dst, vol):
                h = hops[P[:, src], P[:, dst]]               # [B, E]
                return (h.astype(vol.dtype) * vol[None, :]).sum(axis=1)
        elif kind == "full_pallas":
            from ..kernels.noc_segsum import link_traffic_pallas
            interpret = jax.default_backend() != "tpu"
            # dense [n_links, n] one-hot of link_dst: core traffic becomes a
            # matmul on the kernel's output instead of a second scatter
            dst_oh = np.zeros((n_links, n), np.float32)
            dst_oh[np.arange(n_links), t.link_dst] = 1.0
            dst_oh = jnp.asarray(dst_oh)
            inv_bw = 1.0 / self.noc.link_bw

            @jax.jit
            def fn(P, src, dst, vol, comp_nodes):
                s, d = P[:, src], P[:, dst]                  # [B, E]
                h = hops[s, d]
                cc = (h.astype(vol.dtype) * vol[None, :]).sum(axis=1)
                ids = flat_routes[s * n + d]                 # [B, E, max_hops]
                B = ids.shape[0]
                w = jnp.broadcast_to(vol[None, :, None], ids.shape)
                lt = link_traffic_pallas(ids.reshape(B, -1),
                                         w.reshape(B, -1).astype(jnp.float32),
                                         n_links,
                                         interpret=interpret).astype(vol.dtype)
                comp = jnp.zeros((B, n), vol.dtype).at[
                    jnp.arange(B)[:, None], P].set(comp_nodes[None, :])
                if t.uniform:
                    core_tr = _mm(lt, dst_oh.astype(vol.dtype))  # [B, n]
                    per_core_max = (comp + core_tr * inv_bw).max(axis=1)
                    return cc, h.max(axis=1), lt, core_tr, per_core_max
                core_tr = _mm(lt, dst_oh.astype(vol.dtype))
                wct = _mm(lt * inv_bw_l[None, :].astype(vol.dtype),
                          dst_oh.astype(vol.dtype))
                per_core_max = (comp + wct).max(axis=1)
                plat = route_lat_flat[s * n + d].max(axis=1)
                return cc, h.max(axis=1), lt, core_tr, per_core_max, plat
        else:
            def one(p, src, dst, vol, comp_nodes):
                s, d = p[src], p[dst]
                h = hops[s, d]
                cc = jnp.sum(h.astype(vol.dtype) * vol)
                ids = flat_routes[s * n + d]                 # [E, max_hops]
                w = jnp.broadcast_to(vol[:, None], ids.shape)
                lt = jnp.zeros(n_links + 1, vol.dtype).at[ids.reshape(-1)].add(
                    w.reshape(-1))[:n_links]
                core_tr = jnp.zeros(n, vol.dtype).at[link_dst].add(lt)
                comp = jnp.zeros(n, vol.dtype).at[p].set(comp_nodes)
                if t.uniform:
                    per_core_max = (comp + core_tr / self.noc.link_bw).max()
                    return cc, jnp.max(h), lt, core_tr, per_core_max
                wct = jnp.zeros(n, vol.dtype).at[link_dst].add(
                    lt * inv_bw_l.astype(vol.dtype))
                per_core_max = (comp + wct).max()
                plat = route_lat_flat[s * n + d].max()
                return cc, jnp.max(h), lt, core_tr, per_core_max, plat

            fn = jax.jit(jax.vmap(one, in_axes=(0, None, None, None, None)))
        self._jax_fns[kind] = fn
        return fn

    # ---- fused objective scorers (jax/pallas) ------------------------------
    def make_fused_scorer(self, graph: LogicalGraph, terms,
                          e_byte_hop: float = 1e-11,
                          p_core_static: float = 0.05,
                          backend: str = "jax"):
        """``placements [B, n] -> weighted objective scores [B]`` in one
        fused device dispatch.

        ``terms`` is ``((metric, weight), ...)`` over
        ``comm_cost | max_link | latency | mean_hops | energy | interchip``.
        Unlike the generic :func:`repro.deploy.objective.objective_scorer`
        path (full :meth:`evaluate` → :class:`BatchMetrics` → numpy combine),
        this compiles exactly the metric graph the objective needs: gather-only
        for comm/mean-hops combos, a single link-traffic segment-sum (scatter
        on the jax backend, the Pallas kernel on ``backend="pallas"``) when
        link-level terms appear, and per-core reductions only when latency or
        energy is involved. Energy uses the topology's per-link
        ``energy_per_byte`` when available, else the scalar ``e_byte_hop``;
        ``interchip`` contributes 0 on flat topologies.
        """
        resolved = self._resolve(backend)
        if resolved not in ("jax", "pallas"):
            raise ValueError("make_fused_scorer is the jax/pallas fast path; "
                             f"got backend={backend!r}")
        terms = tuple((str(m), float(w)) for m, w in terms)
        key = ("fused", resolved, terms, float(e_byte_hop),
               float(p_core_static))
        fn = self._jax_fns.get(key)
        if fn is None:
            fn = self._build_fused_fn(resolved, terms, e_byte_hop,
                                      p_core_static)
            self._jax_fns[key] = fn
        src, dst, vol, compute = self.edge_arrays(graph)
        if src.size:
            jsrc, jdst = jnp.asarray(src), jnp.asarray(dst)
            jvol = jnp.asarray(vol, _jx_float())
            jcomp = jnp.asarray(compute / self.noc.core_flops, _jx_float())

        def score(placements):
            P = np.asarray(placements, dtype=np.int64)
            if P.ndim == 1:
                P = P[None, :]
            if P.shape[0] == 0 or src.size == 0:
                return np.zeros(P.shape[0])
            return np.asarray(fn(jnp.asarray(P), jsrc, jdst, jvol, jcomp),
                              np.float64)
        return score

    def _build_fused_fn(self, resolved: str, terms, e_byte_hop: float,
                        p_core_static: float):
        _import_jax()
        t = self.tables
        known = ("comm_cost", "max_link", "latency", "mean_hops", "energy",
                 "interchip")
        metrics = [m for m, _ in terms]
        unknown = [m for m in metrics if m not in known]
        if unknown:
            raise ValueError(f"fused scorer cannot compute {unknown}; "
                             f"supported terms: {known}")
        w = {}
        for m, weight in terms:
            w[m] = w.get(m, 0.0) + weight
        need_links = any(m in ("max_link", "latency", "energy", "interchip")
                         for m in w)
        need_latency = "latency" in w or "energy" in w

        hops = jnp.asarray(t.hops)
        flat_routes = jnp.asarray(
            t.route_links.reshape(t.n_cores * t.n_cores, t.max_hops)
            if t.max_hops else
            t.route_links.reshape(t.n_cores * t.n_cores, 0))
        link_dst = jnp.asarray(t.link_dst.astype(np.int32))
        n, n_links = t.n_cores, t.n_links
        inv_bw_l = None if t.uniform else jnp.asarray(t.inv_bw)
        route_lat_flat = (None if t.uniform else
                          jnp.asarray(t.route_lat.reshape(-1)))
        eb = (None if t.energy_per_byte is None
              else jnp.asarray(t.energy_per_byte))
        ic = (None if t.interchip is None
              else jnp.asarray(t.interchip.astype(np.float64)))
        hop_latency, link_bw = self.noc.hop_latency, self.noc.link_bw
        static_w = p_core_static * n

        if resolved == "pallas":
            from ..kernels.noc_segsum import link_traffic_pallas
            interpret = jax.default_backend() != "tpu"
            dst_oh = np.zeros((n_links, n), np.float32)
            dst_oh[np.arange(n_links), t.link_dst] = 1.0
            dst_oh = jnp.asarray(dst_oh)

        def batched_link_traffic(ids, vol, dtype):
            B = ids.shape[0]
            wts = jnp.broadcast_to(vol[None, :, None], ids.shape)
            if resolved == "pallas":
                return link_traffic_pallas(
                    ids.reshape(B, -1), wts.reshape(B, -1).astype(jnp.float32),
                    n_links, interpret=interpret).astype(dtype)

            def one(i, ww):
                return jnp.zeros(n_links + 1, dtype).at[i.reshape(-1)].add(
                    ww.reshape(-1))[:n_links]
            return jax.vmap(one)(ids, wts.astype(dtype))

        def core_sum(lt, dtype):
            """[B, n_links] -> [B, n] sum of link values into their dst core."""
            if resolved == "pallas":
                return _mm(lt, dst_oh.astype(dtype))
            return jax.vmap(lambda x: jnp.zeros(n, dtype).at[link_dst]
                            .add(x))(lt)

        @jax.jit
        def fn(P, src, dst, vol, comp_nodes):
            s, d = P[:, src], P[:, dst]                      # [B, E]
            h = hops[s, d]
            cc = (h.astype(vol.dtype) * vol[None, :]).sum(axis=1)
            total = jnp.zeros_like(cc)
            if "comm_cost" in w:
                total = total + w["comm_cost"] * cc
            if "mean_hops" in w:
                tv = jnp.maximum(vol.sum(), jnp.finfo(vol.dtype).tiny)
                total = total + w["mean_hops"] * cc / tv
            if need_links:
                ids = flat_routes[s * n + d]                 # [B, E, max_hops]
                lt = batched_link_traffic(ids, vol, vol.dtype)
                if "max_link" in w:
                    total = total + w["max_link"] * lt.max(axis=1)
                if "interchip" in w and ic is not None:
                    total = total + w["interchip"] * _mm(lt, ic.astype(vol.dtype))
                if need_latency:
                    B = P.shape[0]
                    comp = jnp.zeros((B, n), vol.dtype).at[
                        jnp.arange(B)[:, None], P].set(comp_nodes[None, :])
                    if t.uniform:
                        wct = core_sum(lt, vol.dtype) / link_bw
                        plat = h.max(axis=1).astype(vol.dtype) * hop_latency
                    else:
                        wct = core_sum(lt * inv_bw_l[None, :].astype(vol.dtype),
                                       vol.dtype)
                        plat = route_lat_flat[s * n + d].max(axis=1)
                    latency = (comp + wct).max(axis=1) + plat
                    if "latency" in w:
                        total = total + w["latency"] * latency
                    if "energy" in w:
                        dyn = (e_byte_hop * cc if eb is None
                               else _mm(lt, eb.astype(vol.dtype)))
                        total = total + w["energy"] * (
                            dyn + static_w * latency)
            return total
        return fn


# ---------------------------------------------------------------------------
# Module-level cache + functional API
# ---------------------------------------------------------------------------

_CACHE: dict = {}


def batched_noc(noc: Topology) -> BatchedNoC:
    """Cached :class:`BatchedNoC` per topology (structural
    :meth:`Topology.cache_key` — grid shape + per-link attribute params)."""
    key = noc.cache_key()
    b = _CACHE.get(key)
    if b is None:
        b = _CACHE[key] = BatchedNoC(noc)
    return b


def evaluate_batch(noc: Topology, graph: LogicalGraph, placements,
                   backend: str = "auto") -> BatchMetrics:
    """Score a [B, n] population of placements in one vectorized call."""
    return batched_noc(noc).evaluate(graph, placements, backend=backend)


def comm_cost_batch(noc: Topology, graph: LogicalGraph, placements,
                    backend: str = "auto") -> np.ndarray:
    """[B] comm_cost (== the CDV objective of Eq. 4, negated reward)."""
    return batched_noc(noc).comm_cost(graph, placements, backend=backend)


def directional_cdv_batch(noc: Topology, graph: LogicalGraph, placements,
                          backend: str = "auto") -> np.ndarray:
    """[B, rows, cols, 4] per-core directional CDV, batched."""
    return batched_noc(noc).directional_cdv(graph, placements, backend=backend)


def validate_placements(noc: Topology, placements, n_nodes: int) -> np.ndarray:
    """Check a [B, n] (or [n]) placement array the way ``Topology.evaluate``
    does (injective, in range, and off dropped cores on degraded
    topologies); returns the 2-D int64 array. For validating user input once
    before handing it to an unvalidated scorer. Does not build (or cache)
    routing tables."""
    P = _check_placements(placements, n_nodes, noc.n_cores)
    dropped = getattr(noc, "dropped_nodes", frozenset)()
    if dropped and P.size:
        # reuse the topology's own rejection (clear InfeasibleTopologyError)
        bad = np.isin(P, np.fromiter(dropped, dtype=np.int64,
                                     count=len(dropped)))
        if bad.any():
            noc._check_placement(P[np.nonzero(bad.any(axis=1))[0][0]])
    return P


# Backends accepted by optimizers: "batch" (vectorized numpy float64 — exact
# parity with the reference loop on integer-volume graphs), "jax" (jit+vmap,
# explicit opt-in), "pallas" (jax path with the tiled segment-sum kernel of
# kernels/noc_segsum for link traffic; interpret mode on CPU, Mosaic on TPU —
# comm-cost-only scoring has no segment-sum, so it shares the jax gather),
# "auto" (currently the numpy path; see _resolve), "reference" (original
# Python loop).
SCORER_BACKENDS = ("batch", "numpy", "jax", "pallas", "auto", "reference")


def _counted_scorer(score, recorder, backend: str, objective_name: str,
                    fused: bool):
    """Wrap a scorer with :class:`repro.obs.Recorder` dispatch/eval counters.

    One ``noc_batch.dispatches`` increment per call and one
    ``noc_batch.evals`` increment per placement scored — deterministic
    counters (they count algorithmic work, not wall time), which is what lets
    the CI regression gate pin them. The wrapper exists only when a recorder
    is attached, so the detached hot path keeps the bare closure.
    """
    recorder.event("noc_batch.scorer", backend=backend,
                   objective=objective_name, fused=fused)

    def counted(placements):
        out = score(placements)
        recorder.count("noc_batch.dispatches")
        recorder.count("noc_batch.evals", int(np.asarray(out).shape[0]))
        return out
    return counted


def make_scorer(noc: Topology, graph: LogicalGraph, backend: str = "batch",
                objective="comm_cost", recorder=None):
    """Build ``placements [B, n] -> score [B]`` for the hot loops.

    ``backend="batch"`` keeps optimizer trajectories bit-identical to the
    sequential reference on integer-volume graphs (float64 all the way), which
    is why it is the optimizers' default. On continuous volumes the vectorized
    sum can differ from the sequential loop in the last ulp (pairwise vs
    sequential float64 summation) — pass ``backend="reference"`` when exact
    seed-reproduction of pre-noc_batch trajectories on such graphs matters.

    ``objective`` selects what the score *is*: the default ``"comm_cost"``
    keeps this exact comm-cost path (bit-identical trajectories); any other
    spec (a name from :data:`repro.deploy.objective.OBJECTIVES` or a
    ``{metric: weight}`` dict) dispatches to the full-metrics objective scorer
    of :mod:`repro.deploy.objective` (which fuses the metric graph into one
    device dispatch on the jax/pallas backends).

    ``recorder`` (a :class:`repro.obs.Recorder`) wraps the scorer with
    deterministic dispatch/eval counters and records which backend /
    objective / fusion path was built; ``None`` returns the bare closure
    (zero overhead — the historical hot path).
    """
    if backend not in SCORER_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"choose from {SCORER_BACKENDS}")
    obj_name = "comm_cost"
    if objective not in (None, "comm_cost"):
        # deploy sits above core in the layering — import lazily to keep
        # `import repro.core` light and cycle-free
        from ..deploy.objective import as_objective, objective_scorer
        obj = as_objective(objective)
        if not obj.is_comm_cost:
            score = objective_scorer(noc, graph, obj, backend)
            if recorder is None:
                return score
            fused = (backend in ("jax", "pallas") and HAS_JAX)
            return _counted_scorer(score, recorder, backend, obj.name, fused)
    if backend == "reference":
        def score_ref(placements):
            P = np.atleast_2d(np.asarray(placements, dtype=int))
            return np.array([noc.evaluate(graph, p).comm_cost for p in P])
        if recorder is not None:
            return _counted_scorer(score_ref, recorder, backend, obj_name,
                                   False)
        return score_ref
    b = batched_noc(noc)
    # Bind the edge arrays once — scorers are called per optimizer step (B=1
    # in sequential SA), so the O(n^2) nonzero scan must not be per-call.
    # No per-call validation: optimizer-generated placements are injective by
    # construction, and callers feeding user input (e.g. SA's ``init``) must
    # validate it once up front (see validate_placements).
    src, dst, vol, _ = b.edge_arrays(graph)
    if b._resolve(backend) in ("jax", "pallas"):
        f = b._get_jax_fn("comm")
        jsrc, jdst = jnp.asarray(src), jnp.asarray(dst)
        jvol = jnp.asarray(vol, _jx_float())

        def score(placements):
            P = np.asarray(placements, dtype=np.int64)
            if P.ndim == 1:
                P = P[None, :]
            if P.shape[0] == 0 or src.size == 0:
                return np.zeros(P.shape[0])
            return np.asarray(f(jnp.asarray(P), jsrc, jdst, jvol), np.float64)
    else:
        hops = b.tables.hops

        def score(placements):
            P = np.asarray(placements, dtype=np.int64)
            if P.ndim == 1:
                P = P[None, :]
            if P.shape[0] == 0 or src.size == 0:
                return np.zeros(P.shape[0])
            return (hops[P[:, src], P[:, dst]] * vol[None, :]).sum(axis=1)
    if recorder is not None:
        return _counted_scorer(score, recorder, backend, obj_name, False)
    return score
