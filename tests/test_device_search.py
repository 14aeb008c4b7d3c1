"""Device-resident search (`repro.core.placement.device_search`) and the
O(degree) delta-cost tables/kernels it builds on."""
import numpy as np
import pytest

try:  # property tests need the dev extra; plain tests below run regardless
    from hypothesis import given, settings, strategies as st
    HAS_HYP = True
except ImportError:
    HAS_HYP = False

from repro.core import NoC, random_dag
from repro.core.noc_batch import (build_incident_tables, delta_comm_cost,
                                  evaluate_batch)
from repro.core.placement import (genetic_device, optimize_placement,
                                  simulated_annealing_device)
from repro.core.placement.baselines import core_pool
from repro.core.topology import degrade
from repro.obs import Recorder


def _int_graph(n, seed, p=0.3):
    g = random_dag(n, p=p, seed=seed)
    g.adj[:] = np.round(g.adj)          # integer volumes: exact float64 sums
    return g


def _comm(noc, g, placement):
    return float(evaluate_batch(noc, g, np.asarray(placement)[None])
                 .comm_cost[0])


# ---------------------------------------------------------------------------
# Incident tables + numpy delta reference
# ---------------------------------------------------------------------------

def test_incident_tables_shape_and_sentinel():
    g = _int_graph(12, seed=0)
    t = build_incident_tables(g)
    assert t.other.shape == t.vol.shape == t.is_src.shape
    assert t.other.shape[0] == g.n + 1
    # sentinel row: all padding, volume zero
    assert (t.other[g.n] == g.n).all() and (t.vol[g.n] == 0).all()
    assert int(t.degree[:g.n].sum()) == 2 * int(
        ((g.adj > 0) & ~np.eye(g.n, dtype=bool)).sum())


def test_delta_exact_vs_full_reference():
    """delta == full(after) - full(before), bit-exact on integer volumes."""
    noc = NoC(4, 8)
    g = _int_graph(24, seed=3)
    tbl = build_incident_tables(g)
    rng = np.random.default_rng(0)
    slots = rng.permutation(noc.n_cores)
    for _ in range(60):
        i, j = (int(x) for x in rng.integers(0, slots.size, 2))
        d = delta_comm_cost(noc, g, slots, i, j, tbl)
        before = _comm(noc, g, slots[:g.n])
        slots[i], slots[j] = slots[j], slots[i]
        after = _comm(noc, g, slots[:g.n])
        assert d == after - before       # exact, not approx


if HAS_HYP:
    @given(st.integers(0, 10_000), st.integers(2, 20), st.integers(0, 200))
    @settings(max_examples=40, deadline=None)
    def test_delta_swap_sequences_accumulate(seed, n, swaps_seed):
        """Random swap sequences via delta_comm_cost accumulate to the full
        evaluate_batch score (numpy path is exact on integer volumes)."""
        noc = NoC(4, 4)
        n = min(n, noc.n_cores)
        g = _int_graph(n, seed=seed, p=0.4)
        tbl = build_incident_tables(g)
        rng = np.random.default_rng(swaps_seed)
        slots = rng.permutation(noc.n_cores)
        cost = _comm(noc, g, slots[:n])
        for _ in range(20):
            i, j = (int(x) for x in rng.integers(0, slots.size, 2))
            cost += delta_comm_cost(noc, g, slots, i, j, tbl)
            slots[i], slots[j] = slots[j], slots[i]
        assert cost == _comm(noc, g, slots[:n])
else:
    @pytest.mark.skip(reason="hypothesis not installed (dev extra)")
    def test_delta_swap_sequences_accumulate():
        """Placeholder so missing property coverage shows as a skip."""


def test_delta_on_degraded_topology():
    """Hop tables rebuild on cache_key change (dropped link/node): the delta
    stays exactly full(after) - full(before) against the detoured routes."""
    noc = NoC(4, 8)
    dt = degrade(noc, links=(5,), nodes=(9,))
    g = _int_graph(20, seed=7)
    tbl = build_incident_tables(g)
    pool = np.asarray(core_pool(dt))
    rng = np.random.default_rng(1)
    slots = rng.permutation(pool)
    for _ in range(40):
        i, j = (int(x) for x in rng.integers(0, slots.size, 2))
        d = delta_comm_cost(dt, g, slots, i, j, tbl)
        before = _comm(dt, g, slots[:g.n])
        slots[i], slots[j] = slots[j], slots[i]
        assert d == _comm(dt, g, slots[:g.n]) - before
    # intact vs degraded must disagree somewhere on the same swap stream
    assert _comm(dt, g, slots[:g.n]) != _comm(noc, g, slots[:g.n])


def _hub_graph(n, degree, seed):
    """A chain over nodes 1..n-1 and node 0 joined to ``degree`` others, as
    source or destination in turn: incident degree exactly ``degree``, most
    rows padded. Volumes are eighths, so float32 sums are exact."""
    from repro.core.graph import LogicalGraph
    rng = np.random.default_rng(seed)
    adj = np.zeros((n, n))
    for u in range(1, n - 1):
        adj[u, u + 1] = rng.integers(1, 4000) / 8
    for k, v in enumerate(rng.choice(np.arange(1, n), degree, replace=False)):
        adj[(0, v) if k % 2 else (v, 0)] = rng.integers(1, 4000) / 8
    return LogicalGraph(adj, np.ones(n), np.ones(n))


@pytest.mark.parametrize("rows,cols,n,degree,faulty", [
    (8, 8, 40, 4, False),       # degree 4 on 64 cores, 24 free slots
    (8, 8, 60, 56, False),      # degree 56 on 64 cores
    (16, 16, 200, 4, False),    # degree 4 on 256 cores
    (16, 16, 200, 56, False),   # degree 56 on 256 cores
    (4, 8, 20, 6, True),        # degraded fabric: hops not symmetric
])
def test_pallas_delta_kernel_matches_numpy(rows, cols, n, degree, faulty):
    """The kernel (interpret mode) equals the numpy reference exactly on
    non-integer volumes, padding rows, free-slot sentinels, a-b edges and
    degenerate swaps; 12 chains leave a tail after the kernel's groups."""
    import jax.numpy as jnp

    from repro.core.noc_batch import batched_noc
    from repro.kernels.delta_cost import delta_cost_pallas, incident_keys
    noc = NoC(rows, cols)
    topo = degrade(noc, links=(5,), nodes=(9,)) if faulty else noc
    g = _hub_graph(n, degree, seed=n + degree)
    tbl = build_incident_tables(g)
    assert tbl.other.shape == (n + 1, degree)
    assert (g.adj % 1).any()
    hops = batched_noc(topo).tables.hops
    assert faulty == (hops != hops.T).any()
    pool = core_pool(topo)
    pool = np.arange(pool) if isinstance(pool, int) else np.asarray(pool)
    rng = np.random.default_rng(degree)
    R = 12
    slots = np.stack([rng.permutation(pool) for _ in range(R)])
    i = rng.integers(0, pool.size, R)
    j = rng.integers(0, pool.size, R)
    partner = int(tbl.other[0, 0])
    # the hub and a partner (an a-b edge) both ways, a free slot, two free
    # slots, the same slot twice
    i[:5] = [0, partner, 0, n, 3]
    j[:5] = [partner, 0, n + 1, n + 1, 3]
    out = np.asarray(delta_cost_pallas(
        jnp.asarray(slots, jnp.int32), jnp.asarray(i, jnp.int32),
        jnp.asarray(j, jnp.int32),
        incident_keys(jnp.asarray(tbl.other), jnp.asarray(tbl.is_src)),
        jnp.asarray(tbl.vol, jnp.float32), jnp.asarray(hops, jnp.float32),
        jnp.asarray(hops.T, jnp.float32), n=n, interpret=True))
    ref = np.array([delta_comm_cost(topo, g, slots[r], int(i[r]), int(j[r]),
                                    tbl) for r in range(R)])
    assert (ref != 0).sum() >= R // 2     # not a vacuous comparison
    np.testing.assert_array_equal(out, ref.astype(np.float32))


# ---------------------------------------------------------------------------
# Device SA
# ---------------------------------------------------------------------------

def test_device_sa_valid_and_improves():
    noc = NoC(4, 8)
    g = _int_graph(28, seed=5)
    p = simulated_annealing_device(g, noc, iters=800, seed=0)
    assert len(set(p.tolist())) == g.n
    assert p.min() >= 0 and p.max() < noc.n_cores
    from repro.core.placement import zigzag
    assert _comm(noc, g, p) < _comm(noc, g, zigzag(g.n, noc))


def test_device_sa_phases_within_the_search():
    """The three phase spans come back through ``phases_s`` and through
    ``optimize_placement``'s result, lie within the search's wall time,
    and change nothing of the plan."""
    import time

    from repro.core.placement.device_search import SA_PHASES
    noc = NoC(4, 4)
    g = _int_graph(12, seed=3)
    phases = {}
    t0 = time.perf_counter()
    p = simulated_annealing_device(g, noc, iters=200, seed=0, restarts=2,
                                   phases_s=phases)
    wall = time.perf_counter() - t0
    assert tuple(phases) == SA_PHASES
    assert all(v >= 0.0 for v in phases.values())
    assert sum(phases.values()) <= wall
    np.testing.assert_array_equal(
        p, simulated_annealing_device(g, noc, iters=200, seed=0, restarts=2))
    res = optimize_placement(g, noc, method="sa", backend="device",
                             budget=200, restarts=2)
    assert tuple(res.phases_s) == SA_PHASES
    assert sum(res.phases_s.values()) <= res.wall_time_s
    np.testing.assert_array_equal(res.placement, p)


def test_device_sa_deterministic_and_restarts_monotone():
    noc = NoC(4, 8)
    g = _int_graph(28, seed=5)
    p1 = simulated_annealing_device(g, noc, iters=400, seed=0)
    p2 = simulated_annealing_device(g, noc, iters=400, seed=0)
    assert np.array_equal(p1, p2)
    # chain 0 is fold_in(seed, 0) regardless of restarts: more chains can
    # only match or beat the single-chain best
    p8 = simulated_annealing_device(g, noc, iters=400, seed=0, restarts=8)
    assert _comm(noc, g, p8) <= _comm(noc, g, p1)


@pytest.mark.parametrize("graph", ["chain", "spikformer"])
def test_device_sa_pallas_delta_matches_jax_delta(graph):
    """Same plan with and without the kernel, on a random DAG and on a tiny
    Spikformer's branched graph (Q/K/V fan-out, residual operands)."""
    if graph == "chain":
        noc = NoC(4, 8)
        g = _int_graph(24, seed=2)
    else:
        from repro.deploy import deploy_model
        from repro.snn import spikformer
        noc = NoC(8, 8)
        model = spikformer(depth=2, dim=64, heads=4, mlp_ratio=4,
                           n_classes=10, in_res=32, in_ch=3, T=4, patch=4)
        g = deploy_model(model, noc, method="zigzag",
                         partition_strategy="balanced",
                         schedule="none").graph
        assert build_incident_tables(g).max_degree > 8
    pj = simulated_annealing_device(g, noc, iters=150, seed=3,
                                    use_pallas=False)
    rec = Recorder()
    pp = simulated_annealing_device(g, noc, iters=150, seed=3,
                                    use_pallas=True, recorder=rec)
    assert np.array_equal(pj, pp)
    summary = [e["attrs"] for e in rec.events if e["name"] == "sa.device"]
    assert summary[0]["delta_path"] == "row_select"


def test_device_sa_recorder_identity_and_schema():
    noc = NoC(4, 8)
    g = _int_graph(24, seed=4)
    rec = Recorder()
    pa = simulated_annealing_device(g, noc, iters=300, seed=1, restarts=4,
                                    recorder=rec)
    pb = simulated_annealing_device(g, noc, iters=300, seed=1, restarts=4)
    assert np.array_equal(pa, pb)        # recorder on/off bit-identity
    ev = [e["attrs"] for e in rec.events if e["name"] == "sa.iter"]
    assert len(ev) == 300                # host schema: one event per step
    assert set(ev[0]) == {"iter", "cost", "best_cost", "temperature",
                          "accepted", "proposed"}
    assert ev[-1]["best_cost"] <= ev[0]["best_cost"]
    n_acc = sum(e["accepted"] for e in ev)
    assert rec.counters.get("sa.accepted", 0) == n_acc
    summary = [e for e in rec.events if e["name"] == "sa.device"]
    assert len(summary) == 1 and summary[0]["attrs"]["restarts"] == 4
    # on the CPU the delta takes the gather path
    assert summary[0]["attrs"]["delta_path"] == "gather"


def test_device_sa_on_degraded_topology():
    noc = NoC(4, 8)
    dt = degrade(noc, nodes=(3,))
    g = _int_graph(24, seed=6)
    p = simulated_annealing_device(g, dt, iters=400, seed=0, restarts=2)
    assert 3 not in p.tolist()           # never lands on the dropped core
    assert len(set(p.tolist())) == g.n


def test_device_sa_rejects_non_comm_objective():
    noc = NoC(4, 8)
    g = _int_graph(16, seed=0)
    with pytest.raises(ValueError, match="comm_cost"):
        simulated_annealing_device(g, noc, iters=10, objective="max_link")


# ---------------------------------------------------------------------------
# Device GA
# ---------------------------------------------------------------------------

def test_device_ga_valid_and_improves():
    noc = NoC(4, 8)
    g = _int_graph(28, seed=5)
    p = genetic_device(g, noc, generations=20, pop_size=16, seed=0)
    assert len(set(p.tolist())) == g.n
    from repro.core.placement import zigzag
    assert _comm(noc, g, p) <= _comm(noc, g, zigzag(g.n, noc))


def test_device_ga_recorder_identity_and_schema():
    noc = NoC(4, 8)
    g = _int_graph(20, seed=8)
    rec = Recorder()
    pa = genetic_device(g, noc, generations=10, pop_size=8, seed=2,
                        recorder=rec)
    pb = genetic_device(g, noc, generations=10, pop_size=8, seed=2)
    assert np.array_equal(pa, pb)
    ev = [e["attrs"] for e in rec.events if e["name"] == "ga.gen"]
    assert [e["gen"] for e in ev] == list(range(-1, 10))  # host schema
    assert set(ev[0]) == {"gen", "best_cost", "cur_min", "cur_mean",
                          "diversity"}
    assert ev[-1]["best_cost"] <= ev[0]["best_cost"]


# ---------------------------------------------------------------------------
# optimize_placement wiring
# ---------------------------------------------------------------------------

def test_optimizer_device_backend_and_aliases():
    noc = NoC(4, 8)
    g = _int_graph(24, seed=1)
    r = optimize_placement(g, noc, method="sa", backend="device", budget=300,
                           restarts=4)
    assert r.method == "simulated_annealing"
    assert r.comm_cost == _comm(noc, g, r.placement)
    r2 = optimize_placement(g, noc, method="ga", backend="device",
                            budget=1000, pop_size=8)
    assert r2.method == "genetic"
    # host backends keep rejecting unknown kwargs / combos
    with pytest.raises(ValueError, match="device"):
        optimize_placement(g, noc, method="zigzag", backend="device")


def test_optimizer_rl_init_joins_best_of():
    """A user-supplied init (e.g. a device-SA placement) can only improve
    the RL methods' returned best."""
    noc = NoC(4, 4)
    g = _int_graph(12, seed=3)
    seed_p = simulated_annealing_device(g, noc, iters=400, seed=0)
    base = optimize_placement(g, noc, method="policy", budget=2, seed=0)
    seeded = optimize_placement(g, noc, method="policy", budget=2, seed=0,
                                init=seed_p)
    assert seeded.comm_cost <= base.comm_cost
    assert seeded.comm_cost <= _comm(noc, g, seed_p)
