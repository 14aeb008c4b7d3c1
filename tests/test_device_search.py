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


def test_pallas_delta_kernel_matches_numpy():
    from repro.kernels.delta_cost import delta_cost_pallas
    rng = np.random.default_rng(0)
    R, K, C = 4, 23, 32
    hops = rng.integers(0, 9, (C, C)).astype(np.float32)
    sb, db, sa_, da = (rng.integers(0, C, (R, K)) for _ in range(4))
    vol = rng.integers(0, 40, (R, K)).astype(np.float32)
    ref = (vol * (hops[sa_, da] - hops[sb, db])).sum(axis=1)
    out = np.asarray(delta_cost_pallas(sb, db, sa_, da, vol, hops,
                                       interpret=True))
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


def test_device_sa_pallas_delta_matches_jax_delta():
    noc = NoC(4, 8)
    g = _int_graph(24, seed=2)
    pj = simulated_annealing_device(g, noc, iters=150, seed=3,
                                    use_pallas=False)
    pp = simulated_annealing_device(g, noc, iters=150, seed=3,
                                    use_pallas=True)
    assert np.array_equal(pj, pp)


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
