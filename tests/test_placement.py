"""Placement methods (paper §4.3/§5): discretization, baselines, PPO."""
import numpy as np
import pytest

try:  # property tests need the dev extra; plain tests below run regardless
    from hypothesis import given, settings, strategies as st
    HAS_HYP = True
except ImportError:
    HAS_HYP = False

from repro.core import NoC, random_dag
from repro.core.placement import (optimize_placement, random_search, sigmate,
                                  simulated_annealing, zigzag)
from repro.core.placement.discretize import (actions_to_placement,
                                             continuous_to_grid,
                                             resolve_collisions)
from repro.core.placement.ppo import PPOConfig, run_ppo

if HAS_HYP:
    @given(st.integers(0, 10_000), st.integers(1, 32), st.integers(2, 8),
           st.integers(2, 8))
    @settings(max_examples=60, deadline=None)
    def test_discretize_always_injective(seed, n, rows, cols):
        """Any continuous action maps to a valid injective placement."""
        if n > rows * cols:
            n = rows * cols
        rng = np.random.default_rng(seed)
        cont = rng.normal(size=(n, 2)) * 2.0
        placement = actions_to_placement(cont, rows, cols)
        assert len(set(placement.tolist())) == n
        assert placement.min() >= 0 and placement.max() < rows * cols
else:
    @pytest.mark.skip(reason="hypothesis not installed (dev extra)")
    def test_hypothesis_properties():
        """Placeholder so missing property coverage shows as a skip."""


def test_no_collision_identity():
    """Non-colliding coords map to exactly their own cells."""
    coords = np.array([[0, 0], [1, 2], [3, 3]])
    out = resolve_collisions(coords, 4, 4)
    assert out.tolist() == [0, 6, 15]


def test_collision_resolved_to_nearest_clockwise():
    coords = np.array([[1, 1], [1, 1]])
    out = resolve_collisions(coords, 4, 4)
    assert out[0] == 5                       # first node keeps the cell
    # second lands at Manhattan distance 1, clockwise scan starts north
    assert out[1] == 1                       # (0,1) is due north of (1,1)


def test_continuous_to_grid_bins():
    cont = np.array([[-1.0, -1.0], [0.999, 0.999], [0.0, 0.0]])
    g = continuous_to_grid(cont, 4, 8, clip=1.0)
    assert g[0].tolist() == [0, 0]
    assert g[1].tolist() == [3, 7]
    assert g[2].tolist() == [2, 4]


def test_zigzag_sigmate_layouts():
    noc = NoC(3, 4)
    assert zigzag(12, noc).tolist() == list(range(12))
    sig = sigmate(12, noc).tolist()
    assert sig[:4] == [0, 1, 2, 3]
    assert sig[4:8] == [7, 6, 5, 4]          # serpentine reversal


def test_methods_beat_or_match_worstcase():
    g = random_dag(16, seed=5)
    noc = NoC(4, 8)
    zz = optimize_placement(g, noc, method="zigzag").comm_cost
    sa = optimize_placement(g, noc, method="simulated_annealing",
                            budget=1500).comm_cost
    gr = optimize_placement(g, noc, method="greedy").comm_cost
    assert sa <= zz * 1.001
    assert gr <= zz * 1.5                     # greedy is near zigzag or better


def test_ppo_improves_over_iterations():
    g = random_dag(12, seed=2)
    noc = NoC(4, 4)
    st_ = run_ppo(g, noc, PPOConfig(batch_size=16, iterations=8, seed=1,
                                    ppo_epochs=4))
    first = st_.history[0]["mean_cost"]
    last = min(h["mean_cost"] for h in st_.history)
    assert last < first                       # sampling distribution improved
    assert st_.best_placement is not None
    assert len(set(st_.best_placement.tolist())) == g.n


def test_ppo_phases_within_the_search():
    """Each iteration's four phase spans are summed into ``phases_s``, lie
    within the search's wall time, and reach the deployment report."""
    import time

    from repro.core.placement.ppo import PPO_PHASES
    g = random_dag(10, seed=4)
    noc = NoC(4, 4)
    t0 = time.perf_counter()
    st_ = run_ppo(g, noc, PPOConfig(batch_size=8, iterations=3,
                                    ppo_epochs=2, seed=0))
    wall = time.perf_counter() - t0
    assert tuple(st_.phases_s) == PPO_PHASES
    assert all(v > 0.0 for v in st_.phases_s.values())
    assert sum(st_.phases_s.values()) <= wall
    res = optimize_placement(g, noc, method="ppo", budget=3, batch_size=8,
                             ppo_epochs=2)
    assert tuple(res.phases_s) == PPO_PHASES
    assert sum(res.phases_s.values()) <= res.wall_time_s
    np.testing.assert_array_equal(res.placement, st_.best_placement)


def _sresnet18_graph():
    """The PPO cell's placement graph: S-ResNet18 cut into 32 slices."""
    from repro.core.partition import partition_model
    from repro.snn import profile_model, spike_resnet18
    prof = profile_model(spike_resnet18(n_classes=10, in_res=32, T=4),
                         batch=8, training=True)
    return partition_model(prof, 32, "balanced").to_graph()


def _eager_sample(key, actor, lap, feats, n_samples, half_log_2pi=None):
    """The sample phase op by op, as run_ppo ran it before it was compiled
    (``ac.gaussian_logp`` computes its own ½·log 2π)."""
    from repro.core.placement import actor_critic as ac
    import jax
    key, k_s = jax.random.split(key)
    mu, log_std = ac.actor_apply(actor, lap, feats)
    acts, logp = ac.sample_actions(k_s, mu, log_std, n_samples)
    return key, acts, logp


@pytest.mark.parametrize("shape", ["sresnet18_b256", "dag10_b8"])
def test_ppo_sample_bitwise_equals_eager(shape):
    """The compiled sample phase (``_sample``) returns the eager path's keys,
    actions and log-probs bit for bit over 40 iterations: one ulp in an
    action can move the discretized plan."""
    import jax
    import jax.numpy as jnp
    from repro.core.placement import actor_critic as ac
    from repro.core.placement.ppo import _sample
    if shape == "sresnet18_b256":
        g, batch = _sresnet18_graph(), 256
    else:
        g, batch = random_dag(10, seed=4), 8
    lap = jnp.asarray(g.laplacian(), jnp.float32)
    feats = jnp.asarray(g.node_features(), jnp.float32)
    actor, _ = ac.init_actor_critic(jax.random.PRNGKey(0), feats.shape[1],
                                    32, 64)
    k_ref = k_new = jax.random.PRNGKey(7)
    half_log_2pi = 0.5 * jnp.log(2 * jnp.pi)      # as run_ppo computes it
    for it in range(40):
        k_ref, a_ref, l_ref = _eager_sample(k_ref, actor, lap, feats, batch)
        k_new, a_new, l_new = _sample(k_new, actor, lap, feats, batch,
                                      half_log_2pi)
        assert np.array_equal(k_ref, k_new), it
        assert np.array_equal(a_ref, a_new), it
        assert np.array_equal(l_ref, l_new), it
        # new weights each iteration, as the update gives them
        actor = jax.tree_util.tree_map(lambda x: x * 1.01, actor)


def test_ppo_plan_unchanged_by_compiled_sampling(monkeypatch):
    """run_ppo on the PPO cell's graph and fabric returns the plan and the
    history of a run whose sample phase is the eager composition, and counts
    the sample phase's compiled programs."""
    from repro.core.placement import ppo
    from repro.core.topology import parse_topology
    from repro.obs import Recorder
    g, noc = _sresnet18_graph(), parse_topology("mesh:4x8")
    cfg = PPOConfig(batch_size=32, iterations=4, ppo_epochs=2, seed=0)
    rec = Recorder()
    new = run_ppo(g, noc, cfg, recorder=rec)
    assert rec.counters["ppo.sample.programs"] == \
        ppo.SAMPLE_PROGRAMS * cfg.iterations == 2 * cfg.iterations
    assert new.counters == {"ppo.sample.programs": 2 * cfg.iterations}
    monkeypatch.setattr(ppo, "_sample", _eager_sample)
    ref = run_ppo(g, noc, cfg)
    np.testing.assert_array_equal(new.best_placement, ref.best_placement)
    assert new.history == ref.history


def test_ppo_freeze_gcn_keeps_gcn_params():
    """Paper: the GCN encoder is pre-trained and not updated by PPO."""
    import jax
    import jax.numpy as jnp
    from repro.core.placement.actor_critic import init_actor_critic
    g = random_dag(8, seed=0)
    noc = NoC(3, 3)
    st_ = run_ppo(g, noc, PPOConfig(batch_size=8, iterations=2, ppo_epochs=2,
                                    freeze_gcn=True, seed=0))
    actor0, _ = init_actor_critic(jax.random.PRNGKey(0), 5, 32, 64)
    assert jnp.allclose(st_.actor["gcn"]["w0"], actor0["gcn"]["w0"])
    # the FC head DID move
    assert not jnp.allclose(st_.actor["fc1_w"], actor0["fc1_w"])


def test_ppo_fused_scan_matches_epoch_loop():
    """_ppo_update_scan (one dispatch) == ppo_epochs separate _ppo_update
    dispatches — the fused loop must not change the training math."""
    import jax
    import jax.numpy as jnp
    from repro.core.placement import actor_critic as ac
    from repro.core.placement.ppo import _ppo_update, _ppo_update_scan
    from repro.train.optim import AdamWConfig, adamw_init

    g = random_dag(10, seed=4)
    lap = jnp.asarray(g.laplacian(), jnp.float32)
    feats = jnp.asarray(g.node_features(), jnp.float32)
    actor, critic = ac.init_actor_critic(jax.random.PRNGKey(0),
                                         feats.shape[1], 32, 64)
    adam = AdamWConfig(lr=5e-3)
    opt_a, opt_c = adamw_init(actor, adam), adamw_init(critic, adam)
    mu, log_std = ac.actor_apply(actor, lap, feats)
    acts, logp = ac.sample_actions(jax.random.PRNGKey(1), mu, log_std, 12)
    rewards = jnp.linspace(-1.0, 1.0, 12)

    a1, c1, oa1, oc1 = actor, critic, opt_a, opt_c
    for _ in range(4):
        a1, c1, oa1, oc1, la1, lc1 = _ppo_update(
            a1, c1, oa1, oc1, lap, feats, acts, logp, rewards,
            0.2, 1e-3, True, adam, adam)
    a2, c2, oa2, oc2, la2, lc2 = _ppo_update_scan(
        actor, critic, opt_a, opt_c, lap, feats, acts, logp, rewards,
        4, 0.2, 1e-3, True, adam, adam)
    # full pytrees: params AND optimizer moments (run_ppo threads all four
    # across iterations, so a swapped carry slot must fail here). Bitwise:
    # the rolled scan keeps seed-for-seed trajectories, so any last-ulp
    # drift (e.g. from unroll>1 re-fusing epochs) is exactly the regression
    # this test must catch.
    for t1, t2 in ((a1, a2), (c1, c2), (oa1, oa2), (oc1, oc2)):
        l1 = jax.tree_util.tree_leaves(t1)
        l2 = jax.tree_util.tree_leaves(t2)
        assert len(l1) == len(l2)
        for x, y in zip(l1, l2):
            assert jnp.array_equal(x, y), (x, y)
    assert jnp.array_equal(la1, la2)
    assert jnp.array_equal(lc1, lc2)


def test_random_search_monotone_in_budget():
    g = random_dag(10, seed=9)
    noc = NoC(4, 4)
    c1 = noc.evaluate(g, random_search(g, noc, iters=20, seed=3)).comm_cost
    c2 = noc.evaluate(g, random_search(g, noc, iters=400, seed=3)).comm_cost
    assert c2 <= c1


def test_greedy_matches_reference():
    """Vectorized greedy pins identical placements to the per-pair oracle —
    integer and continuous volumes, intact and degraded fabrics."""
    from repro.core.placement.baselines import _greedy_reference, greedy
    from repro.core.topology import degrade
    noc = NoC(4, 8)
    for seed in range(5):
        g = random_dag(20, seed=seed)
        gi = random_dag(20, seed=seed)
        gi.adj[:] = np.round(gi.adj)
        for graph in (g, gi):
            assert np.array_equal(greedy(graph, noc),
                                  _greedy_reference(graph, noc))
    dt = degrade(noc, nodes=(0, 7))
    g = random_dag(20, seed=11)
    p = greedy(g, dt)
    assert np.array_equal(p, _greedy_reference(g, dt))
    assert not {0, 7} & set(p.tolist())


def test_sa_degenerate_decay_schedules():
    """Default keeps the historical stretched schedule (degenerate proposals
    skip the decay); decay_on_degenerate=True realizes the intended fixed
    geometric schedule ending at t_init * t_end_frac."""
    from repro.obs import Recorder
    g = random_dag(28, seed=5)
    g.adj[:] = np.round(g.adj)
    noc = NoC(4, 8)
    iters, t_end_frac = 500, 1e-3
    cooling = t_end_frac ** (1.0 / iters)

    runs = {}
    for flag in (False, True):
        rec = Recorder()
        p = simulated_annealing(g, noc, iters=iters, seed=0,
                                t_end_frac=t_end_frac, recorder=rec,
                                decay_on_degenerate=flag)
        ev = [e["attrs"] for e in rec.events if e["name"] == "sa.iter"]
        assert len(ev) == iters
        runs[flag] = (p, ev)

    n_degen = sum(not e["proposed"] for e in runs[False][1])
    assert n_degen > 0                    # the stream does collide here
    t_init = runs[False][1][0]["temperature"] / (
        cooling if runs[False][1][0]["proposed"] else 1.0)
    # historical default: decay happens on the proposed steps only
    np.testing.assert_allclose(
        runs[False][1][-1]["temperature"],
        t_init * cooling ** (iters - n_degen), rtol=1e-9)
    # fixed schedule: exactly iters decays regardless of collisions
    np.testing.assert_allclose(
        runs[True][1][-1]["temperature"],
        t_init * cooling ** iters, rtol=1e-9)
    # the proposal/accept RNG stream is untouched by the flag at these
    # temperatures: same placement either way, so default stays bit-identical
    assert np.array_equal(runs[False][0], runs[True][0])
