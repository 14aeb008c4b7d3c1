"""Placement-as-a-service: typed requests, plan cache, warm starts,
batches, the HTTP surface, and the method-kwarg validation that rides along.

The load-bearing guarantees pinned here:

* `DeployRequest` round-trips through JSON with a `cache_key()` that is
  stable across processes (the cache's restart-persistence contract);
* a `DegradedTopology` request never serves the healthy topology's cached
  plan (fault isolation of the cache key);
* `deploy_model` delegating through the request layer is bit-identical to
  the direct engine call, and a batch's rows are answered against the
  cache as the batch found it (with no donor there, bit-identical to solo
  cold searches);
* typo'd method kwargs raise TypeError listing the accepted names instead
  of being silently swallowed.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core import NoC, random_dag
from repro.core.placement import optimize_placement
from repro.core.placement.optimizer import method_kwargs, validate_method_kw
from repro.core.placement.ppo import PPOConfig
from repro.core.topology import degrade
from repro.deploy import (DeployRequest, PlacementService, PlanCache,
                          RequestEncodeError, deploy_model, execute_request,
                          instantiate_plan, topology_from_key)
from repro.deploy.runtime import run_scenario
from repro.deploy.service import (DeployResponse, fetch_plan, make_server,
                                  request_over_http)
from repro.launch.serve import MicroBatchQueue
from repro.snn import spike_resnet18


def _model_noc():
    return spike_resnet18(n_classes=10, in_res=32, T=4), NoC(4, 4)


def _req(seed=0, budget=120, **kw):
    model, noc = _model_noc()
    kw.setdefault("method", "simulated_annealing")
    kw.setdefault("schedule", "none")
    return DeployRequest.from_call(model, noc, seed=seed, budget=budget, **kw)


# ---------------------------------------------------------------------------
# DeployRequest: round-trip, keys
# ---------------------------------------------------------------------------

def test_request_json_roundtrip_and_key_stability():
    req = _req(seed=3, method_kw={"t0": 0.1, "init": np.arange(16)})
    blob = json.dumps(req.to_json())
    back = DeployRequest.from_json(json.loads(blob))
    assert back == req
    assert back.cache_key() == req.cache_key()
    assert back.warm_key() == req.warm_key()
    # unknown / missing fields are hard errors, not silent drops
    d = json.loads(blob)
    d["bogus"] = 1
    with pytest.raises(ValueError, match="bogus"):
        DeployRequest.from_json(d)


def test_cache_key_stable_across_processes():
    script = (
        "import sys; sys.path.insert(0, 'src')\n"
        "from repro.core import NoC\n"
        "from repro.deploy import DeployRequest\n"
        "from repro.snn import spike_resnet18\n"
        "req = DeployRequest.from_call(\n"
        "    spike_resnet18(n_classes=10, in_res=32, T=4), NoC(4, 4),\n"
        "    method='simulated_annealing', schedule='none',\n"
        "    seed=3, budget=120)\n"
        "print(req.cache_key())\n")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, check=True,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.stdout.strip() == _req(seed=3).cache_key()


def test_cache_key_sensitivity_and_warm_key_invariance():
    base = _req(seed=0)
    assert base.cache_key() != _req(seed=1).cache_key()
    assert base.cache_key() != _req(seed=0, budget=121).cache_key()
    assert base.cache_key() != _req(seed=0, objective="max_link").cache_key()
    # seed / budget / objective are *not* part of the logical graph: the
    # warm key stays put, so these are exactly the near-miss warm starts
    assert base.warm_key() == _req(seed=1).warm_key()
    assert base.warm_key() == _req(seed=0, objective="max_link").warm_key()
    # a different topology is a different graph: both keys move
    model, _ = _model_noc()
    other = DeployRequest.from_call(model, NoC(2, 8), seed=0, budget=120,
                                    method="simulated_annealing",
                                    schedule="none")
    assert other.cache_key() != base.cache_key()
    assert other.warm_key() != base.warm_key()


def test_degraded_topology_never_serves_healthy_plan():
    model, noc = _model_noc()
    faulty = degrade(noc, links=(0,))
    healthy = DeployRequest.from_call(model, noc, seed=0, budget=80,
                                      method="simulated_annealing",
                                      schedule="none")
    degraded = DeployRequest.from_call(model, faulty, seed=0, budget=80,
                                       method="simulated_annealing",
                                       schedule="none")
    assert healthy.cache_key() != degraded.cache_key()
    assert healthy.warm_key() != degraded.warm_key()
    # the reconstructed topology is degraded, not the healthy base
    rebuilt = topology_from_key(degraded.topology)
    assert rebuilt.cache_key() == faulty.cache_key()
    svc = PlacementService()
    first = svc.submit(healthy)
    assert first.status == "miss"
    resp = svc.submit(degraded)
    assert resp.status == "miss"           # not "hit": fault isolation
    assert resp.cache_key != first.cache_key


def test_topology_roundtrip():
    _, noc = _model_noc()
    for topo in (noc, degrade(noc, links=(3,), nodes=(5,))):
        req = DeployRequest.from_call(_model_noc()[0], topo, seed=0,
                                      budget=50, schedule="none",
                                      method="random_search")
        assert topology_from_key(req.topology).cache_key() == topo.cache_key()


# ---------------------------------------------------------------------------
# wrapper identity: deploy_model == execute_request(from_json(...))
# ---------------------------------------------------------------------------

def test_deploy_model_bit_identical_through_request_layer():
    model, noc = _model_noc()
    plan = deploy_model(model, noc, method="simulated_annealing", budget=150,
                        seed=5, schedule="none")
    req = DeployRequest.from_json(json.loads(json.dumps(
        _req(seed=5, budget=150).to_json())))
    plan2 = execute_request(req)
    np.testing.assert_array_equal(plan.placement.placement,
                                  plan2.placement.placement)
    assert plan.placement.objective_cost == plan2.placement.objective_cost


def test_instantiate_plan_reevaluates_fixed_placement():
    req = _req(seed=2, budget=80)
    plan = execute_request(req)
    again = instantiate_plan(req, plan.placement.placement)
    np.testing.assert_array_equal(plan.placement.placement,
                                  again.placement.placement)
    assert again.placement.objective_cost == plan.placement.objective_cost
    with pytest.raises(ValueError, match="placement"):
        instantiate_plan(req, [0, 1, 2])    # wrong length


def test_unencodable_call_falls_back_to_direct_engine():
    # a migration-bearing objective cannot live in a canonical request;
    # deploy_model must still work (direct engine path, no caching layer)
    from repro.deploy import as_objective
    from repro.deploy.runtime import MigrationSpec, with_migration

    model, noc = _model_noc()
    req_probe = _req(seed=0, budget=50)
    graph_n = len(execute_request(req_probe).placement.placement)
    obj = with_migration(as_objective("comm_cost"),
                         MigrationSpec(old_placement=tuple(range(graph_n)),
                                       state_bytes=(1.0,) * graph_n),
                         weight=0.5)
    with pytest.raises(RequestEncodeError):
        DeployRequest.from_call(model, noc, objective=obj, budget=50,
                                method="simulated_annealing", schedule="none")
    plan = deploy_model(model, noc, objective=obj, budget=50, seed=0,
                        method="simulated_annealing", schedule="none")
    assert plan.placement.objective_cost > 0


# ---------------------------------------------------------------------------
# method-kwarg validation (no more silently swallowed typos)
# ---------------------------------------------------------------------------

def test_unknown_method_kwarg_raises_with_accepted_list():
    g, noc = random_dag(12, seed=3), NoC(4, 4)
    with pytest.raises(TypeError, match=r"t_zero.*accepted.*t0"):
        optimize_placement(g, noc, method="simulated_annealing", t_zero=0.5)
    with pytest.raises(TypeError, match="bogus_kw"):
        optimize_placement(g, noc, method="random_search", bogus_kw=1)
    model, nnoc = _model_noc()
    with pytest.raises(TypeError, match="bogus_kw"):
        deploy_model(model, nnoc, method="simulated_annealing",
                     schedule="none", budget=10, bogus_kw=1)
    # valid tuning kwargs still pass through
    res = optimize_placement(g, noc, method="simulated_annealing",
                             iters=50, t0=0.1, seed=0)
    assert res.comm_cost > 0


def test_method_kwargs_table():
    assert "t0" in method_kwargs("simulated_annealing")
    assert "init" in method_kwargs("random_search")
    assert "coarsen_to" in method_kwargs("multilevel")
    # multilevel accepts its coarse method's kwargs too
    assert "t0" in method_kwargs("multilevel",
                                 coarse_method="simulated_annealing")
    with pytest.raises(ValueError, match="unknown method"):
        method_kwargs("annealing_simulated")
    validate_method_kw("simulated_annealing", {"t0": 0.1})  # no raise


def test_cfg_plus_loose_kwargs_rejected():
    g, noc = random_dag(10, seed=1), NoC(4, 4)
    with pytest.raises(TypeError, match="both cfg=.*loose"):
        optimize_placement(g, noc, method="ppo",
                           cfg=PPOConfig(iterations=1), batch_size=8)


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------

def test_plan_cache_hit_miss_warm_evict_save_load(tmp_path):
    r0, r1 = _req(seed=0, budget=60), _req(seed=1, budget=60)
    cache = PlanCache()
    plan0 = execute_request(r0)
    cache.put(r0, plan0)
    assert r0.cache_key() in cache and r1.cache_key() not in cache
    assert cache.get(r0.cache_key())["objective_cost"] == \
        plan0.placement.objective_cost
    donor = cache.find_warm(r1)
    assert donor is not None and donor["cache_key"] == r0.cache_key()
    assert cache.find_warm(r0) is None      # exact key is never its own donor

    path = tmp_path / "plans.json"
    cache.save(str(path))
    loaded = PlanCache.load(str(path))
    entry = loaded.get(r0.cache_key())
    assert entry is not None
    assert entry["placement"] == list(map(int, plan0.placement.placement))

    small = PlanCache(max_entries=2)
    for s in (0, 1, 2):
        small.put(_req(seed=s, budget=60), plan0)
    assert len(small) == 2
    assert _req(seed=0, budget=60).cache_key() not in small   # LRU evicted


# ---------------------------------------------------------------------------
# PlacementService: hit / warm / batch
# ---------------------------------------------------------------------------

def test_service_miss_hit_warm_flow():
    svc = PlacementService()
    r0 = _req(seed=0, budget=200)
    miss = svc.submit(r0)
    assert miss.status == "miss"
    hit = svc.submit(r0)
    assert hit.status == "hit"
    assert hit.placement == miss.placement
    assert hit.objective_cost == miss.objective_cost
    warm = svc.submit(_req(seed=9, budget=200))
    assert warm.status == "warm"
    assert warm.warm_from == miss.cache_key
    # init-seeded searches keep the best seen: never worse than the donor
    assert warm.objective_cost <= miss.objective_cost
    c = svc.stats()["counters"]
    assert c["service.requests"] == 3
    assert c["service.hits"] == 1 and c["service.misses"] == 1
    assert c["service.warm_starts"] == 1
    # responses survive a dict round trip (the HTTP wire format); saved
    # answers carrying fields the response no longer has still load
    assert DeployResponse.from_dict(warm.to_dict()) == warm
    assert DeployResponse.from_dict({**warm.to_dict(), "fused": False}) == warm


def test_service_cross_objective_warm_start():
    svc = PlacementService()
    donor = svc.submit(_req(seed=0, budget=200))
    other = svc.submit(_req(seed=0, budget=200, objective="max_link"))
    assert other.status == "warm" and other.warm_from == donor.cache_key


@pytest.mark.parametrize("method, seeds, budget", [
    ("simulated_annealing", (11, 12, 13), 150),
    ("random_search", (1, 2), 100),
])
def test_batch_rows_bit_identical_to_solo_cold(method, seeds, budget):
    reqs = [_req(seed=s, budget=budget, method=method) for s in seeds]
    svc = PlacementService()
    resps = svc.submit_batch(reqs)
    assert all(r.status == "miss" for r in resps)
    for req, resp in zip(reqs, resps):
        solo = execute_request(req)
        np.testing.assert_array_equal(np.asarray(resp.placement),
                                      solo.placement.placement)
        assert resp.objective_cost == solo.placement.objective_cost
    c = svc.stats()["counters"]
    assert c["service.misses"] == len(seeds)


def test_batch_hits_in_batch_duplicates():
    r = _req(seed=4, budget=100)
    svc = PlacementService()
    a, b = svc.submit_batch([r, r])
    assert a.placement == b.placement
    assert {a.status, b.status} == {"miss", "hit"}


def test_batch_rows_warm_only_from_donors_before_the_batch():
    """Rows sharing a warm key each warm-start from the donor the cache held
    when the batch arrived, never from one another; with no donor both are
    cold, and equal their solo searches."""
    a, b = _req(seed=31, budget=150), _req(seed=32, budget=150)
    svc = PlacementService()
    donor = svc.submit(_req(seed=30, budget=150))
    ra, rb = svc.submit_batch([a, b])
    assert (ra.status, rb.status) == ("warm", "warm")
    assert ra.warm_from == rb.warm_from == donor.cache_key
    assert max(ra.objective_cost, rb.objective_cost) <= donor.objective_cost

    ra, rb = PlacementService().submit_batch([a, b])
    assert (ra.status, rb.status) == ("miss", "miss")
    for req, resp in ((a, ra), (b, rb)):
        solo = execute_request(req)
        np.testing.assert_array_equal(np.asarray(resp.placement),
                                      solo.placement.placement)


def test_cache_survives_restart(tmp_path):
    path = tmp_path / "plans.json"
    r = _req(seed=0, budget=120)
    svc = PlacementService()
    cold = svc.submit(r)
    svc.cache.save(str(path))
    svc2 = PlacementService(cache=PlanCache.load(str(path)))
    warmed = svc2.submit(r)
    assert warmed.status == "hit"
    assert warmed.placement == cold.placement


# ---------------------------------------------------------------------------
# runtime integration: run_scenario(plan=...)
# ---------------------------------------------------------------------------

def test_run_scenario_accepts_prebuilt_plan():
    model, noc = _model_noc()
    kw = dict(method="simulated_annealing", budget=48, seed=0,
              migration_weight=0.0)
    plan = deploy_model(model, noc, schedule="none", **{k: v for k, v in
                        kw.items() if k != "migration_weight"})
    direct = run_scenario(model, noc, "steps=2", schedule="none", **kw)
    via_plan = run_scenario(model, noc, "steps=2", plan=plan,
                            schedule="none", **kw)
    assert direct.to_dict() == via_plan.to_dict()


# ---------------------------------------------------------------------------
# MicroBatchQueue
# ---------------------------------------------------------------------------

def test_microbatch_queue_batches_and_propagates_errors():
    seen = []

    def process(items):
        seen.append(list(items))
        return [x * 2 for x in items]

    q = MicroBatchQueue(process, max_batch=4, window_s=0.05)
    out, threads = [None] * 4, []
    for i in range(4):
        def run(i=i):
            out[i] = q.submit(i, timeout=10)
        threads.append(threading.Thread(target=run))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert out == [0, 2, 4, 6]
    assert max(len(b) for b in seen) > 1    # at least one fused batch

    def boom(items):
        raise RuntimeError("kaput")

    qb = MicroBatchQueue(boom, window_s=0.0)
    with pytest.raises(RuntimeError, match="kaput"):
        qb.submit(1, timeout=10)
    qb.close()
    with pytest.raises(RuntimeError, match="closed"):
        qb.submit(2)
    q.close()


def test_microbatch_queue_lone_item_waits_the_window():
    """With nothing else queued the batch never fills: the item waits the
    whole window, and submit_timed says so."""
    q = MicroBatchQueue(lambda items: [x + 1 for x in items], max_batch=8,
                        window_s=0.03)
    try:
        result, wait_s = q.submit_timed(1, timeout=10)
        assert result == 2
        assert 0.03 <= wait_s < 5.0
        assert q.submit(5, timeout=10) == 6
    finally:
        q.close()


def test_microbatch_queue_result_count_mismatch():
    q = MicroBatchQueue(lambda items: [1, 2, 3], window_s=0.0)
    with pytest.raises(RuntimeError, match="returned 3 results"):
        q.submit("x", timeout=10)
    q.close()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------

def test_http_server_roundtrip():
    svc = PlacementService()
    server, queue = make_server(svc, port=0, window_s=0.005)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        req = _req(seed=0, budget=120)
        miss = request_over_http(url, req)
        assert miss.status == "miss"
        hit = request_over_http(url, req)
        assert hit.status == "hit"
        assert hit.placement == miss.placement

        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            assert json.loads(r.read()) == {"ok": True}
        with urllib.request.urlopen(url + "/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["cache_entries"] == 1
        assert stats["counters"]["service.hits"] == 1
        assert stats["latency"]["service.latency_s"]["count"] == 2

        plan_entry = fetch_plan(f"{url}/plan/{miss.cache_key}")
        assert plan_entry["placement"] == miss.placement
        # a fetched plan re-materializes to the same deployment
        live = instantiate_plan(DeployRequest.from_json(plan_entry["request"]),
                                plan_entry["placement"])
        assert live.placement.objective_cost == miss.objective_cost

        bad = urllib.request.Request(url + "/deploy", data=b"{not json",
                                     headers={"Content-Type":
                                              "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/plan/deadbeef", timeout=30)
        assert ei.value.code == 404
    finally:
        server.shutdown()
        server.server_close()
        queue.close()


def test_http_concurrent_posts_micro_batch():
    """Three concurrent POSTs all get answers through the queue. Where the
    batching windows fall decides which rows are cold and which warm-start
    from an answer made in an earlier batch, so each status is held to its
    own rule: a miss equals its solo cold search, a warm answer names an
    earlier answer as its donor and costs no more than it."""
    svc = PlacementService()
    server, queue = make_server(svc, port=0, window_s=0.1, max_batch=8)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        reqs = [_req(seed=20 + i, budget=120) for i in range(3)]
        resps, threads = [None] * 3, []
        start = threading.Barrier(3)
        for i in range(3):
            def run(i=i):
                start.wait()
                resps[i] = request_over_http(url, reqs[i])
            threads.append(threading.Thread(target=run))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        assert all(r is not None for r in resps)
        by_key = {r.cache_key: r for r in resps}
        for req, resp in zip(reqs, resps):
            assert resp.status in ("miss", "warm")
            if resp.status == "miss":
                solo = execute_request(req)
                np.testing.assert_array_equal(np.asarray(resp.placement),
                                              solo.placement.placement)
            else:
                assert resp.warm_from in by_key
                assert resp.warm_from != resp.cache_key
                assert (resp.objective_cost
                        <= by_key[resp.warm_from].objective_cost)
        assert svc.stats()["counters"]["service.requests"] == 3
    finally:
        server.shutdown()
        server.server_close()
        queue.close()


def test_http_answer_carries_queue_wait():
    svc = PlacementService()
    server, queue = make_server(svc, port=0, window_s=0.02)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        resp = request_over_http(url, _req(seed=3, budget=60))
        assert 0.02 <= resp.queue_s < resp.latency_s + 5.0
        assert resp.report["search_phases_s"] == {}   # host SA times none
        assert resp.report["compiles"] >= 0
    finally:
        server.shutdown()
        server.server_close()
        queue.close()
    # in-process answers waited in no queue
    assert svc.submit(_req(seed=3, budget=60)).queue_s == 0.0


def test_service_recorder_stays_bounded():
    """The service recorder keeps counters and histograms only: its event
    list does not grow with the requests it serves."""
    svc = PlacementService()
    svc.submit_batch([_req(seed=s, budget=60, method="random_search")
                      for s in (5, 6)])                   # two misses
    svc.submit(_req(seed=0, budget=60))                   # warm
    svc.submit(_req(seed=0, budget=60))                   # hit
    svc.submit(_req(seed=1, budget=60))                   # warm
    assert svc.recorder.events == []
    c = svc.stats()["counters"]
    assert c["service.requests"] == 5 and c["service.misses"] == 2
    assert c["service.hits"] == 1 and c["service.warm_starts"] == 2


def test_compiles_counted_per_request():
    """A device-SA request with an unseen seed compiles (the seed is a
    static argument of the chain keys); its exact repeat is a hit and
    compiles nothing. ``/stats`` counts every compile once."""
    svc = PlacementService()
    req = _req(seed=918273, budget=40, method="simulated_annealing",
               backend="device", method_kw={"restarts": 2})
    miss = svc.submit(req)
    hit = svc.submit(req)
    assert miss.status == "miss" and hit.status == "hit"
    assert miss.report["compiles"] >= 1
    assert hit.report["compiles"] == 0
    assert tuple(miss.report["search_phases_s"]) == (
        "sa.prepare", "sa.run", "sa.select")
    assert svc.stats()["counters"]["service.compiles"] == \
        miss.report["compiles"]


def test_search_counters_reach_stats():
    """A cold PPO search's work counts reach the service's ``/stats``
    counters: two compiled sample programs an iteration."""
    svc = PlacementService()
    req = _req(seed=0, budget=3, method="ppo",
               method_kw={"batch_size": 8, "ppo_epochs": 2})
    assert svc.submit(req).status == "miss"
    assert svc.submit(req).status == "hit"
    assert svc.stats()["counters"]["ppo.sample.programs"] == 2 * 3


def test_search_phases_are_the_requests_own():
    """An answer's ``search_phases_s`` times its own search: a miss's
    phases, a warm start's summed over its attempts, none for a hit. The
    cached report stores none, so a hit cannot repeat another's."""
    svc = PlacementService()
    kw = dict(budget=40, method="simulated_annealing", backend="device",
              method_kw={"restarts": 2})
    miss = svc.submit(_req(seed=818273, **kw))
    hit = svc.submit(_req(seed=818273, **kw))
    warm = svc.submit(_req(seed=818274, **kw))
    assert (miss.status, hit.status, warm.status) == ("miss", "hit", "warm")
    phases = ("sa.prepare", "sa.run", "sa.select")
    assert tuple(miss.report["search_phases_s"]) == phases
    assert hit.report["search_phases_s"] == {}
    assert tuple(warm.report["search_phases_s"]) == phases
    assert 0.0 < sum(warm.report["search_phases_s"].values()) \
        <= warm.latency_s
    assert all("search_phases_s" not in e["report"]
               for e in svc.cache.entries())


# ---------------------------------------------------------------------------
# a branched model: Spikformer through the request layer and the service
# ---------------------------------------------------------------------------

def _spikformer_req(**kw):
    from repro.core.topology import parse_topology
    from repro.snn import spikformer

    model = spikformer(depth=2, dim=64, heads=4, mlp_ratio=4, n_classes=10,
                       in_res=32, in_ch=3, T=4, patch=4)
    kw.setdefault("method", "simulated_annealing")
    kw.setdefault("schedule", "none")
    return DeployRequest.from_call(model, parse_topology("mesh:4x8"),
                                   partition_strategy="balanced", **kw)


def test_spikformer_request_roundtrip_and_key():
    req = _spikformer_req(budget=40, backend="device",
                          method_kw={"restarts": 2})
    back = DeployRequest.from_json(json.loads(json.dumps(req.to_json())))
    assert back == req and back.cache_key() == req.cache_key()
    assert back.materialize_model() == req.materialize_model()
    assert _spikformer_req(budget=40, backend="device",
                           method_kw={"restarts": 2}).cache_key() \
        == req.cache_key()
    assert _spikformer_req(budget=41, backend="device",
                           method_kw={"restarts": 2}).cache_key() \
        != req.cache_key()


def test_service_serves_spikformer_with_device_sa():
    svc = PlacementService()
    req = _spikformer_req(budget=40, backend="device",
                          method_kw={"restarts": 2})
    resp = svc.submit(req)
    assert resp.status == "miss"
    p = np.asarray(resp.placement)
    assert p.shape == (32,) and len(set(p.tolist())) == 32
    assert p.min() >= 0 and p.max() < 32
    plan = instantiate_plan(req, p)
    src, dst, _ = plan.graph.edge_arrays()
    deg = np.bincount(np.concatenate([src, dst])).max()
    stats = resp.report["graph"]
    assert stats == {"nodes": 32, "edges": len(src),
                     "branch_edges": stats["branch_edges"],
                     "max_degree": int(deg)}
    assert stats["branch_edges"] > 0
    assert resp.comm_cost == pytest.approx(
        plan.noc.evaluate(plan.graph, p).comm_cost, rel=1e-12)
    c = svc.stats()["counters"]
    assert c["deploy.graphs"] == 1
    assert (c["deploy.graph.nodes"], c["deploy.graph.edges"],
            c["deploy.graph.branch_edges"]) == (
        32, len(src), stats["branch_edges"])
    assert svc.recorder.events == []
