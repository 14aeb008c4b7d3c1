"""Benchmark: placement service (`repro.deploy.service`).

Pins the serving layer's headline claims on a 16-core fabric with the
S-ResNet18 deployment request:

* **cache hits** — repeating an identical :class:`DeployRequest` must be
  answered from the :class:`PlanCache` at >= 50x below the cold-search p50
  (the PR's acceptance floor), returning the bit-identical plan.
* **warm near-miss** — a request sharing the donor's ``warm_key`` (same
  model/topology/partition, different seed) warm-starts from the cached
  placement: final cost within 5% of the full cold search on that request,
  at <= 50% of its wall time.
* **batches** — k cold same-graph requests submitted as one batch find no
  donor in the cache, so every row must match its *solo cold*
  ``execute_request`` result bit-for-bit.
* **persistence** — a cache saved to JSON and reloaded in a fresh service
  still answers the original request as a hit.

Timings are machine-dependent so the regression gate never compares them —
it gates the derived booleans (``speedup_ok``, ``cost_ok``, ``time_ok``,
``results_match``, ``hit_after_reload``), an absolute ceiling on the hit
p50 (a hit is a hash + dict lookup; 50 ms of slack is three orders of
magnitude), the numpy-deterministic seeded costs at the tight band, and
the service's deterministic hit/miss/warm work counters.

Emits ``results/BENCH_service.json`` and run.py CSV rows.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

from .common import (CORE_FLOPS, HOP_LAT, LINK_BW, SPIKE_MODELS,
                     counter_record, percentiles, timed, write_record,
                     write_trace)

from repro.core import NoC  # noqa: E402
from repro.deploy import (DeployRequest, PlacementService,  # noqa: E402
                          PlanCache, execute_request)
from repro.obs import Recorder  # noqa: E402

# large enough that the search loop dominates the per-request fixed costs
# (profiling + partitioning + scorer build) — the warm wall-ratio band is
# only meaningful when the budget fraction is what drives the wall time
SA_BUDGET = {"smoke": 4000, "full": 12000}
COLD_REPEATS = {"smoke": 5, "full": 12}
HIT_REPEATS = {"smoke": 40, "full": 300}
WARM_REPEATS = {"smoke": 5, "full": 7}
SPEEDUP_FLOOR = 50.0          # acceptance: cached >= 50x faster than cold p50
WARM_COST_BAND = 1.05         # acceptance: warm cost <= 105% of cold cost
WARM_WALL_BAND = 0.5          # acceptance: warm wall <= 50% of cold wall
BATCH_ROWS = 4
NEAR_MISS_SEED = 777


def service(smoke: bool = False, json_path: str | None = None):
    mode = "smoke" if smoke else "full"
    budget = SA_BUDGET[mode]
    recorder = Recorder()
    record = {"smoke": smoke}
    rows_out = []

    noc = NoC(4, 4, torus=False, link_bw=LINK_BW, core_flops=CORE_FLOPS,
              hop_latency=HOP_LAT)
    cfg = SPIKE_MODELS["S-ResNet18"]()

    def make_req(seed: int) -> DeployRequest:
        return DeployRequest.from_call(
            cfg, noc, partition_strategy="balanced",
            method="simulated_annealing", objective="comm_cost",
            schedule="none", budget=budget, seed=seed)

    record["setup"] = {"n_cores": noc.n_cores, "model": "S-ResNet18",
                       "method": "simulated_annealing", "budget": budget,
                       "cache_key": make_req(0).cache_key()}

    # ---- cold: every request a genuine miss (fresh service each) ---------
    cold_lat, cold_cost = [], None
    for s in range(COLD_REPEATS[mode]):
        resp = PlacementService(recorder=recorder).submit(make_req(s))
        cold_lat.append(resp.latency_s)
        if s == 0:
            cold_cost = resp.objective_cost
    cold = percentiles(cold_lat)
    record["cold"] = {"n": len(cold_lat), "p50_s": cold["p50"],
                      "p99_s": cold["p99"], "objective_cost": cold_cost}
    rows_out.append(("service.cold", cold["p50"] * 1e6,
                     f"n={len(cold_lat)} p50={cold['p50']*1e3:.1f}ms "
                     f"p99={cold['p99']*1e3:.1f}ms cost={cold_cost:.3e}"))

    # ---- hits: one persistent service, identical request repeated --------
    svc = PlacementService(recorder=recorder)
    first = svc.submit(make_req(0))                       # populate: miss
    hits = [svc.submit(make_req(0)) for _ in range(HIT_REPEATS[mode])]
    all_hits = all(r.status == "hit" for r in hits)
    hit = percentiles([r.latency_s for r in hits])
    speedup = cold["p50"] / max(hit["p50"], 1e-12)
    record["hit"] = {
        "n": len(hits), "p50_s": hit["p50"], "p99_s": hit["p99"],
        "all_hits": all_hits,
        "matches_cold": bool(hits[-1].objective_cost == cold_cost
                             and first.status == "miss"),
        "objective_cost": hits[-1].objective_cost,
        "speedup_p50": speedup, "speedup_floor": SPEEDUP_FLOOR,
        "speedup_ok": speedup >= SPEEDUP_FLOOR,
    }
    rows_out.append(("service.hit", hit["p50"] * 1e6,
                     f"n={len(hits)} p50={hit['p50']*1e6:.0f}us "
                     f"p99={hit['p99']*1e6:.0f}us speedup=x{speedup:.0f} "
                     f"(floor x{SPEEDUP_FLOOR:g}) ok={all_hits and record['hit']['speedup_ok']}"))

    # ---- warm near-miss: same warm_key, new seed --------------------------
    # each repeat gets a fresh cache holding only the donor entry, so the
    # warm search always starts from the same donor (no self-feeding)
    donor_req = make_req(0)
    donor_plan = execute_request(donor_req)
    miss_req = make_req(NEAR_MISS_SEED)

    def run_warm():
        c = PlanCache()
        c.put(donor_req, donor_plan)
        return PlacementService(cache=c, recorder=recorder).submit(miss_req)

    warm_resps = [run_warm() for _ in range(WARM_REPEATS[mode])]
    warm = percentiles([r.latency_s for r in warm_resps])
    wr = warm_resps[0]
    cold_ref, cold_ref_lat = None, []
    for _ in range(WARM_REPEATS[mode]):
        cold_ref, us = timed(execute_request, miss_req)
        cold_ref_lat.append(us / 1e6)
    cold_ref_p50 = percentiles(cold_ref_lat)["p50"]
    cost_ratio = wr.objective_cost / cold_ref.placement.objective_cost
    # the CI-gated wall ratio compares best-of-N timings: min is robust to
    # transient load spikes that would skew a 3-sample p50 on ~25 ms runs,
    # while still measuring the same warm-vs-cold compute ratio (p50s are
    # recorded alongside for the latency report)
    wall_ratio = min(r.latency_s for r in warm_resps) / max(min(cold_ref_lat),
                                                            1e-12)
    record["warm"] = {
        "n": len(warm_resps),
        "status_warm": all(r.status == "warm" for r in warm_resps),
        "attempts": wr.attempts, "warm_from": wr.warm_from,
        "objective_cost": wr.objective_cost,
        "donor_cost": donor_plan.placement.objective_cost,
        "cold_cost": cold_ref.placement.objective_cost,
        "cost_ratio": cost_ratio, "cost_band": WARM_COST_BAND,
        "cost_ok": cost_ratio <= WARM_COST_BAND,
        "p50_s": warm["p50"], "cold_p50_s": cold_ref_p50,
        "wall_ratio": wall_ratio, "wall_band": WARM_WALL_BAND,
        "time_ok": wall_ratio <= WARM_WALL_BAND,
    }
    rows_out.append(("service.warm", warm["p50"] * 1e6,
                     f"attempts={wr.attempts} cost_ratio={cost_ratio:.3f} "
                     f"(band {WARM_COST_BAND:g}) wall_ratio={wall_ratio:.2f} "
                     f"(band {WARM_WALL_BAND:g}) "
                     f"ok={record['warm']['cost_ok'] and record['warm']['time_ok']}"))

    # ---- batch vs solo cold -----------------------------------------------
    batch_reqs = [make_req(100 + i) for i in range(BATCH_ROWS)]
    svc_b = PlacementService(recorder=recorder)
    t0 = time.perf_counter()
    batch = svc_b.submit_batch(batch_reqs)
    batch_wall = time.perf_counter() - t0
    serial_wall, match = 0.0, True
    for req, resp in zip(batch_reqs, batch):
        solo, us = timed(execute_request, req)
        serial_wall += us / 1e6
        match = match and bool(
            resp.status == "miss"
            and np.array_equal(np.asarray(resp.placement),
                               solo.placement.placement)
            and resp.objective_cost == solo.placement.objective_cost)
    record["batch"] = {
        "rows": BATCH_ROWS, "results_match": match,
        "batch_wall_s": batch_wall, "serial_wall_s": serial_wall,
        "costs": [r.objective_cost for r in batch],
    }
    rows_out.append(("service.batch", batch_wall / BATCH_ROWS * 1e6,
                     f"rows={BATCH_ROWS} batch={batch_wall:.2f}s "
                     f"serial={serial_wall:.2f}s bit_identical={match}"))

    # ---- persistence: save -> reload -> hit -------------------------------
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "plans.json")
        svc.cache.save(path)
        svc2 = PlacementService(cache=PlanCache.load(path), recorder=recorder)
        reloaded = svc2.submit(make_req(0))
    record["persistence"] = {
        "hit_after_reload": bool(reloaded.status == "hit"
                                 and reloaded.objective_cost == cold_cost),
    }
    rows_out.append(("service.persistence", reloaded.latency_s * 1e6,
                     f"hit_after_reload={record['persistence']['hit_after_reload']}"))

    record["counters"] = counter_record(recorder)
    record["latency"] = recorder.histogram_summaries()

    out = write_record(record, json_path, smoke, "BENCH_service.json")
    if out:
        rows_out.append(("service.json", 0.0, f"wrote {os.path.relpath(out)}"))
    tr = write_trace(recorder, "service", json_path, smoke)
    if tr:
        rows_out.append(("service.trace", 0.0, f"wrote {os.path.relpath(tr)}"))
    return rows_out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale subset for CI")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the benchmark record to PATH")
    args = ap.parse_args()
    for name, us, derived in service(smoke=args.smoke, json_path=args.json):
        print(f"{name},{us:.1f},{derived}")
