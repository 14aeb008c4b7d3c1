"""Benchmark: device-resident search (`repro.core.placement.device_search`).

Pins the PR's headline at the ``BENCH_deploy_e2e`` shape (S-ResNet18 sliced
to the 32-core grid, budget 4000): one-dispatch scanned SA vs the host
``backend="batch"`` sequential SA, the restarts-vs-quality curve (vmapped
parallel chains — 64 chains must beat the single chain at well under 64x its
wall time), device GA vs host genetic, and the O(degree) delta-cost parity
bits (numpy exact on integer volumes; Pallas kernel vs numpy in float32).

Timings are machine-dependent so the regression gate never compares them —
it gates the derived *booleans* (``speedup_ok``, ``restarts_improve_ok``,
``restarts_wall_ok``, parity bits, recorder identity) plus the device best
costs at a wide jax band. ``--smoke`` runs a seconds-scale subset with a
conservative speedup threshold so noisy CI runners don't flake.

Emits ``results/BENCH_device_search.json`` and run.py CSV rows.
"""
from __future__ import annotations

import argparse
import os

import numpy as np

from .common import (bench_percentiles, counter_record, make_noc,
                     model_graph, write_record, write_trace)

from repro.core.noc_batch import (build_incident_tables, delta_comm_cost,
                                  evaluate_batch)  # noqa: E402
from repro.core.placement import optimize_placement  # noqa: E402
from repro.core.placement.device_search import (  # noqa: E402
    genetic_device, simulated_annealing_device)
from repro.obs import Recorder  # noqa: E402

BUDGET = 4000                 # matches the deploy_e2e SA budget
# full runs must hold the PR's >=10x headline; smoke gates a conservative
# floor so a loaded CI runner doesn't flake the gate
SPEEDUP_FLOOR = {"full": 10.0, "smoke": 4.0}
WALL_RATIO_CEILING = 8.0      # max-restarts wall time vs single chain


def _comm(noc, graph, placement) -> float:
    return float(evaluate_batch(noc, graph,
                                np.asarray(placement)[None]).comm_cost[0])


def _delta_parity(noc, graph, swaps: int = 200) -> dict:
    """Numpy O(degree) delta vs full(after) - full(before) over a random
    swap stream, plus the Pallas kernel vs the same numpy reference."""
    from repro.kernels.delta_cost import delta_cost_pallas, incident_keys
    tbl = build_incident_tables(graph)
    rng = np.random.default_rng(0)
    slots = rng.permutation(noc.n_cores)
    max_err = 0.0
    for _ in range(swaps):
        i, j = (int(x) for x in rng.integers(0, slots.size, 2))
        d = delta_comm_cost(noc, graph, slots, i, j, tbl)
        before = _comm(noc, graph, slots[:graph.n])
        slots[i], slots[j] = slots[j], slots[i]
        max_err = max(max_err, abs(d - (_comm(noc, graph, slots[:graph.n])
                                        - before)))

    # Pallas row-select kernel vs the same numpy reference on random swaps
    C, R = noc.n_cores, 4
    hops = np.asarray(
        [[noc.hops(s, t) for t in range(C)] for s in range(C)],
        dtype=np.float32)
    slots_r = np.stack([rng.permutation(C) for _ in range(R)])
    i_r, j_r = rng.integers(0, C, R), rng.integers(0, C, R)
    ref = np.array([delta_comm_cost(noc, graph, slots_r[k], int(i_r[k]),
                                    int(j_r[k]), tbl) for k in range(R)])
    out = np.asarray(delta_cost_pallas(
        slots_r, i_r, j_r, incident_keys(tbl.other, tbl.is_src), tbl.vol,
        hops, hops.T, n=graph.n, interpret=True))
    pallas_err = float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1.0))
    return {"numpy_max_abs_err": float(max_err),
            "numpy_exact": max_err == 0.0,
            "pallas_max_rel_err": pallas_err,
            "pallas_ok": pallas_err <= 1e-5}


def device_search(smoke: bool = False, json_path: str | None = None):
    mode = "smoke" if smoke else "full"
    noc = make_noc(32)
    graph, _ = model_graph("S-ResNet18", 32)
    repeats = 3 if smoke else 10
    restart_grid = (1, 16) if smoke else (1, 4, 16, 64)

    record = {"smoke": smoke, "shape": {"model": "S-ResNet18", "n_cores": 32,
                                        "n_nodes": graph.n, "budget": BUDGET}}
    rows_out = []

    # ---- delta-cost parity bits (seed-deterministic, gated exactly) -----
    record["delta_parity"] = _delta_parity(noc, graph,
                                           swaps=60 if smoke else 200)
    rows_out.append((
        "device_search.delta_parity", 0.0,
        f"numpy_exact={record['delta_parity']['numpy_exact']} "
        f"pallas_rel_err={record['delta_parity']['pallas_max_rel_err']:.1e}"))

    # ---- headline: host sequential SA vs one-dispatch device SA ---------
    def host_sa():
        return optimize_placement(graph, noc, method="simulated_annealing",
                                  seed=0, budget=BUDGET)

    def device_sa(restarts=1):
        return optimize_placement(graph, noc, method="simulated_annealing",
                                  backend="device", seed=0, budget=BUDGET,
                                  restarts=restarts)

    host_res = host_sa()
    host_lat = bench_percentiles(host_sa, repeats=repeats, warmup=0)
    dev_res = device_sa()
    dev_lat = bench_percentiles(device_sa, repeats=repeats, warmup=1)
    speedup = host_lat["p50"] / max(dev_lat["p50"], 1e-12)
    record["headline"] = {
        "host_p50_s": host_lat["p50"], "host_p99_s": host_lat["p99"],
        "device_p50_s": dev_lat["p50"], "device_p99_s": dev_lat["p99"],
        "speedup_p50": speedup,
        "speedup_floor": SPEEDUP_FLOOR[mode],
        "speedup_ok": speedup >= SPEEDUP_FLOOR[mode],
        "host_comm_cost": host_res.comm_cost,
        "device_comm_cost": dev_res.comm_cost,
        # float32 device arithmetic vs float64 host on the same schedule:
        # the search qualities must stay comparable even though the RNG
        # streams (numpy vs threefry) necessarily differ
        "cost_ratio_device_over_host": dev_res.comm_cost / host_res.comm_cost,
    }
    rows_out.append((
        "device_search.headline", dev_lat["p50"] * 1e6,
        f"host_p50={host_lat['p50']*1e3:.1f}ms "
        f"device_p50={dev_lat['p50']*1e3:.1f}ms speedup=x{speedup:.1f} "
        f"(floor x{SPEEDUP_FLOOR[mode]:g}, ok={speedup >= SPEEDUP_FLOOR[mode]}) "
        f"cost host={host_res.comm_cost:.3e} dev={dev_res.comm_cost:.3e}"))

    # ---- restarts-vs-quality curve (vmapped parallel chains) ------------
    curve = []
    for r in restart_grid:
        res = device_sa(restarts=r)
        lat = bench_percentiles(lambda r=r: device_sa(restarts=r),
                                repeats=repeats, warmup=1)
        curve.append({"restarts": r, "best_cost": res.comm_cost,
                      "p50_s": lat["p50"],
                      "wall_ratio_vs_r1": lat["p50"] / max(
                          curve[0]["p50_s"] if curve else lat["p50"], 1e-12)})
        rows_out.append((
            f"device_search.restarts_{r}", lat["p50"] * 1e6,
            f"best={res.comm_cost:.3e} p50={lat['p50']*1e3:.1f}ms "
            f"ratio_vs_r1=x{curve[-1]['wall_ratio_vs_r1']:.2f}"))
    rmax = curve[-1]
    record["restarts"] = {
        "grid": list(restart_grid), "curve": curve,
        # chain 0's stream is independent of the chain count, so the max-R
        # best can only match or beat the single chain — a correctness bit
        "restarts_improve_ok": rmax["best_cost"] <= curve[0]["best_cost"],
        # R chains in one dispatch must cost far less than R sequential runs
        "restarts_wall_ok": rmax["wall_ratio_vs_r1"] < WALL_RATIO_CEILING,
    }

    # ---- device GA vs host genetic --------------------------------------
    gens, pop = (12, 16) if smoke else (80, 64)

    def host_ga():
        return optimize_placement(graph, noc, method="genetic", seed=0,
                                  generations=gens, pop_size=pop)

    def device_ga():
        return optimize_placement(graph, noc, method="genetic",
                                  backend="device", seed=0,
                                  generations=gens, pop_size=pop)

    hg, dg = host_ga(), device_ga()
    hg_lat = bench_percentiles(host_ga, repeats=repeats, warmup=0)
    dg_lat = bench_percentiles(device_ga, repeats=repeats, warmup=1)
    record["ga"] = {
        "generations": gens, "pop_size": pop,
        "host_p50_s": hg_lat["p50"], "device_p50_s": dg_lat["p50"],
        "speedup_p50": hg_lat["p50"] / max(dg_lat["p50"], 1e-12),
        "host_comm_cost": hg.comm_cost, "device_comm_cost": dg.comm_cost,
    }
    rows_out.append((
        "device_search.ga", dg_lat["p50"] * 1e6,
        f"host_p50={hg_lat['p50']*1e3:.1f}ms "
        f"device_p50={dg_lat['p50']*1e3:.1f}ms "
        f"speedup=x{record['ga']['speedup_p50']:.1f} "
        f"cost host={hg.comm_cost:.3e} dev={dg.comm_cost:.3e}"))

    # ---- recorder identity + trace --------------------------------------
    # the sa.iter/ga.gen streams are replayed post-dispatch from scan
    # outputs that are computed either way, so attaching a recorder must
    # leave the returned placements bit-identical
    recorder = Recorder()
    pa = simulated_annealing_device(graph, noc, iters=BUDGET, seed=0,
                                    restarts=4, recorder=recorder)
    pb = simulated_annealing_device(graph, noc, iters=BUDGET, seed=0,
                                    restarts=4)
    ga_a = genetic_device(graph, noc, generations=gens, pop_size=pop, seed=0,
                          recorder=recorder)
    ga_b = genetic_device(graph, noc, generations=gens, pop_size=pop, seed=0)
    identical = bool(np.array_equal(pa, pb) and np.array_equal(ga_a, ga_b))
    record["recorder_identity"] = {"results_identical": identical}
    record["counters"] = counter_record(recorder)
    rows_out.append(("device_search.recorder_identity", 0.0,
                     f"results_identical={identical} "
                     f"sa_accepted={record['counters'].get('sa_accepted', 0)}"))

    out = write_record(record, json_path, smoke, "BENCH_device_search.json")
    if out:
        rows_out.append(("device_search.json", 0.0,
                         f"wrote {os.path.relpath(out)}"))
    tr = write_trace(recorder, "device_search", json_path, smoke)
    if tr:
        rows_out.append(("device_search.trace", 0.0,
                         f"wrote {os.path.relpath(tr)}"))
    return rows_out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale subset for CI")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the benchmark record to PATH")
    args = ap.parse_args()
    for name, us, derived in device_search(smoke=args.smoke,
                                           json_path=args.json):
        print(f"{name},{us:.1f},{derived}")
