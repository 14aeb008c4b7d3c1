"""Benchmark: the end-to-end deployment engine (`repro.deploy`).

Runs ``deploy_model`` — profile -> partition -> place -> schedule — for the
paper's models on the 32-core grid, across placement methods and objectives,
and records per-stage wall times plus the deployed metrics. Also measures the
multi-objective payoff: simulated annealing under the ``max_link`` objective
vs the comm-cost optimum (hotspot peak reduction), and an energy-weighted
combo. Emits ``results/BENCH_deploy_e2e.json`` and run.py CSV rows;
``--smoke`` runs a seconds-scale subset (no JSON) for CI.
"""
from __future__ import annotations

import argparse
import os

import numpy as np

from .common import (SPIKE_MODELS, bench_percentiles, counter_record,
                     make_noc, model_graph, write_record, write_trace)

from repro.core.placement import optimize_placement  # noqa: E402
from repro.core.placement.ppo import PPOConfig  # noqa: E402
from repro.deploy import deploy_model  # noqa: E402
from repro.obs import Recorder  # noqa: E402

ENERGY_COMBO = {"comm_cost": 1.0, "energy": 2e9}


def _case(model_name, model_cfg, noc, method, objective, budget=None,
          recorder=None, **kw):
    # **kw may itself carry a cfg= (e.g. a PPOConfig) for optimize_placement
    plan = deploy_model(model_cfg, noc, method=method, objective=objective,
                        schedule="fpdeep", n_units=8, budget=budget,
                        recorder=recorder, **kw)
    rep = plan.report()
    rep["model"] = model_name
    # "graph" runs inside "partition"
    total = sum(v for k, v in rep["stage_times_s"].items() if k != "graph")
    rep["total_s"] = total
    return plan, rep


def deploy_e2e(smoke: bool = False, json_path: str | None = None):
    if smoke:
        models = ["S-ResNet18"]
        methods = [("zigzag", {}), ("random_search", {"budget": 64})]
        sa_budget = 200
    else:
        models = ["S-VGG16", "S-ResNet18"]
        methods = [
            ("zigzag", {}),
            ("sigmate", {}),
            ("random_search", {"budget": 1500}),
            ("simulated_annealing", {"budget": 4000}),
            ("ppo", {"cfg": PPOConfig(batch_size=48, iterations=15,
                                      ppo_epochs=4, seed=0)}),
        ]
        sa_budget = 4000
    noc = make_noc(32)

    # one recorder across the whole suite: every deployment's stage spans and
    # search trajectory land in one TRACE_deploy_e2e.jsonl artifact, and the
    # work counters (deployments, scorer dispatches/evals) are
    # seed-deterministic — check_regression gates them
    recorder = Recorder()
    record = {"smoke": smoke, "cases": [], "objective_demo": {}}
    rows_out = []
    for model_name in models:
        cfg = SPIKE_MODELS[model_name]()
        for method, kw in methods:
            _, rep = _case(model_name, cfg, noc, method, "comm_cost",
                           recorder=recorder, **kw)
            record["cases"].append(rep)
            st = rep["stage_times_s"]
            rows_out.append((
                f"deploy_e2e.{model_name}.{method}",
                rep["total_s"] * 1e6,
                f"comm={rep['placement']['comm_cost']:.3e} "
                f"profile={st['profile']*1e3:.1f}ms "
                f"partition={st['partition']*1e3:.1f}ms "
                f"place={st['place']:.2f}s "
                f"schedule={st['schedule']*1e3:.1f}ms"))

    # ---- multi-objective payoff (paper Fig 7 hotspot story) -------------
    # same searcher + budget + seed, three objectives; the hotspot-aware
    # optimum must flatten the peak link the comm-cost optimum leaves hot
    demo_model = models[0]
    cfg = SPIKE_MODELS[demo_model]()
    by_obj = {}
    for objective in ("comm_cost", "max_link", ENERGY_COMBO):
        plan, rep = _case(demo_model, cfg, noc, "simulated_annealing",
                          objective, budget=sa_budget, recorder=recorder)
        key = rep["placement"]["objective"]
        by_obj[key] = (plan, rep)
        record["objective_demo"][key] = rep["placement"]
    comm = by_obj["comm_cost"][1]["placement"]
    ml = by_obj["max_link"][1]["placement"]
    reduction = comm["max_link"] / max(ml["max_link"], 1e-30)
    placements_differ = not np.array_equal(
        by_obj["comm_cost"][0].placement.placement,
        by_obj["max_link"][0].placement.placement)
    record["objective_demo"]["hotspot_peak_reduction"] = reduction
    record["objective_demo"]["placements_differ"] = placements_differ
    rows_out.append((
        f"deploy_e2e.objective_demo.{demo_model}", 0.0,
        f"max_link obj cuts peak link x{reduction:.2f} vs comm optimum "
        f"(placements_differ={placements_differ})"))

    # ---- placement latency distribution (p50/p99, not just the mean) ----
    # the `place` stage dominates sweep wall time; measure its distribution
    # for the host SA and the device-resident SA (single chain and a
    # 16-restart fan-out) at the suite's shape. recorder=None on purpose:
    # these extra runs must not move the suite's gated work counters.
    graph, _ = model_graph(demo_model, 32)
    repeats = 5 if smoke else 20
    lat = {}
    for label, okw in (
            ("sa_batch", {}),
            ("sa_device", {"backend": "device"}),
            ("sa_device_r16", {"backend": "device", "restarts": 16})):
        def place(okw=okw):
            optimize_placement(graph, noc, method="simulated_annealing",
                               seed=0, budget=sa_budget, **okw)
        lat[label] = bench_percentiles(place, repeats=repeats, warmup=1)
    record["placement_latency"] = lat
    rows_out.append((
        "deploy_e2e.placement_latency", lat["sa_batch"]["p50"] * 1e6,
        " ".join(f"{k}:p50={v['p50']*1e3:.1f}ms,p99={v['p99']*1e3:.1f}ms"
                 for k, v in lat.items())))

    record["counters"] = counter_record(recorder)
    rows_out.append(("deploy_e2e.counters", 0.0,
                     " ".join(f"{k}={v:g}"
                              for k, v in sorted(record["counters"].items()))))

    out = write_record(record, json_path, smoke, "BENCH_deploy_e2e.json")
    if out:
        rows_out.append(("deploy_e2e.json", 0.0,
                         f"wrote {os.path.relpath(out)}"))
    tr = write_trace(recorder, "deploy_e2e", json_path, smoke)
    if tr:
        rows_out.append(("deploy_e2e.trace", 0.0,
                         f"wrote {os.path.relpath(tr)}"))
    return rows_out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale subset for CI")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the benchmark record to PATH")
    args = ap.parse_args()
    for name, us, derived in deploy_e2e(smoke=args.smoke,
                                        json_path=args.json):
        print(f"{name},{us:.1f},{derived}")
