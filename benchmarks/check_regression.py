"""Benchmark-regression gate for CI (``python -m benchmarks.check_regression``).

Runs every benchmark suite's ``--smoke`` mode in-process, writes the fresh
records to ``--out-dir`` (uploaded as CI artifacts), and compares each
suite's *deterministic* headline metrics against the committed baselines
``results/BENCH_<suite>_smoke.json`` within a per-metric tolerance band.
Timings are never gated (CI runners are too noisy); what is gated is the
seeded search results, parity deviations, schedule makespans, and the
deterministic work counters from the suites' recorders (scorer dispatch /
evaluation counts — they count algorithmic work, not time) — the quantities
a code regression actually moves.

Exit status is non-zero if any metric leaves its band (or a suite crashes),
which fails the CI job. The bands are two-sided on purpose: an unexplained
*improvement* is also a drift worth looking at — if it is intentional,
regenerate the baselines with ``--update-baselines`` and commit them
alongside the change (the benchmark regression policy in the README).

Metric kinds:

* ``rtol``     — relative band around the committed baseline value;
* ``max_abs``  — absolute ceiling, no baseline needed (parity deviations);
* ``expect``   — exact expected value (parity booleans).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import traceback

from .common import RESULTS_DIR


@dataclasses.dataclass(frozen=True)
class Metric:
    path: str                    # dotted path into the record; ints index lists
    rtol: float | None = None
    max_abs: float | None = None
    expect: object = None
    optional: bool = False       # absent in the fresh record -> skipped

    def __post_init__(self):
        if sum(x is not None for x in (self.rtol, self.max_abs,
                                       self.expect)) != 1:
            raise ValueError(f"{self.path}: exactly one of rtol/max_abs/"
                             "expect must be set")


_MISSING = object()


def get_path(record, path: str):
    """Extract ``a.0.b`` style dotted paths (ints index into lists)."""
    cur = record
    for seg in path.split("."):
        if isinstance(cur, list):
            try:
                cur = cur[int(seg)]
            except (ValueError, IndexError):
                return _MISSING
        elif isinstance(cur, dict):
            if seg not in cur:
                return _MISSING
            cur = cur[seg]
        else:
            return _MISSING
    return cur


def check_metric(metric: Metric, fresh, baseline) -> dict:
    """One metric's verdict: {'path', 'status', 'fresh', 'baseline', 'detail'}
    with status in {'ok', 'fail', 'skip'}."""
    val = get_path(fresh, metric.path)
    out = {"path": metric.path, "fresh": None if val is _MISSING else val,
           "baseline": None, "detail": ""}
    if val is _MISSING:
        out["status"] = "skip" if metric.optional else "fail"
        out["detail"] = "metric missing from fresh record"
        return out
    if metric.expect is not None:
        ok = val == metric.expect
        out["status"] = "ok" if ok else "fail"
        out["detail"] = "" if ok else f"expected {metric.expect!r}"
        return out
    if metric.max_abs is not None:
        ok = abs(float(val)) <= metric.max_abs
        out["status"] = "ok" if ok else "fail"
        out["detail"] = "" if ok else f"|{val:.3e}| > {metric.max_abs:.1e}"
        return out
    base = get_path(baseline, metric.path) if baseline is not None else _MISSING
    if base is _MISSING:
        out["status"] = "fail"
        out["detail"] = "metric missing from committed baseline"
        return out
    out["baseline"] = base
    band = metric.rtol * max(abs(float(base)), 1e-30)
    ok = abs(float(val) - float(base)) <= band
    out["status"] = "ok" if ok else "fail"
    if not ok:
        out["detail"] = (f"{float(val):.6e} vs baseline {float(base):.6e} "
                         f"(rtol {metric.rtol:g})")
    return out


def compare_suite(metrics, fresh, baseline) -> list:
    """All verdicts for one suite (pure — unit-tested with injected
    regressions in tests/test_check_regression.py)."""
    return [check_metric(m, fresh, baseline) for m in metrics]


# Deterministic-metric tolerance: the seeded numpy searches reproduce to the
# last ulp on one machine; the loose 1e-6 band absorbs summation-order drift
# across numpy/python versions in the CI matrix. jax-backed results (PPO)
# get a wide sanity band instead — they vary across jaxlib builds.
DET = 1e-6
PPO_BAND = 0.35

SUITES = {
    "noc_eval": [
        Metric("parity.max_rel_diff_numpy", max_abs=1e-9),
        Metric("parity.max_rel_diff_jax", max_abs=1e-4, optional=True),
        # observability invariants: recorder on/off must not change seeded
        # results, and the attached run's work counters are deterministic
        Metric("recorder_overhead.results_identical", expect=True),
        Metric("counters.noc_batch_dispatches", rtol=DET),
        Metric("counters.noc_batch_evals", rtol=DET),
    ],
    "ppo_pipeline": [
        Metric("pallas.matches_numpy", expect=True),
    ],
    "deploy_e2e": [
        Metric("cases.0.placement.comm_cost", rtol=DET),       # zigzag
        Metric("cases.1.placement.comm_cost", rtol=DET),       # random_search
        Metric("objective_demo.comm_cost.comm_cost", rtol=DET),
        Metric("objective_demo.max_link.max_link", rtol=DET),
        Metric("objective_demo.hotspot_peak_reduction", rtol=DET),
        # deterministic work counters from the suite-wide recorder: a changed
        # dispatch or eval count means the search loops did different work
        Metric("counters.deploy_deployments", rtol=DET),
        Metric("counters.noc_batch_dispatches", rtol=DET),
        Metric("counters.noc_batch_evals", rtol=DET),
    ],
    "device_search": [
        # O(degree) delta-cost parity: exact vs full re-evaluation on the
        # integer-volume model graph, Pallas kernel vs numpy in float32
        Metric("delta_parity.numpy_exact", expect=True),
        Metric("delta_parity.numpy_max_abs_err", max_abs=1e-9),
        Metric("delta_parity.pallas_max_rel_err", max_abs=1e-5),
        # timings are never gated — the *booleans* derived from them are:
        # the one-dispatch SA must clear its smoke speedup floor, and the
        # vmapped restart fan-out must beat the single chain at far below
        # linear wall-time scaling
        Metric("headline.speedup_ok", expect=True),
        Metric("restarts.restarts_improve_ok", expect=True),
        Metric("restarts.restarts_wall_ok", expect=True),
        Metric("recorder_identity.results_identical", expect=True),
        # device best costs are jax(float32)-backed: wide band like PPO
        Metric("headline.device_comm_cost", rtol=PPO_BAND),
        Metric("restarts.curve.1.best_cost", rtol=PPO_BAND),
        Metric("ga.device_comm_cost", rtol=PPO_BAND),
        # host references on the same shape stay numpy-deterministic
        Metric("headline.host_comm_cost", rtol=DET),
        Metric("ga.host_comm_cost", rtol=DET),
        Metric("counters.sa_accepted", rtol=PPO_BAND),
    ],
    "multilevel": [
        # timings never gated; the derived booleans are: the V-cycle must
        # clear its smoke speedup floor vs flat SA at equal-or-better cost,
        # and the 16k-node placement must complete validly
        Metric("headline.speedup_ok", expect=True),
        Metric("headline.cost_ok", expect=True),
        Metric("large.completed", expect=True),
        Metric("large.valid", expect=True),
        # the V-cycle and the flat host SA are numpy-deterministic
        Metric("headline.flat_comm_cost", rtol=DET),
        Metric("headline.ml_comm_cost", rtol=DET),
        Metric("large.comm_cost", rtol=DET),
        Metric("large.n_levels", rtol=DET),
        Metric("identity.delegation_identical", expect=True),
        Metric("recorder_identity.results_identical", expect=True),
        Metric("counters.ml_levels", rtol=DET),
    ],
    "multichip": [
        Metric("cases.0.comm_cost", rtol=DET),                 # zigzag
        Metric("cases.1.comm_cost", rtol=DET),                 # sigmate
        Metric("cases.2.comm_cost", rtol=DET),                 # random_search
        Metric("cases.3.comm_cost", rtol=DET),                 # sim. annealing
        Metric("cases.4.comm_cost", rtol=DET),                 # genetic
        Metric("cases.4.interchip_bytes", rtol=DET),
        Metric("cases.5.comm_cost", rtol=PPO_BAND),            # ppo (jax)
        Metric("cases.6.interchip_bytes", rtol=DET),           # genetic+ic
        Metric("counters.noc_batch_dispatches", rtol=DET),
        Metric("counters.noc_batch_evals", rtol=DET),
    ],
    "copartition": [
        Metric("grids.0.cases.0.interchip_bytes", rtol=DET),   # balanced
        Metric("grids.0.cases.0.makespan_s", rtol=DET),
        Metric("grids.0.cases.1.interchip_bytes", rtol=DET),   # chip
        Metric("grids.0.cases.1.makespan_s", rtol=DET),
        Metric("grids.0.cases.1.partition_cut_bytes", rtol=DET),
        Metric("grids.0.cases.3.interchip_bytes", rtol=DET),   # chip+copart
        Metric("counters.noc_batch_evals", rtol=DET),
    ],
    "service": [
        # serving-layer acceptance bits: cached answers must clear the 50x
        # speedup floor over the cold p50, warm near-misses must land within
        # the cost band at under half the cold wall, batch rows must be
        # bit-identical to their solo cold searches, and a reloaded cache
        # must still hit. Raw latency percentiles are recorded, never gated —
        # except the hit p50's generous absolute ceiling (a hit is a hash +
        # dict lookup; 50 ms of slack is orders of magnitude).
        Metric("hit.all_hits", expect=True),
        Metric("hit.matches_cold", expect=True),
        Metric("hit.speedup_ok", expect=True),
        Metric("hit.p50_s", max_abs=0.05),
        Metric("warm.status_warm", expect=True),
        Metric("warm.cost_ok", expect=True),
        Metric("warm.time_ok", expect=True),
        Metric("batch.results_match", expect=True),
        Metric("persistence.hit_after_reload", expect=True),
        # the seeded SA searches behind the service are numpy-deterministic
        Metric("cold.objective_cost", rtol=DET),
        Metric("warm.objective_cost", rtol=DET),
        Metric("warm.attempts", rtol=DET),
        Metric("batch.costs.0", rtol=DET),
        Metric("batch.costs.3", rtol=DET),
        # deterministic service work counters (hits/misses/warm starts)
        Metric("counters.service_requests", rtol=DET),
        Metric("counters.service_hits", rtol=DET),
        Metric("counters.service_misses", rtol=DET),
        Metric("counters.service_warm_starts", rtol=DET),
    ],
    "fault_replace": [
        # the online re-placement loop is fully deterministic (seeded SA on
        # the batch backend, analytic drift): gate the recovery outcomes,
        # the acceptance window, and the loop's algorithmic work counters
        Metric("acceptance.link_drop_triggered_replacement", expect=True),
        Metric("acceptance.warm_within_10pct_of_cold", expect=True),
        Metric("acceptance.warm_moves_at_most_25pct_of_cold_bytes",
               expect=True),
        Metric("recorder_identity.results_identical", expect=True),
        Metric("scenarios.link_drop.final_objective", rtol=DET),
        Metric("scenarios.link_drop.moved_state_bytes", rtol=DET),
        Metric("scenarios.link_drop.recoveries.0.objective_after", rtol=DET),
        Metric("scenarios.drift.final_objective", rtol=DET),
        Metric("scenarios.node_drop.final_objective", rtol=DET),
        Metric("scenarios.node_drop.n_replacements", rtol=DET),
        Metric("counters.noc_batch_evals", rtol=DET),
        Metric("counters.runtime_replacements", rtol=DET),
    ],
}


def _run_suite(name: str, json_path: str) -> None:
    """Run one suite's smoke mode in-process, record written to json_path."""
    from . import (copartition, deploy_e2e, device_search, fault_replace,
                   multichip, multilevel, noc_eval, ppo_pipeline, service)
    fn = {"noc_eval": noc_eval.noc_eval,
          "ppo_pipeline": ppo_pipeline.ppo_pipeline,
          "deploy_e2e": deploy_e2e.deploy_e2e,
          "device_search": device_search.device_search,
          "multilevel": multilevel.multilevel,
          "multichip": multichip.multichip,
          "copartition": copartition.copartition,
          "fault_replace": fault_replace.fault_replace,
          "service": service.service}[name]
    for row in fn(smoke=True, json_path=json_path):
        print(f"  {row[0]},{row[1]:.1f},{row[2]}")


def baseline_path(name: str, baseline_dir: str) -> str:
    return os.path.join(baseline_dir, f"BENCH_{name}_smoke.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="check_regression",
        description="Run benchmark smoke suites and gate headline metrics "
                    "against the committed results/BENCH_*_smoke.json "
                    "baselines.")
    ap.add_argument("--suites", default=",".join(SUITES),
                    help=f"comma list from {tuple(SUITES)}")
    ap.add_argument("--out-dir", default="smoke-results",
                    help="where fresh smoke records are written "
                         "(uploaded as CI artifacts)")
    ap.add_argument("--baseline-dir", default=RESULTS_DIR,
                    help="directory holding BENCH_<suite>_smoke.json")
    ap.add_argument("--update-baselines", action="store_true",
                    help="write the fresh records as the new committed "
                         "baselines instead of gating")
    args = ap.parse_args(argv)

    names = [s for s in args.suites.split(",") if s]
    unknown = [s for s in names if s not in SUITES]
    if unknown:
        ap.error(f"unknown suites {unknown}; choose from {tuple(SUITES)}")

    os.makedirs(args.out_dir, exist_ok=True)
    failures = 0
    for name in names:
        fresh_path = os.path.join(args.out_dir, f"BENCH_{name}_smoke.json")
        print(f"[{name}] running smoke...")
        try:
            _run_suite(name, fresh_path)
            with open(fresh_path) as f:
                fresh = json.load(f)
        except Exception:  # noqa: BLE001 — a crashing suite must fail the gate
            traceback.print_exc()
            print(f"[{name}] FAIL (suite crashed)")
            failures += 1
            continue

        base_file = baseline_path(name, args.baseline_dir)
        if args.update_baselines:
            os.makedirs(args.baseline_dir, exist_ok=True)
            with open(base_file, "w") as f:
                json.dump(fresh, f, indent=2)
            print(f"[{name}] baseline updated -> {base_file}")
            continue

        baseline = None
        if os.path.exists(base_file):
            with open(base_file) as f:
                baseline = json.load(f)
        verdicts = compare_suite(SUITES[name], fresh, baseline)
        bad = [v for v in verdicts if v["status"] == "fail"]
        for v in verdicts:
            mark = {"ok": "ok  ", "fail": "FAIL", "skip": "skip"}[v["status"]]
            print(f"  [{mark}] {v['path']}"
                  + (f": {v['detail']}" if v["detail"] else ""))
        if bad:
            failures += 1
            print(f"[{name}] FAIL ({len(bad)} metric(s) out of band)")
        else:
            print(f"[{name}] ok")

    if failures:
        print(f"regression gate: {failures} suite(s) failed "
              "(if the change is intentional, regenerate baselines with "
              "--update-baselines and commit them)")
        return 1
    print("regression gate: all suites within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
