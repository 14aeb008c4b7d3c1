"""Readings the limits of ``correct`` are set from, for one cell, in one
process on the chip: the compared numbers of sound runs on many seeds, of
the control, and of the planted faults.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--mode sound|frozen]

* ``sound``    the cell as it runs; each run also gives the ``control``
  reading: the reference put in the program's place in the next lower
  precision, each served plan's comm cost replaced by the reference's own
  cost of that plan accumulated in bfloat16 (the configuration states
  float32 for the search and float64 for the served cost);
* ``frozen``   a search step that returns its state unchanged: device SA
  returns its initial placement, PPO's update leaves the policy as it was.

It prints one JSON line per reading. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import check  # noqa: E402
from bench import run as bench_run  # noqa: E402


def lower_precision_costs(run, ref):
    """Replace every served comm cost by the reference's bfloat16 cost of
    the served plan (the control's answer in the program's place)."""
    import ml_dtypes
    import numpy as np

    for rec in run.answers():
        resp = rec["response"]
        if resp is None:
            continue
        sent = run.bodies[rec["req"]]
        g = ref.graph(run.config, {k: sent[k] for k in
                                   ("batch", "spike_density", "training")})
        p = np.asarray(resp["placement"])
        if p.shape == (g[0],):
            resp["comm_cost"] = ref.comm_cost(run.config, g, p,
                                              ml_dtypes.bfloat16)


@contextlib.contextmanager
def control_in_place():
    evaluate = check.evaluate

    def patched(run, ref):
        lower_precision_costs(run, ref)
        return evaluate(run, ref)
    with mock.patch.object(check, "evaluate", patched):
        yield


@contextlib.contextmanager
def frozen_search():
    """Searches that return their state unchanged."""
    import functools

    import numpy as np
    from repro.core.placement import baselines, device_search, ppo

    @functools.wraps(device_search.simulated_annealing_device)
    def sa_unchanged(graph, noc, init=None, **kw):
        return np.asarray(init if init is not None
                          else baselines.zigzag(graph.n, noc), np.int64)

    update = ppo._ppo_update_scan

    @functools.wraps(update)
    def ppo_unchanged(actor, critic, opt_a, opt_c, *args, **kw):
        out = update(actor, critic, opt_a, opt_c, *args, **kw)
        return (actor, critic, opt_a, opt_c) + tuple(out[4:])
    with mock.patch.object(device_search, "simulated_annealing_device",
                           sa_unchanged), \
            mock.patch.object(ppo, "_ppo_update_scan", ppo_unchanged):
        yield


@contextlib.contextmanager
def sound_and_control(found: dict):
    """Check each run twice: as served, and with the control's costs in the
    program's place, so one window gives both readings."""
    evaluate = check.evaluate

    def both(run, ref):
        found["sound"] = numbers = evaluate(run, ref)
        saved = [None if r["response"] is None else dict(r["response"])
                 for r in run.answers()]
        lower_precision_costs(run, ref)
        found["control"] = evaluate(run, ref)
        for r, resp in zip(run.answers(), saved):
            r["response"] = resp
        evaluate(run, ref)                  # restore run.rows for metrics
        return numbers
    with mock.patch.object(check, "evaluate", both):
        yield


def readings(workload: str, seed: int, seconds: float, mode: str,
             require_chip: bool = True) -> list:
    """``mode`` ``sound`` gives the sound and the control reading of one
    run; ``frozen`` the reading of a run with the frozen search."""
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    found: dict = {}
    ctx = sound_and_control(found) if mode == "sound" else frozen_search()
    with ctx:
        out = bench_run.run_cell(args, require_chip=require_chip)
    if mode != "sound":
        found[mode] = {k: v["value"] for k, v in out["checks"].items()}
    limits = bench_run.cell_spec(workload)[2]["limits"]
    return [{"workload": workload, "seed": seed, "mode": m,
             "correct": check.judge(n, limits)[0],
             "attempted": out["attempted"], "numbers": n,
             "device": out["device"]["kind"]} for m, n in found.items()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--mode", choices=("sound", "frozen"), default="sound")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(args.workload, seed, args.seconds, args.mode):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
