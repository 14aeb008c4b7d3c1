"""The comparison that decides ``correct``: every answer of a run against the
configuration's plain reference.

Numbers compared, each against the configuration's ``limits``:

``failed``          requests issued that got no plan (HTTP error or none);
``invalid``         plans that are not an injective map of the request's
                    logical cores onto the fabric (wrong length, a core out of
                    range or used twice);
``wrong_request``   answers that echo another request than the one sent;
``cost_gap``        largest |served comm_cost - reference| / reference, the
                    reference being bytes x XY hops of the plan on the graph
                    the reference builds for the request;
``mean_vs_zigzag``  mean, over the run's fixed quality sample, of the plan's
                    reference cost over the zigzag deployment's.
"""
from __future__ import annotations

import json

import numpy as np


def canonical(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def evaluate(run, ref) -> dict:
    """Fill ``run.rows`` (one per valid answer: reference cost, zigzag cost,
    status) and return the compared numbers."""
    cfg = run.config
    n_cores = ref.n_cores(cfg)
    graphs: dict = {}
    failed = invalid = wrong = 0
    gap = 0.0
    run.rows = {}
    for rec in run.answers():
        if rec["response"] is None:
            failed += 1
            continue
        resp = rec["response"]
        sent = run.bodies[rec["req"]]
        if canonical(resp.get("request")) != canonical(sent):
            wrong += 1
            continue
        fields = {k: sent[k] for k in ("batch", "spike_density", "training")}
        key = canonical(fields)
        if key not in graphs:
            graphs[key] = ref.graph(cfg, fields)
        g = graphs[key]
        p = np.asarray(resp.get("placement", []))
        ok = (p.shape == (g[0],) and p.dtype.kind in "iu"
              and len(set(p.tolist())) == g[0]
              and int(p.min(initial=0)) >= 0
              and int(p.max(initial=0)) < n_cores)
        if not ok:
            invalid += 1
            continue
        cost = ref.comm_cost(cfg, g, p)
        zz = ref.comm_cost(cfg, g, ref.zigzag(g))
        gap = max(gap, abs(float(resp["comm_cost"]) - cost) / cost)
        run.rows[rec["id"]] = {"cost": cost, "zigzag": zz, "status":
                              resp.get("status")}
    sample = [run.rows[i]["cost"] / run.rows[i]["zigzag"]
              for i in run.sample if i in run.rows]
    quality = (float(np.mean(sample)) if len(sample) == len(run.sample)
               else float("inf"))
    return {"failed": failed, "invalid": invalid, "wrong_request": wrong,
            "cost_gap": gap,
            "mean_vs_zigzag": quality}


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``: each number at or below
    its limit."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    return all(v["value"] <= v["limit"] for v in table.values()), table
