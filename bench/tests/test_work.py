"""The SA step's byte count follows the algorithm's shapes, which the Pallas
path and the gather path share."""
import json

import pytest

from bench.run import BENCH, build_request_factory
from bench.work import sa_step_bytes


def test_count_at_paper_shape():
    assert sa_step_bytes(4, 64) == 16128


@pytest.mark.parametrize("use_pallas", [True, False])
def test_same_count_on_both_paths(use_pallas):
    from repro.core.placement.device_search import _sa_inputs
    from repro.deploy.engine import execute_request
    import numpy as np

    config = json.loads((BENCH / "configs" /
                         "sresnet50-mesh8x8-devsa.json").read_text())
    req = build_request_factory(config)({})
    plan = execute_request(req, _fixed_placement=np.arange(64),
                           schedule="none")
    args, static = _sa_inputs(plan.graph, req.materialize_topology(), 5000,
                              0.05, 1e-3, 0, None, 64, 1.0, use_pallas, 256)
    assert static["use_pallas"] is use_pallas
    chains, degree = args[0].shape[0], args[4].shape[1]
    assert sa_step_bytes(degree, chains) == sa_step_bytes(4, 64)
