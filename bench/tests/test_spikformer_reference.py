"""The Spikformer reference against the program: the same logical graph, to
the last bit, and bytes x XY hops equal to ``Topology.evaluate``, on a tiny
Spikformer; the full configuration's graph has the program's shape."""
import json

import numpy as np
import pytest

from bench.run import BENCH, build_request_factory, load_module

NAME = "spikformer8-768-mesh16x16-devsa"
ref = load_module(BENCH / "reference" / "spikformer_mesh.py",
                  "bench_spikformer_reference")
FULL = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
TINY = {**FULL, "fabric": "mesh:4x8,bw=8e9,flops=25.6e9,lat=2e-8",
        "model": {"family": "spikformer", "depth": 2, "dim": 64, "heads": 4,
                  "mlp_ratio": 4, "n_classes": 10, "in_res": 32, "in_ch": 3,
                  "T": 4, "patch": 4}}


def fields(density):
    return {"batch": 8, "spike_density": density, "training": True}


def program_graph(config, density):
    from repro.deploy.engine import execute_request

    req = build_request_factory(config)({"spike_density": density})
    n = ref.n_cores(config)
    plan = execute_request(req, _fixed_placement=np.arange(n),
                           schedule="none")
    return plan, req.materialize_topology()


@pytest.mark.parametrize("density", [0.10005, 0.15, 0.19995])
def test_reference_matches_program(density):
    g = ref.graph(TINY, fields(density))
    plan, noc = program_graph(TINY, density)
    src, dst, vol = plan.graph.edge_arrays()
    assert g[0] == plan.graph.n
    np.testing.assert_array_equal(g[1], src)
    np.testing.assert_array_equal(g[2], dst)
    np.testing.assert_array_equal(g[3], vol)
    assert plan.report()["graph"]["branch_edges"] > 0
    rng = np.random.default_rng(0)
    for _ in range(5):
        p = rng.permutation(ref.n_cores(TINY))[:g[0]]
        assert ref.comm_cost(TINY, g, p) == pytest.approx(
            noc.evaluate(plan.graph, p).comm_cost, rel=1e-12)


def test_full_config_graph_shape_matches_program():
    g = ref.graph(FULL, fields(0.15))
    plan, _ = program_graph(FULL, 0.15)
    stats = plan.report()["graph"]
    deg = np.bincount(np.concatenate([g[1], g[2]])).max()
    assert (g[0], len(g[1]), int(deg)) == (
        stats["nodes"], stats["edges"], stats["max_degree"])
    assert g[0] == 256
    np.testing.assert_array_equal(g[3], plan.graph.edge_arrays()[2])


def test_density_grid_keeps_one_graph_shape():
    """Every firing rate the cold mix draws partitions to one graph shape,
    so set-up warms the one scan the window runs."""
    from bench.traffic import pool

    mix = json.loads((BENCH / "traffic" / "cold.json").read_text())
    shapes = set()
    for d in pool(mix["vary"]["spike_density"])[::111]:
        n, src, dst, _ = ref.graph(FULL, fields(float(d)))
        deg = np.bincount(np.concatenate([src, dst])).max()
        shapes.add((n, len(src), int(deg)))
    assert len(shapes) == 1, shapes
