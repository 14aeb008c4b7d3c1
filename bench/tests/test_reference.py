"""The plain reference against the program on both configurations: the same
logical graph, and bytes x XY hops equal to ``Topology.evaluate``."""
import json

import numpy as np
import pytest

from bench.run import BENCH, build_request_factory, load_module

CONFIGS = ["sresnet50-mesh8x8-devsa", "sresnet18-mesh4x8-ppo"]
ref = load_module(BENCH / "reference" / "snn_mesh.py", "bench_reference")


def program_graph(config, density):
    from repro.deploy.engine import execute_request

    req = build_request_factory(config)({"spike_density": density})
    n_cores = ref.n_cores(config)
    n = ref.graph(config, {"batch": 8, "spike_density": density,
                           "training": True})[0]
    plan = execute_request(req, _fixed_placement=np.arange(n),
                           schedule="none")
    return plan.graph, req.materialize_topology(), n_cores


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("density", [0.10005, 0.15, 0.19995])
def test_reference_matches_program(name, density):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    g = ref.graph(config, {"batch": 8, "spike_density": density,
                           "training": True})
    graph, noc, n_cores = program_graph(config, density)
    src, dst, vol = graph.edge_arrays()[:3]
    assert g[0] == graph.n
    np.testing.assert_array_equal(g[1], src)
    np.testing.assert_array_equal(g[2], dst)
    np.testing.assert_array_equal(g[3], vol)
    rng = np.random.default_rng(0)
    for _ in range(5):
        p = rng.permutation(n_cores)[:g[0]]
        assert ref.comm_cost(config, g, p) == pytest.approx(
            noc.evaluate(graph, p).comm_cost, rel=1e-12)


@pytest.mark.parametrize("name", CONFIGS)
def test_density_grid_keeps_one_graph_shape(name):
    """Every firing rate the cold mix draws partitions to one graph shape
    (nodes, edges, largest incident degree), so set-up warms one scan."""
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    mix = json.loads((BENCH / "traffic" / "cold.json").read_text())
    from bench.traffic import pool

    shapes = set()
    for d in pool(mix["vary"]["spike_density"])[::37]:
        n, src, dst, _ = ref.graph(config, {"batch": 8, "spike_density":
                                            float(d), "training": True})
        deg = np.bincount(np.concatenate([src, dst])).max()
        shapes.add((n, len(src), int(deg)))
    assert len(shapes) == 1, shapes


def test_lower_precision_cost_differs():
    """The control's bfloat16 accumulation moves the cost far past the
    cost_gap limit; float64 reproduces itself."""
    import ml_dtypes

    config = json.loads((BENCH / "configs" /
                         "sresnet50-mesh8x8-devsa.json").read_text())
    g = ref.graph(config, {"batch": 8, "spike_density": 0.15,
                           "training": True})
    p = np.random.default_rng(1).permutation(64)
    c64 = ref.comm_cost(config, g, p)
    c16 = ref.comm_cost(config, g, p, ml_dtypes.bfloat16)
    assert abs(c16 - c64) / c64 > 10 * config["limits"]["cost_gap"]
