"""Balanced compute+storage partitioning (paper §4.2, Fig 4)."""
import numpy as np
import pytest

try:  # property tests need the dev extra; plain tests below run regardless
    from hypothesis import given, settings, strategies as st
    HAS_HYP = True
except ImportError:
    HAS_HYP = False

from repro.core import CoreSpec, LayerProfile, partition_model
from repro.core.partition import _alloc_largest_remainder, _group_contiguous


def _layers(rng, n):
    return [LayerProfile(f"l{i}", flops=float(rng.uniform(1e8, 1e10)),
                         weight_bytes=float(rng.uniform(1e4, 1e7)),
                         out_bytes=float(rng.uniform(1e3, 1e6)),
                         c_in=64, c_out=64) for i in range(n)]


if HAS_HYP:
    @given(st.integers(0, 1000), st.integers(2, 10), st.integers(1, 4))
    @settings(max_examples=30, deadline=None)
    def test_partition_exact_core_count(seed, n_layers, mult):
        rng = np.random.default_rng(seed)
        layers = _layers(rng, n_layers)
        n_cores = n_layers * mult
        for strategy in ("compute", "storage", "balanced"):
            p = partition_model(layers, n_cores, strategy)
            assert p.n == n_cores
            fr = {}
            for s in p.slices:
                fr[s.layer] = fr.get(s.layer, 0.0) + s.frac
            for li, f in fr.items():
                assert f == pytest.approx(1.0)  # channels fully covered

    @given(st.integers(0, 500))
    @settings(max_examples=20, deadline=None)
    def test_balanced_not_worse_than_compute_or_storage(seed):
        """The paper's claim: combined balancing avoids the bucket effect."""
        rng = np.random.default_rng(seed)
        layers = _layers(rng, 6)
        core = CoreSpec(sram_bytes=5e5, flops_per_s=1e10, stream_bw=5e9)
        lat = {}
        for strategy in ("compute", "storage", "balanced"):
            p = partition_model(layers, 24, strategy, core)
            lat[strategy] = p.latencies().max()
        assert lat["balanced"] <= lat["compute"] * 1.001
        assert lat["balanced"] <= lat["storage"] * 1.001
else:
    @pytest.mark.skip(reason="hypothesis not installed (dev extra)")
    def test_hypothesis_properties():
        """Placeholder so missing property coverage shows as a skip."""


def test_group_contiguous_covers_all():
    w = np.array([5, 1, 1, 1, 8, 1, 1, 3.0])
    groups = _group_contiguous(w, 4)
    assert groups[0][0] == 0 and groups[-1][1] == len(w)
    for (a, b), (a2, b2) in zip(groups[:-1], groups[1:]):
        assert b == a2 and a < b
    assert len(groups) == 4


def test_alloc_largest_remainder_sums():
    for n in (8, 13, 32):
        alloc = _alloc_largest_remainder(np.array([1.0, 2.0, 3.0, 10.0]), n)
        assert alloc.sum() == n
        assert (alloc >= 1).all()


def test_more_layers_than_cores_groups():
    rng = np.random.default_rng(7)
    layers = _layers(rng, 54)
    p = partition_model(layers, 32, "balanced")
    assert p.n == 32
    g = p.to_graph()
    assert g.validate_dag()


def test_to_graph_multicast_volumes():
    layers = [
        LayerProfile("a", 1e9, 1e5, 1000.0, c_out=64),
        LayerProfile("b", 1e9, 1e5, 500.0, c_out=64),
    ]
    p = partition_model(layers, 4, "compute")
    g = p.to_graph()
    # every slice of layer0 multicasts its shard to both slices of layer1
    slices0 = [i for i, s in enumerate(p.slices) if s.layer == 0]
    slices1 = [i for i, s in enumerate(p.slices) if s.layer == 1]
    for i in slices0:
        for j in slices1:
            assert g.adj[i, j] == pytest.approx(p.slices[i].out_bytes)
    feats = g.node_features()
    assert (feats[slices0, 0] == 1.0).all()     # multicast flag set


def test_spill_latency_model():
    core = CoreSpec(sram_bytes=1e6, flops_per_s=1e9, stream_bw=1e9)
    fits = LayerProfile("fits", 1e9, 9e5, 1.0)
    spills = LayerProfile("spills", 1e9, 2e6, 1.0)
    pf = partition_model([fits], 1, "balanced", core)
    ps = partition_model([spills], 1, "balanced", core)
    assert ps.latencies()[0] > pf.latencies()[0]


# ---------------------------------------------------------------------------
# producer edges (branched profiles)
# ---------------------------------------------------------------------------

def _tiny_spikformer_profile(density=0.15):
    from repro.snn import profile_model, spikformer

    cfg = spikformer(depth=2, dim=64, heads=4, mlp_ratio=4, n_classes=10,
                     in_res=32, in_ch=3, T=4, patch=4)
    return profile_model(cfg, batch=8, spike_density=density)


def _consecutive_graph(p):
    """Every slice of layer l to every slice of layer l+1, the volume its
    shard: the chain construction the producer edges must reproduce."""
    n = p.n
    adj = np.zeros((n, n))
    for i, s in enumerate(p.slices):
        for j, t in enumerate(p.slices):
            if t.layer == s.layer + 1:
                adj[i, j] = s.out_bytes
    return adj


@pytest.mark.parametrize("model,n_cores", [
    ("spike_resnet18", 32), ("spike_resnet50", 64), ("spike_resnet50", 32),
    ("spike_vgg16", 64)])
def test_chain_graphs_bit_equal_consecutive_construction(model, n_cores):
    import repro.snn as snn

    prof = snn.profile_model(getattr(snn, model)(), batch=8)
    assert all(l.producers == () for l in prof)
    p = partition_model(prof, n_cores, "balanced")
    assert p.unit_edges is None
    np.testing.assert_array_equal(p.to_graph().adj, _consecutive_graph(p))


def test_aligned_edge_volumes_sum_to_producer_out_bytes():
    prof = _tiny_spikformer_profile()
    p = partition_model(prof, 32, "balanced")
    adj = p.to_graph().adj
    layer = np.array([s.layer for s in p.slices])
    aligned = [(a, b, vol) for a, b, kind, vol, _, _ in p.unit_edges
               if kind == "aligned"]
    assert len(aligned) == 2 * 5          # attn <- q, k, v; proj; fc2
    for a, b, vol in aligned:
        assert vol == prof[a].out_bytes
        block = adj[np.ix_(layer == a, layer == b)]
        assert block.sum() == pytest.approx(vol, rel=1e-12)
        # each producer slice sends exactly its own shard
        shard = [s.out_bytes for s in p.slices if s.layer == a]
        np.testing.assert_allclose(block.sum(axis=1), shard, rtol=1e-12)


def test_aligned_edges_follow_channel_overlap():
    """8 channels in 3 slices (3, 3, 2) to 4 heads in 2 slices (2, 2):
    each pair carries the producer's bytes x the overlap of their ranges."""
    layers = [LayerProfile("x", 1e9, 0.0, 800.0, c_out=8),
              LayerProfile("h", 1e9, 0.0, 100.0, c_out=4,
                           producers=(("x", "aligned"),))]
    p = partition_model(layers, 5, "compute")
    assert [s.layer for s in p.slices] == [0, 0, 0, 1, 1]
    want = np.zeros((5, 5))
    want[0, 3], want[1, 3], want[1, 4], want[2, 4] = 300.0, 100.0, 200.0, 200.0
    np.testing.assert_array_equal(p.to_graph().adj, want)


def test_grouped_units_keep_producer_edges():
    """20 units on 16 cores: contiguous groups of one slice each; an edge
    inside a group is dropped, and each producer's tensor reaches another
    group once, at its full volume."""
    from repro.core.partition import _group_contiguous, _layer_weight

    prof = _tiny_spikformer_profile()
    core = CoreSpec()
    p = partition_model(prof, 16, "balanced", core)
    assert p.n == 16 and all(s.frac == 1.0 for s in p.slices)
    groups = _group_contiguous(
        np.array([_layer_weight(l, "balanced", core) for l in prof]), 16)
    group_of = {}
    for g, (a, b) in enumerate(groups):
        for u in range(a, b):
            group_of[prof[u].name] = g
    want = np.zeros((16, 16))
    sent = set()
    for u, l in enumerate(prof):
        prods = [n for n, _ in l.producers] or ([prof[u - 1].name] if u else [])
        for name in prods:
            ga, gb = group_of[name], group_of[l.name]
            if ga != gb and (name, gb) not in sent:
                sent.add((name, gb))
                want[ga, gb] += next(x.out_bytes for x in prof
                                     if x.name == name)
    adj = p.to_graph().adj
    np.testing.assert_array_equal(adj, want)
    assert np.trace(adj) == 0.0


def test_chip_strategies_refuse_branched_profiles():
    from repro.core.topology import parse_topology

    noc = parse_topology("hier:2x2:4x4")
    for strategy in ("chip", "chip_balanced"):
        with pytest.raises(ValueError, match="branch"):
            partition_model(_tiny_spikformer_profile(), 64, strategy,
                            topology=noc)


@pytest.mark.parametrize("producers,match", [
    ((("nope", "full"),), "not an earlier unit"),
    ((("b", "full"),), "not an earlier unit"),
    ((("a", "sideways"),), "kind"),
])
def test_bad_producers_rejected(producers, match):
    layers = [LayerProfile("a", 1e9, 1e5, 1e3, c_out=8),
              LayerProfile("b", 1e9, 1e5, 1e3, c_out=8,
                           producers=producers)]
    with pytest.raises(ValueError, match=match):
        partition_model(layers, 4, "balanced")
