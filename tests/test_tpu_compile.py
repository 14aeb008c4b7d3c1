"""The placement kernels compiled for a described TPU v5e (no chip needed).

Interpret mode on the CPU accepts block shapes and VMEM footprints that the
chip's compiler refuses, so these tests compile the Pallas kernels and the
device-SA scan for a v5e at real sizes. The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test-runner worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.topology import parse_topology
from repro.deploy import deploy_model
from repro.kernels.delta_cost import delta_cost_pallas
from repro.kernels.noc_segsum import link_traffic_pallas
from repro.snn import spike_vgg16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("R,K,C", [
    (64, 32, 64),       # S-VGG16 on hier:2x2:4x4, 64 restarts
    (64, 64, 1024),     # 1024-core fabric: the delta kernel's size limit
])
def test_delta_cost_kernel_compiles(one_chip, R, K, C):
    ent = [_spec((R, K), jnp.int32, one_chip)] * 4
    compiled = jax.jit(delta_cost_pallas).lower(
        *ent, _spec((R, K), jnp.float32, one_chip),
        _spec((C, C), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_link_traffic_kernel_compiles(one_chip):
    B, K, L = 256, 4096, 1024
    compiled = jax.jit(lambda i, w: link_traffic_pallas(i, w, L)).lower(
        _spec((B, K), jnp.int32, one_chip),
        _spec((B, K), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_sa_scan_with_kernel_compiles(one_chip):
    from repro.core.placement.device_search import _sa_chains, _sa_inputs

    noc = parse_topology("hier:2x2:4x4", link_bw=8e9, core_flops=25.6e9,
                         hop_latency=2e-8)
    graph = deploy_model(spike_vgg16(n_classes=10, in_res=32, T=4), noc,
                         method="zigzag", schedule="none").graph
    args, static = _sa_inputs(graph, noc, iters=5000, t0=0.05,
                              t_end_frac=1e-3, seed=0, init=None,
                              restarts=64, t0_spread=1.0, use_pallas=True,
                              refresh_every=256)
    static["interpret"] = False          # this host is a CPU; compile Mosaic
    shapes = [_spec(np.shape(a), a.dtype, one_chip) for a in args]
    compiled = _sa_chains.lower(*shapes, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
