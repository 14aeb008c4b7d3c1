"""SNN substrate: LIF dynamics, surrogate gradients, spike models, BPTT."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.snn import (LIFConfig, init_state, lif_rollout, lif_step,
                       model_rollout, model_specs, model_step, profile_model,
                       spike, spike_resnet18, spike_resnet50, spike_vgg16,
                       spikformer)
from repro.snn.bptt import make_optimizer, train_step
from repro.models.specs import materialize, n_params


def tiny_spikformer():
    return spikformer(depth=2, dim=64, heads=4, mlp_ratio=4, n_classes=10,
                      in_res=32, in_ch=3, T=4, patch=4)


def test_lif_integrates_and_fires():
    cfg = LIFConfig(threshold=1.0, decay=0.5)
    u = jnp.zeros((1,))
    s = jnp.zeros((1,))
    spikes = []
    for _ in range(6):
        u, s = lif_step(u, s, jnp.ones((1,)) * 0.8, cfg)
        spikes.append(float(s[0]))
    assert max(spikes) == 1.0                  # eventually fires
    assert spikes[0] == 0.0                    # not instantly at 0.8 < 1.0


def test_hard_reset_clears_membrane():
    cfg = LIFConfig(threshold=1.0, decay=1.0, reset="hard")
    u, s = lif_step(jnp.zeros((1,)), jnp.zeros((1,)), jnp.array([1.5]), cfg)
    assert float(s[0]) == 1.0
    u2, s2 = lif_step(u, s, jnp.zeros((1,)), cfg)
    assert float(u2[0]) == 0.0                 # membrane zeroed after spike


def test_surrogate_gradient_nonzero_near_threshold():
    for kind in ("rect", "sigmoid", "atan"):
        g = jax.grad(lambda x: spike(x, kind, 2.0).sum())(jnp.array([0.1]))
        assert float(g[0]) > 0.0
    # far from threshold the rect window gives exactly zero
    g = jax.grad(lambda x: spike(x, "rect", 2.0).sum())(jnp.array([5.0]))
    assert float(g[0]) == 0.0


def test_lif_rollout_rates_monotone_in_current():
    cfg = LIFConfig()
    t = 16
    low = lif_rollout(jnp.full((t, 8), 0.3), cfg).mean()
    high = lif_rollout(jnp.full((t, 8), 1.2), cfg).mean()
    assert float(high) > float(low)


@pytest.mark.parametrize("builder", [spike_resnet18, spike_vgg16,
                                     spike_resnet50])
def test_spike_models_forward(builder):
    cfg = builder(n_classes=10, in_res=16, T=2, width_mult=0.125)
    params = materialize(jax.random.PRNGKey(0), model_specs(cfg))
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    logits, rate = model_rollout(params, cfg, x)
    assert logits.shape == (2, 10)
    assert bool(jnp.isfinite(logits).all())
    assert 0.0 <= float(rate) <= 1.0


def test_spike_outputs_are_binary():
    cfg = spike_resnet18(n_classes=4, in_res=8, T=1, width_mult=0.125)
    params = materialize(jax.random.PRNGKey(0), model_specs(cfg))
    state = init_state(cfg, 2)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 3))
    new_state, _ = model_step(params, cfg, state, x)
    for (u, s) in new_state.values():
        vals = np.unique(np.asarray(s))
        assert set(vals.tolist()) <= {0.0, 1.0}


def test_bptt_reduces_loss():
    cfg = spike_vgg16(n_classes=4, in_res=8, T=2, width_mult=0.125)
    params = materialize(jax.random.PRNGKey(0), model_specs(cfg))
    opt = make_optimizer(params)
    x = jax.random.uniform(jax.random.PRNGKey(1), (8, 8, 8, 3))
    y = jnp.array([0, 1, 2, 3, 0, 1, 2, 3])
    losses = []
    for _ in range(8):
        params, opt, m = train_step(params, opt, x, y, cfg)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_profile_matches_partitioner_contract():
    cfg = spike_resnet18(n_classes=10, in_res=32, T=4)
    prof = profile_model(cfg, batch=8)
    assert all(p.flops > 0 and p.weight_bytes > 0 for p in prof)
    # training triples compute vs inference
    prof_inf = profile_model(cfg, batch=8, training=False)
    for pt, pi in zip(prof, prof_inf):
        assert pt.flops > pi.flops


def test_spikformer_8_768_parameter_count():
    """Spikformer-8-768 at its published sizes: 66.34 M parameters
    (arXiv:2209.15425, ImageNet table), counted from specs alone."""
    cfg = spikformer()
    assert n_params(model_specs(cfg)) == pytest.approx(66.34e6, rel=5e-3)
    prof = profile_model(cfg)
    assert len(prof) == 62
    # patch 16 at 224x224: N = 14 x 14 tokens at the attention
    attn = next(p for p in prof if p.name == "b0attn")
    assert attn.weight_bytes == 0.0 and attn.c_out == 12
    macs = 2 * 196 * 196 * 768
    assert attn.flops == (2.0 * macs * 0.15 + 4.0 * macs) * 4


def test_spikformer_producers():
    prof = profile_model(tiny_spikformer(), batch=8)
    got = {p.name: p.producers for p in prof}
    assert [p.name for p in prof][:5] == ["sps0", "sps1", "sps2", "sps3",
                                          "rpe"]
    assert got["sps3"] == () and got["rpe"] == () and got["head"] == ()
    for b, x in ((0, "rpe"), (1, "b0fc2")):
        p = f"b{b}"
        for u in "qkv":
            assert got[p + u] == ((x, "full"),)
        assert got[p + "attn"] == tuple((p + u, "aligned") for u in "qkv")
        assert got[p + "proj"] == ((p + "attn", "full"), (x, "aligned"))
        assert got[p + "fc1"] == ((p + "proj", "full"),)
        assert got[p + "fc2"] == ((p + "fc1", "full"), (p + "proj", "aligned"))
    by = {p.name: p for p in prof}
    # patch 4: pools after sps2 and sps3 only, so 8 x 8 tokens
    assert by["b0q"].flops == (2.0 * 64 * 64 * 64 * 0.15
                               + 4.0 * 64 * 64 * 64) * 4 * 8
    # residual-stream outputs carry a byte an element, spikes a bit
    n_d = 64 * 64
    assert by["b0proj"].out_bytes == (n_d + 2.0 * n_d) * 4 * 8
    assert by["b0q"].out_bytes == (n_d / 8 + 2.0 * n_d) * 4 * 8
    assert by["rpe"].out_bytes == by["b0fc2"].out_bytes


@pytest.mark.parametrize("fn", [
    lambda cfg: init_state(cfg, 2),
    lambda cfg: model_step({}, cfg, {}, jnp.zeros((2, 32, 32, 3))),
])
def test_spikformer_forward_refused(fn):
    with pytest.raises(NotImplementedError, match="SPS"):
        fn(tiny_spikformer())


def test_spikformer_rejects_bad_patch():
    with pytest.raises(ValueError, match="patch"):
        spikformer(patch=3)
