"""Actor / Critic networks (paper Fig 5b, 5c).

Actor: GCN(L̂, X) -> per-node embedding, concatenated with a mean-pooled global
context, through two FC layers (ReLU) to four outputs per node — (mu, log_std) for the
row dimension and for the column dimension. ``tanh`` bounds the means inside the grid
(the paper's "Tanh was used to constrain the output deployment scheme"), matching the
[-clip, clip] range that ``discretize`` bins onto. The paper's action for an n-node /
R×C-core problem is exactly this: continuous values matching the number of cores,
Gaussian-distributed per node and re-discretized.

Critic: its own GCN + pooled MLP -> scalar state value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...models.specs import param, materialize
from .gcn import gcn_specs, gcn_apply

LOG_STD_MIN, LOG_STD_MAX = -4.0, 1.0
LOG_STD_INIT = -1.2          # initial std ~0.3 of the [-1,1] action range


def actor_specs(d_feat: int = 5, d_gcn: int = 32, d_fc: int = 64):
    return {
        "gcn": gcn_specs(d_feat, d_gcn),
        "fc1_w": param((2 * d_gcn, d_fc), ("ac_in", "ac_out")),
        "fc1_b": param((d_fc,), ("ac_out",), init="zeros"),
        "fc2_w": param((d_fc, 4), ("ac_in", "ac_out"), scale=0.01),
        "fc2_b": param((4,), ("ac_out",), init="zeros"),
    }


def critic_specs(d_feat: int = 5, d_gcn: int = 32, d_fc: int = 64):
    return {
        "gcn": gcn_specs(d_feat, d_gcn),
        "fc1_w": param((d_gcn, d_fc), ("ac_in", "ac_out")),
        "fc1_b": param((d_fc,), ("ac_out",), init="zeros"),
        "fc2_w": param((d_fc, 1), ("ac_in", "ac_out"), scale=0.01),
        "fc2_b": param((1,), ("ac_out",), init="zeros"),
    }


def actor_apply(params, lap, x):
    """Returns (mu [n,2], log_std [n,2])."""
    h = gcn_apply(params["gcn"], lap, x)                      # [n, d_gcn]
    g = jnp.broadcast_to(h.mean(axis=0, keepdims=True), h.shape)
    z = jnp.concatenate([h, g], axis=-1)
    z = jnp.maximum(z @ params["fc1_w"] + params["fc1_b"], 0.0)
    out = z @ params["fc2_w"] + params["fc2_b"]               # [n, 4]
    mu = jnp.tanh(out[:, :2])
    log_std = jnp.clip(out[:, 2:] + LOG_STD_INIT, LOG_STD_MIN, LOG_STD_MAX)
    return mu, log_std


def critic_apply(params, lap, x):
    h = gcn_apply(params["gcn"], lap, x).mean(axis=0)         # [d_gcn]
    z = jnp.maximum(h @ params["fc1_w"] + params["fc1_b"], 0.0)
    return (z @ params["fc2_w"] + params["fc2_b"])[0]


def sample_actions(key, mu, log_std, n_samples: int):
    """Gaussian sample a batch of continuous actions: [B, n, 2] + logp [B]."""
    std = jnp.exp(log_std)
    eps = jax.random.normal(key, (n_samples,) + mu.shape)
    acts = mu[None] + std[None] * eps
    logp = gaussian_logp(acts, mu, log_std)
    return acts, logp


def gaussian_logp(acts, mu, log_std, half_log_2pi=None):
    """Sum of diagonal-Gaussian log-densities over nodes and dims: [B].

    ``half_log_2pi`` is the normalizing constant ½·log 2π, computed here when
    not given. Computed here, it is folded on the host when this runs
    compiled and computed on the device when it runs op by op; on a TPU the
    two differ in the last bits, so a compiled caller that must match the
    op-by-op result passes the device's value in."""
    if half_log_2pi is None:
        half_log_2pi = 0.5 * jnp.log(2 * jnp.pi)
    std = jnp.exp(log_std)
    z = (acts - mu[None]) / std[None]
    per = -0.5 * z ** 2 - log_std[None] - half_log_2pi
    return per.sum(axis=(1, 2))


def entropy(log_std):
    return jnp.sum(log_std + 0.5 * jnp.log(2 * jnp.pi * jnp.e))


def init_actor_critic(key, d_feat: int = 5, d_gcn: int = 32, d_fc: int = 64):
    ka, kc = jax.random.split(key)
    return (materialize(ka, actor_specs(d_feat, d_gcn, d_fc)),
            materialize(kc, critic_specs(d_feat, d_gcn, d_fc)))
