"""Mixture-of-Experts with sort-based capacity dispatch (EP-shardable).

Dispatch avoids the GShard [T,E,C] one-hot tensors (quadratic in tokens): tokens are
argsorted by expert id, positions-within-expert computed from group offsets, and a
flat gather index [E*C] built by scatter of *indices* (cheap int array). The heavy
data movement is then a single gather -> batched expert GEMM [E,C,D]x[E,D,F] -> a
combine-weighted scatter-add back. Under GSPMD, sharding the [E, C, ...] buffers on
the "expert" axis turns the gather/scatter into the expert-parallel all-to-all.

Supports top-k softmax routing (Qwen3-style normalized top-k), optional shared
experts (DeepSeek), capacity factor with token dropping (dropped tokens fall back to
the residual path), and the standard load-balance auxiliary loss.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .specs import param


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                     # per-expert hidden dim
    n_shared: int = 0             # shared (always-on) experts
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_dtype: str = "float32"


def moe_specs(d: int, cfg: MoEConfig, dtype=jnp.bfloat16):
    e, f = cfg.n_experts, cfg.d_ff
    out = {
        "router": param((d, e), ("embed", "expert"), dtype=jnp.float32,
                        scale=0.02),
        "w_gate": param((e, d, f), ("expert", "embed", "mlp"), dtype=dtype),
        "w_up": param((e, d, f), ("expert", "embed", "mlp"), dtype=dtype),
        "w_down": param((e, f, d), ("expert", "mlp", "embed"), dtype=dtype),
    }
    if cfg.n_shared:
        fs = f * cfg.n_shared
        out["shared_gate"] = param((d, fs), ("embed", "mlp"), dtype=dtype)
        out["shared_up"] = param((d, fs), ("embed", "mlp"), dtype=dtype)
        out["shared_down"] = param((fs, d), ("mlp", "embed"), dtype=dtype)
    return out


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)       # round up to 8


def _route(p, xf, cfg: MoEConfig):
    """Router: returns (top_p [T,k], top_ids [T,k], aux-loss pieces)."""
    e, k = cfg.n_experts, cfg.top_k
    t = xf.shape[0]
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    density = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(
        1.0) / (t * k)
    mean_prob = probs.mean(axis=0)
    return top_p, top_ids, density, mean_prob


def _dispatch(xf, top_ids, top_p, e: int, cap: int):
    """Sort tokens by expert -> (buf [E,C,D], combine metadata)."""
    t, d = xf.shape
    k = top_ids.shape[1]
    flat_expert = top_ids.reshape(-1)                           # [T*k]
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = top_p.reshape(-1)
    order = jnp.argsort(flat_expert)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    offsets = jnp.cumsum(counts) - counts                       # exclusive
    pos = jnp.arange(t * k) - offsets[se]                       # pos in expert
    keep = pos < cap
    slot = se * cap + pos                                       # flat slot
    gather_idx = jnp.full((e * cap,), t, jnp.int32)
    gather_idx = gather_idx.at[jnp.where(keep, slot, e * cap)].set(
        st.astype(jnp.int32), mode="drop")
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    buf = xf_pad[gather_idx].reshape(e, cap, d)
    return buf, (st, slot, keep, sg)


def _combine(y_flat, meta, t: int, dtype):
    st, slot, keep, sg = meta
    d = y_flat.shape[-1]
    contrib = y_flat[jnp.where(keep, slot, 0)] * \
        jnp.where(keep, sg, 0.0)[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[st].add(
        contrib.astype(jnp.float32))
    return out.astype(dtype)


def _expert_ffn(p, buf):
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def _shared_ffn(p, x):
    gs = jnp.einsum("bsd,df->bsf", x, p["shared_gate"])
    us = jnp.einsum("bsd,df->bsf", x, p["shared_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * us, p["shared_down"])


def moe_apply(p, x, cfg: MoEConfig):
    """x [B,S,D] -> (y [B,S,D], aux_loss scalar).

    Single-device / GSPMD-global formulation. Under a mesh context with
    n_experts divisible by the model axis, dispatch runs expert-parallel via
    ``shard_map`` + explicit all-to-all (``_moe_ep``) — tokens stay local to
    their data shard, only the top-k activations cross the EP axis (the
    collective whose torus locality the placement optimizer targets).
    """
    from ..sharding.rules import _ctx
    mesh = getattr(_ctx, "mesh", None)
    if (mesh is not None and "model" in mesh.shape
            and cfg.n_experts % mesh.shape["model"] == 0
            and x.shape[1] % mesh.shape["model"] == 0):
        return _moe_ep(p, x, cfg, mesh)

    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    top_p, top_ids, density, mean_prob = _route(p, xf, cfg)
    aux = cfg.aux_loss_coef * cfg.n_experts * jnp.sum(density * mean_prob)
    cap = _capacity(t, cfg)
    buf, meta = _dispatch(xf, top_ids, top_p, cfg.n_experts, cap)
    y = _expert_ffn(p, buf).reshape(cfg.n_experts * cap, d)
    out = _combine(y, meta, t, x.dtype).reshape(b, s, d)
    if cfg.n_shared:
        out = out + _shared_ffn(p, x)
    return out, aux


def _moe_ep(p, x, cfg: MoEConfig, mesh):
    """Expert-parallel MoE: shard_map over the model axis with all-to-all.

    Tokens are split over (pod, data) × model(seq); each device routes its own
    tokens, all-to-all regroups top-k activations by expert shard, local expert
    FFN, inverse all-to-all, local combine. Shared experts run outside in
    plain GSPMD.
    """
    import math

    from jax.sharding import PartitionSpec as P
    b, s, d = x.shape
    n_ep = mesh.shape["model"]
    e, e_loc = cfg.n_experts, cfg.n_experts // n_ep
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    while batch_axes and b % math.prod(
            mesh.shape[a] for a in batch_axes) != 0:
        batch_axes = batch_axes[:-1]
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    x_spec = P(bspec, "model", None)
    all_axes = tuple(mesh.axis_names)

    def local_fn(router, w_gate, w_up, w_down, x_loc):
        pl = {"router": router, "w_gate": w_gate, "w_up": w_up,
              "w_down": w_down}
        b_loc, s_loc, _ = x_loc.shape
        t = b_loc * s_loc
        xf = x_loc.reshape(t, d)
        top_p, top_ids, density, mean_prob = _route(pl, xf, cfg)
        aux = cfg.aux_loss_coef * e * jnp.sum(
            jax.lax.pmean(density, all_axes)
            * jax.lax.pmean(mean_prob, all_axes))
        cap = _capacity(t, cfg)
        buf, meta = _dispatch(xf, top_ids, top_p, e, cap)    # [E, cap, d]
        buf = buf.reshape(n_ep, e_loc, cap, d)
        # EP all-to-all: tokens regroup onto their expert's shard
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=2,
                                 tiled=True)                 # [1? e_loc,n*cap,d]
        buf = buf.reshape(e_loc, n_ep * cap, d)
        y = _expert_ffn(pl, buf)                             # [e_loc,n*cap,d]
        y = y.reshape(e_loc, n_ep, cap, d)
        y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                               tiled=True)
        y = y.reshape(e * cap, d)
        out = _combine(y, meta, t, x_loc.dtype)
        return out.reshape(b_loc, s_loc, d), aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    if cfg.n_shared:
        out = out + _shared_ffn(p, x)
    return out, aux
