"""The SNN model zoo: Spike-ResNet18 / Spike-VGG16 / Spike-ResNet50 (the paper's
workloads, §5.1) and Spikformer (Zhou et al., ICLR 2023, arXiv:2209.15425).

Architecture = descriptor list; ``model_specs`` / ``init_state`` / ``model_step`` all
walk the same descriptors, so the profiler (`snn.profile`) and partitioner see exactly
the executed graph. Time is handled by ``lax.scan`` outside the step function with the
per-layer LIF membrane states as carry (BPTT through time unrolls this scan).

Reduced ("smoke") configs scale width/depth/resolution down so the full training step
runs on CPU; the full configs match torchvision channel plans.

Spikformer's descriptors (:class:`SPS`, :class:`TransformerBlock` with its
:class:`SpikingSelfAttention` and :class:`LinearBNLif` units) are placement
descriptors: ``model_specs`` sizes them and ``snn.profile`` profiles them, but
the BPTT forward (``init_state`` / ``model_step``) is implemented for the
convolution stacks only and raises ``NotImplementedError`` on them.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from .neurons import LIFConfig, lif_step


# ---- descriptors -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvBNLif:
    name: str
    cin: int
    cout: int
    k: int = 3
    stride: int = 1
    spike_out: bool = True    # False: BN only (pre-residual-add branch)


@dataclasses.dataclass(frozen=True)
class Residual:
    name: str
    body: tuple               # tuple[ConvBNLif, ...] (last one spike_out=False)
    downsample: Any = None    # optional ConvBNLif (1x1, spike_out=False)


@dataclasses.dataclass(frozen=True)
class MaxPool:
    name: str
    k: int = 2
    stride: int = 2


@dataclasses.dataclass(frozen=True)
class Classifier:
    name: str
    din: int
    dout: int


@dataclasses.dataclass(frozen=True)
class LinearBNLif:
    """Token-wise Linear-BN-LIF: a 1x1 unit applied to each of the N tokens."""
    name: str
    din: int
    dout: int


@dataclasses.dataclass(frozen=True)
class SpikingSelfAttention:
    """SSA: Q, K, V = LIF(BN(Linear(X))); A = LIF(Q·Kᵀ·V·scale) per head, with
    no softmax; output LIF(BN(Linear(A))) (``proj``), added to X."""
    name: str
    q: LinearBNLif
    k: LinearBNLif
    v: LinearBNLif
    proj: LinearBNLif
    heads: int
    scale: float = 0.125


@dataclasses.dataclass(frozen=True)
class TransformerBlock:
    """X' = X + SSA(X); X'' = X' + fc2(fc1(X'))."""
    name: str
    attn: SpikingSelfAttention
    fc1: LinearBNLif
    fc2: LinearBNLif


@dataclasses.dataclass(frozen=True)
class SPS:
    """Spiking Patch Splitting: Conv-BN-LIF ``convs``, each followed by a 2x2
    max-pool where ``pool_after`` says, then the relative-position conv
    ``rpe``, whose output is added to its own input."""
    name: str
    convs: tuple              # tuple[ConvBNLif, ...]
    pool_after: tuple         # tuple[bool, ...], one per conv
    rpe: ConvBNLif


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    name: str
    blocks: tuple
    n_classes: int
    in_res: int
    in_ch: int = 3
    T: int = 4
    lif: LIFConfig = LIFConfig()


# ---- model builders ---------------------------------------------------------

def _resnet_blocks(stage_plan, widths, bottleneck: bool, width_mult: float,
                   in_ch: int):
    w = lambda c: max(int(c * width_mult), 8)
    blocks = [ConvBNLif("stem", in_ch, w(64), k=7, stride=2),
              MaxPool("stem_pool", 3, 2)]
    cin = w(64)
    for si, (n_blocks, width) in enumerate(zip(stage_plan, widths)):
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            cout = w(width) * (4 if bottleneck else 1)
            if bottleneck:
                body = (
                    ConvBNLif(f"s{si}b{bi}c1", cin, w(width), 1, stride),
                    ConvBNLif(f"s{si}b{bi}c2", w(width), w(width), 3, 1),
                    ConvBNLif(f"s{si}b{bi}c3", w(width), cout, 1, 1,
                              spike_out=False),
                )
            else:
                body = (
                    ConvBNLif(f"s{si}b{bi}c1", cin, cout, 3, stride),
                    ConvBNLif(f"s{si}b{bi}c2", cout, cout, 3, 1,
                              spike_out=False),
                )
            down = None
            if stride != 1 or cin != cout:
                down = ConvBNLif(f"s{si}b{bi}down", cin, cout, 1, stride,
                                 spike_out=False)
            blocks.append(Residual(f"s{si}b{bi}", body, down))
            cin = cout
    return tuple(blocks), cin


def spike_resnet18(n_classes=10, in_res=32, T=4, width_mult=1.0,
                   in_ch=3) -> SNNConfig:
    blocks, cout = _resnet_blocks([2, 2, 2, 2], [64, 128, 256, 512], False,
                                  width_mult, in_ch)
    blocks = blocks + (Classifier("fc", cout, n_classes),)
    return SNNConfig("spike-resnet18", blocks, n_classes, in_res, in_ch, T)


def spike_resnet50(n_classes=10, in_res=32, T=4, width_mult=1.0,
                   in_ch=3) -> SNNConfig:
    blocks, cout = _resnet_blocks([3, 4, 6, 3], [64, 128, 256, 512], True,
                                  width_mult, in_ch)
    blocks = blocks + (Classifier("fc", cout, n_classes),)
    return SNNConfig("spike-resnet50", blocks, n_classes, in_res, in_ch, T)


def spike_vgg16(n_classes=10, in_res=32, T=4, width_mult=1.0,
                in_ch=3) -> SNNConfig:
    plan = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
            512, 512, 512, "M", 512, 512, 512, "M"]
    w = lambda c: max(int(c * width_mult), 8)
    blocks: list = []
    cin, i = in_ch, 0
    for item in plan:
        if item == "M":
            blocks.append(MaxPool(f"pool{i}"))
        else:
            blocks.append(ConvBNLif(f"conv{i}", cin, w(item), 3, 1))
            cin = w(item)
            i += 1
    blocks.append(Classifier("fc", cin, n_classes))
    return SNNConfig("spike-vgg16", tuple(blocks), n_classes, in_res, in_ch, T)


def spikformer(depth=8, dim=768, heads=12, mlp_ratio=4, n_classes=1000,
               in_res=224, in_ch=3, T=4, patch=16) -> SNNConfig:
    """Spikformer-``depth``-``dim``: SPS (four 3x3 Conv-BN-LIF with channels
    dim/8, dim/4, dim/2, dim, a 2x2 max-pool after each of the last
    log2(``patch``), then ``rpe``), ``depth`` transformer blocks of ``heads``
    heads and MLP ratio ``mlp_ratio``, global average pool and a linear head.
    ``patch=16`` at 224x224 is the ImageNet model (N = 196 tokens);
    ``patch=4`` at 32x32 the CIFAR one."""
    n_pools = patch.bit_length() - 1
    if patch < 1 or 1 << n_pools != patch or n_pools > 4:
        raise ValueError(f"patch must be 1, 2, 4, 8 or 16, got {patch}")
    if dim % 8 or dim % heads:
        raise ValueError(f"dim {dim} must divide by 8 and by heads {heads}")
    chans = (in_ch, dim // 8, dim // 4, dim // 2, dim)
    convs = tuple(ConvBNLif(f"sps{i}", chans[i], chans[i + 1], 3, 1)
                  for i in range(4))
    pools = tuple(i >= 4 - n_pools for i in range(4))
    blocks: list = [SPS("sps", convs, pools,
                        ConvBNLif("rpe", dim, dim, 3, 1))]
    hidden = dim * mlp_ratio
    for b in range(depth):
        lin = lambda n, i, o: LinearBNLif(f"b{b}{n}", i, o)  # noqa: E731
        attn = SpikingSelfAttention(f"b{b}attn", lin("q", dim, dim),
                                    lin("k", dim, dim), lin("v", dim, dim),
                                    lin("proj", dim, dim), heads)
        blocks.append(TransformerBlock(f"b{b}", attn, lin("fc1", dim, hidden),
                                       lin("fc2", hidden, dim)))
    blocks.append(Classifier("head", dim, n_classes))
    return SNNConfig(f"spikformer-{depth}-{dim}", tuple(blocks), n_classes,
                     in_res, in_ch, T)


# ---- specs / state / step ----------------------------------------------------

def _conv_unit_specs(u: ConvBNLif):
    return {"conv": L.conv_specs(u.cin, u.cout, u.k), "bn": L.bn_specs(u.cout)}


def _linear_unit_specs(u: LinearBNLif):
    return {"linear": L.linear_specs(u.din, u.dout), "bn": L.bn_specs(u.dout)}


def _forward_unsupported(b) -> None:
    if isinstance(b, (SPS, TransformerBlock)):
        raise NotImplementedError(
            f"{type(b).__name__} {b.name!r}: the BPTT forward covers the "
            "convolution stacks only; Spikformer descriptors are for "
            "profiling and placement")


def model_specs(cfg: SNNConfig):
    out: dict = {}
    for b in cfg.blocks:
        if isinstance(b, ConvBNLif):
            out[b.name] = _conv_unit_specs(b)
        elif isinstance(b, Residual):
            d = {u.name: _conv_unit_specs(u) for u in b.body}
            if b.downsample is not None:
                d[b.downsample.name] = _conv_unit_specs(b.downsample)
            out[b.name] = d
        elif isinstance(b, SPS):
            out[b.name] = {u.name: _conv_unit_specs(u)
                           for u in b.convs + (b.rpe,)}
        elif isinstance(b, TransformerBlock):
            a = b.attn
            out[b.name] = {u.name: _linear_unit_specs(u)
                           for u in (a.q, a.k, a.v, a.proj, b.fc1, b.fc2)}
        elif isinstance(b, Classifier):
            out[b.name] = L.linear_specs(b.din, b.dout)
    return out


def _shapes(cfg: SNNConfig, batch: int):
    """Walk descriptors tracking (H, W, C) to size LIF states."""
    h = w = cfg.in_res
    shapes = {}
    for b in cfg.blocks:
        _forward_unsupported(b)
        if isinstance(b, ConvBNLif):
            h = -(-h // b.stride)
            w = -(-w // b.stride)
            if b.spike_out:
                shapes[b.name] = (batch, h, w, b.cout)
        elif isinstance(b, Residual):
            for u in b.body:
                h2 = -(-h // u.stride)
                w2 = -(-w // u.stride)
                if u.spike_out:
                    shapes[u.name] = (batch, h2, w2, u.cout)
                h, w = h2, w2
            shapes[b.name] = (batch, h, w, b.body[-1].cout)   # post-add LIF
        elif isinstance(b, MaxPool):
            h = -(-h // b.stride)
            w = -(-w // b.stride)
    return shapes


def init_state(cfg: SNNConfig, batch: int, dtype=jnp.float32):
    """Per-LIF (membrane u, last spike s) carried across timesteps."""
    return {name: (jnp.zeros(s, dtype), jnp.zeros(s, dtype))
            for name, s in _shapes(cfg, batch).items()}


def _apply_unit(p, u: ConvBNLif, x, state, new_state, lif: LIFConfig):
    y = L.conv2d(p["conv"], x, stride=u.stride)
    y = L.batch_norm(p["bn"], y)
    if u.spike_out:
        mu, ms = state[u.name]
        mu, s = lif_step(mu, ms, y, lif)
        new_state[u.name] = (mu, s)
        return s
    return y


def model_step(params, cfg: SNNConfig, state, x):
    """One timestep: x [B,H,W,C] (analog or spikes) -> (new_state, logits)."""
    new_state: dict = {}
    h = x
    logits = None
    for b in cfg.blocks:
        _forward_unsupported(b)
        if isinstance(b, ConvBNLif):
            h = _apply_unit(params[b.name], b, h, state, new_state, cfg.lif)
        elif isinstance(b, Residual):
            r = h
            for u in b.body:
                r = _apply_unit(params[b.name][u.name], u, r, state, new_state,
                                cfg.lif)
            if b.downsample is not None:
                h = _apply_unit(params[b.name][b.downsample.name], b.downsample,
                                h, state, new_state, cfg.lif)
            y = r + h
            mu, ms = state[b.name]
            mu, s = lif_step(mu, ms, y, cfg.lif)
            new_state[b.name] = (mu, s)
            h = s
        elif isinstance(b, MaxPool):
            h = L.max_pool(h, b.k, b.stride)
        elif isinstance(b, Classifier):
            h = L.avg_pool_global(h)
            logits = L.linear(params[b.name], h)
    return new_state, logits


def model_rollout(params, cfg: SNNConfig, x):
    """x [B,H,W,C] static input (direct encoding), scanned over cfg.T steps.

    Returns mean logits over time [B, n_classes] and mean spike rate (aux).
    """
    state = init_state(cfg, x.shape[0], x.dtype)

    def body(state, _):
        new_state, logits = model_step(params, cfg, state, x)
        rate = sum(s.mean() for (_, s) in new_state.values()) / max(len(new_state), 1)
        return new_state, (logits, rate)

    _, (logits_t, rates) = jax.lax.scan(body, state, jnp.arange(cfg.T))
    return logits_t.mean(axis=0), rates.mean()
