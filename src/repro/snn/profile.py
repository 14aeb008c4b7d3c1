"""Per-layer cost profiles feeding the partitioner (paper §4.2 step 1:
"calculate computational operations and memory requirements of each layer").

Spike-specific accounting:
* forward conv on binary spikes = accumulate-only ops (the FP engine's
  selector+adder), counted as ``flops × spike_density`` effective ACs;
* inter-layer traffic is spike *bits*, not FP16 activations (1 bit/neuron/step),
  except the analog stem input;
* training triples the pass count (FP + BP + WG, Fig 3), with BP/WG on FP16 data.

Spikformer (``SPS`` + ``TransformerBlock``) adds:

* token-wise Linear-BN-LIF units (Q, K, V, proj, fc1, fc2) profiled as 1x1
  conv units over the N = H·W tokens;
* the attention unit, which holds no weights: macs = 2·N²·D (Q·Kᵀ, then ·V,
  summed over heads), flops by the conv rule on those macs (2·macs·density
  forward, +4·macs when training, the gradients of both operands), ``c_out``
  = the number of heads so a slice holds whole heads, out_bytes = N·D spike
  bits plus FP16 gradients;
* residual-stream outputs (``rpe``, proj and fc2, each after its add) carry
  1 byte per element forward, since the stream holds sums of at most
  2·depth+1 spikes, plus FP16 gradients;
* producers: each unit names the units it reads, ``"full"`` (a contraction
  over input channels: every producer slice sends its whole shard to every
  consumer slice) or ``"aligned"`` (per channel or per head: producer slice
  i sends to consumer slice j only where their channel ranges overlap), see
  :meth:`repro.core.partition.Partition.to_graph`. A unit that lists none
  reads the previous unit in full, so every convolution stack stays a chain.
"""
from __future__ import annotations

from ..core.partition import LayerProfile
from .models import (Classifier, ConvBNLif, LinearBNLif, MaxPool, Residual,
                     SNNConfig, SPS, TransformerBlock)


def _train_flops(macs: float, spike_density: float, training: bool) -> float:
    fwd = 2.0 * macs * spike_density            # ACs on spiking inputs
    flops = fwd
    if training:
        flops += 2 * 2.0 * macs                 # BP (dense) + WG passes
    return flops


def _out_bytes(elems: int, training: bool, residual: bool = False) -> float:
    # 1 spike bit per neuron; a residual-stream sum takes 1 byte
    out_bytes = elems * 1.0 if residual else elems / 8.0
    if training:                                # BP sends FP16 grads back
        out_bytes += elems * 2.0
    return out_bytes


def _conv_profile(u: ConvBNLif, h: int, w: int, T: int, spike_density: float,
                  training: bool, batch: int, residual: bool = False):
    ho, wo = -(-h // u.stride), -(-w // u.stride)
    macs = ho * wo * u.cin * u.cout * u.k * u.k
    flops = _train_flops(macs, spike_density, training)
    out_bytes = _out_bytes(ho * wo * u.cout, training, residual)
    return (flops * T * batch,
            u.k * u.k * u.cin * u.cout * 2.0,   # FP16 weights
            out_bytes * T * batch, ho, wo)


def profile_model(cfg: SNNConfig, batch: int = 1, spike_density: float = 0.15,
                  training: bool = True):
    """Returns list[LayerProfile]; one entry per conv/fc/linear unit (BN
    folded in) and one per spiking self-attention."""
    h = w = cfg.in_res
    profiles = []

    def add_unit(u: ConvBNLif, h, w):
        flops, wbytes, obytes, ho, wo = _conv_profile(
            u, h, w, cfg.T, spike_density, training, batch)
        profiles.append(LayerProfile(u.name, flops, wbytes, obytes,
                                     c_in=u.cin, c_out=u.cout))
        return ho, wo

    def add_linear(u: LinearBNLif, producers, residual=False):
        # a 1x1 conv over the h*w tokens
        conv = ConvBNLif(u.name, u.din, u.dout, 1, 1)
        flops, wbytes, obytes, _, _ = _conv_profile(
            conv, h, w, cfg.T, spike_density, training, batch, residual)
        profiles.append(LayerProfile(u.name, flops, wbytes, obytes,
                                     c_in=u.din, c_out=u.dout,
                                     producers=producers))

    x = None                                    # the residual stream's unit
    for b in cfg.blocks:
        if isinstance(b, SPS):
            for u, pool in zip(b.convs, b.pool_after):
                h, w = add_unit(u, h, w)
                if pool:
                    h, w = -(-h // 2), -(-w // 2)
            # rpe reads the last conv in full; its residual operand is that
            # same tensor, which the edge already carries
            flops, wbytes, obytes, h, w = _conv_profile(
                b.rpe, h, w, cfg.T, spike_density, training, batch,
                residual=True)
            profiles.append(LayerProfile(b.rpe.name, flops, wbytes, obytes,
                                         c_in=b.rpe.cin, c_out=b.rpe.cout))
            x = b.rpe.name
        elif isinstance(b, TransformerBlock):
            a, n_tok = b.attn, h * w
            for u in (a.q, a.k, a.v):
                add_linear(u, ((x, "full"),))
            macs = 2 * n_tok * n_tok * a.q.dout
            profiles.append(LayerProfile(
                a.name, _train_flops(macs, spike_density, training)
                * cfg.T * batch, 0.0,
                _out_bytes(n_tok * a.q.dout, training) * cfg.T * batch,
                c_in=a.q.dout, c_out=a.heads,
                producers=tuple((u.name, "aligned") for u in (a.q, a.k, a.v))))
            add_linear(a.proj, ((a.name, "full"), (x, "aligned")),
                       residual=True)
            add_linear(b.fc1, ((a.proj.name, "full"),))
            add_linear(b.fc2, ((b.fc1.name, "full"), (a.proj.name, "aligned")),
                       residual=True)
            x = b.fc2.name
        elif isinstance(b, ConvBNLif):
            h, w = add_unit(b, h, w)
        elif isinstance(b, Residual):
            hh, ww = h, w
            for u in b.body:
                hh, ww = add_unit(u, hh, ww)
            if b.downsample is not None:
                add_unit(b.downsample, h, w)
            h, w = hh, ww
        elif isinstance(b, MaxPool):
            h, w = -(-h // b.stride), -(-w // b.stride)
        elif isinstance(b, Classifier):
            flops = 2.0 * b.din * b.dout * cfg.T * batch
            if training:
                flops *= 3
            profiles.append(LayerProfile(b.name, flops, b.din * b.dout * 2.0,
                                         b.dout * 2.0 * cfg.T * batch,
                                         c_in=b.din, c_out=b.dout))
    return profiles
