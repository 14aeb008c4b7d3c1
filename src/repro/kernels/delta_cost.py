"""Tiled O(degree) swap-delta kernel for placement search (Pallas, TPU target).

A pairwise swap of two placement slots only perturbs the edges incident to
the (at most two) moved nodes, so the comm-cost change of a proposed swap is

    delta = sum_k vol[k] * (hops[src_after[k], dst_after[k]]
                            - hops[src_before[k], dst_before[k]])

over the K incident-edge entries the host gathers from
``noc_batch.IncidentTables`` (padding entries carry ``vol == 0``). The
device-resident SA chains of :mod:`repro.core.placement.device_search`
evaluate one such delta per chain per step; this kernel tiles the R chains in
blocks of up to 8 along the grid's first axis (a TPU block's second-to-last
dim must be a multiple of 8 or the whole axis; more than 8 chains are padded
to a multiple of 8 with weight-0 rows) and recasts both hop gathers as
one-hot matmuls so they map straight onto the MXU (same trick as
``noc_segsum``): for each tile of ``bk`` entries of a chain,
``one_hot(src) @ hops`` pulls the needed hop-matrix rows and a masked row-sum
against ``one_hot(dst)`` selects the column — no dynamic-index gathers, which
lower poorly on TPU.

The core axis is padded to a lane multiple (128); padded entries index core 0
with weight 0. The whole padded ``[Cp, Cp]`` hop matrix is one VMEM block, so
the kernel fits v5e up to 1024 cores (``device_search`` takes the gather path
beyond). Accumulation is float32 in VMEM scratch rows, flushed on the last
k-step (init/flush idiom of ``noc_segsum``/``spike_matmul``). On CPU the
kernel runs in interpret mode; on TPU the same code compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .noc_segsum import _round_up, _row_tile


def _delta_kernel(src_b_ref, dst_b_ref, src_a_ref, dst_a_ref, vol_ref,
                  hops_ref, o_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hops = hops_ref[...]                                 # [Cp, Cp] float32
    cp = hops.shape[1]
    bb, bk = vol_ref.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (bk, cp), 1)

    def gather(s_ref, d_ref, r):
        # hops[s, d] per entry: one-hot(s) @ hops selects rows on the MXU,
        # the masked row-sum against one-hot(d) selects the column. Hop
        # counts are small integers, exact in a single bf16 MXU pass.
        oh_s = (s_ref[r:r + 1, :].reshape(bk, 1) == iota).astype(jnp.float32)
        rows = jnp.dot(oh_s, hops, preferred_element_type=jnp.float32)
        oh_d = (d_ref[r:r + 1, :].reshape(bk, 1) == iota).astype(jnp.float32)
        return jnp.sum(rows * oh_d, axis=1, keepdims=True)   # [bk, 1]

    for r in range(bb):                  # static unroll over the chain tile
        diff = (gather(src_a_ref, dst_a_ref, r)
                - gather(src_b_ref, dst_b_ref, r))
        acc_ref[r:r + 1, :] += jnp.sum(
            vol_ref[r:r + 1, :].reshape(bk, 1) * diff)

    @pl.when(k_idx == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def delta_cost_pallas(src_b, dst_b, src_a, dst_a, vol, hops, *,
                      block_k: int = 256, interpret: bool = False):
    """Per-chain swap deltas ``[R]`` from incident-edge entry tables.

    src_b/dst_b/src_a/dst_a [R, K] int32 core ids in ``[0, C)`` (before/after
    endpoints of each incident edge; padding may index any valid core), vol
    [R, K] float weights (0 on padding), hops [C, C] hop matrix. Returns
    float32 ``[R]`` = sum(vol * (hops[after] - hops[before])) per chain.
    """
    R, K = vol.shape
    C = hops.shape[0]
    assert hops.shape == (C, C), hops.shape
    for a in (src_b, dst_b, src_a, dst_a):
        assert a.shape == (R, K), (a.shape, (R, K))
    cp = _round_up(C, 128)
    hops_p = jnp.zeros((cp, cp), jnp.float32).at[:C, :C].set(
        hops.astype(jnp.float32))
    bk = min(block_k, _round_up(max(K, 1), 128))
    Kp = _round_up(max(K, 1), bk)
    bb, Rp = _row_tile(R)
    if (Rp, Kp) != (R, K):
        pad = ((0, Rp - R), (0, Kp - K))
        src_b, dst_b, src_a, dst_a = (jnp.pad(a, pad)
                                      for a in (src_b, dst_b, src_a, dst_a))
        vol = jnp.pad(vol, pad)
    n_k = Kp // bk
    kern = functools.partial(_delta_kernel, n_k=n_k)
    ent = pl.BlockSpec((bb, bk), lambda r, k: (r, k))
    out = pl.pallas_call(
        kern,
        grid=(Rp // bb, n_k),
        in_specs=[ent, ent, ent, ent, ent,
                  pl.BlockSpec((cp, cp), lambda r, k: (0, 0))],
        out_specs=pl.BlockSpec((bb, 128), lambda r, k: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, 128), jnp.float32)],
        interpret=interpret,
    )(src_b.astype(jnp.int32), dst_b.astype(jnp.int32),
      src_a.astype(jnp.int32), dst_a.astype(jnp.int32),
      vol.astype(jnp.float32), hops_p)
    return out[:R, 0]
