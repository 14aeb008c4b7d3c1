"""Swap-delta kernel of the device SA: per-chain row selects (Pallas, TPU).

A pairwise swap of two placement slots only perturbs the edges incident to
the (at most two) moved nodes, so the comm-cost change of a proposed swap is

    delta = sum_k vol[k] * (hops[src_after[k], dst_after[k]]
                            - hops[src_before[k], dst_before[k]])

over the incident-edge entries of both nodes (``noc_batch.IncidentTables``;
padding entries carry ``vol == 0``). The device-resident SA chains of
:mod:`repro.core.placement.device_search` evaluate one such delta per chain
per step, all ``R`` chains in one call.

Only the two moved cores ``ci`` and ``cj`` change place, so every hop a
chain needs lies in four vectors: the rows and columns of ``hops`` at ``ci``
and ``cj``. Per chain the kernel reads those (rows of ``hops`` and of its
transpose) and the two nodes' rows of the incident tables with dynamic
sublane loads, exact for any float32 value. An entry's partner core is a
lane select on the chain's slot row; its hop before and after the swap is a
lane select on the moved core's row (the node is the edge's source) or
column (its destination). The work per chain is ``2·D`` entries against the
``S`` slots and ``C`` cores, with no padding of the entry axis.

The incident tables, ``hops`` and ``hopsᵀ`` are whole-array blocks with a
block index that never changes, fetched once per call; the moved nodes and
cores of every chain arrive by scalar prefetch. One grid step loops over the
chains. Each chain's products ``vol·(after − before)`` are summed as one
column, ``[node a's D entries, node b's D entries]`` padded with zeros to a
multiple of 128, in tiles of up to 256: the float32 order of the one-hot
MXU kernel this one replaced, whose deltas it reproduces bit for bit on a
v5e. On CPU the kernel runs in interpret mode; on TPU the same code compiles
to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .noc_segsum import _round_up


#: Chains per iteration of the kernel's loop, unrolled so that one chain's
#: loads overlap another's arithmetic: on a v5e, 64 chains of degree 4 take
#: 35.8 us a step one at a time and 21.8 us eight at a time (degree 56:
#: 51.5 and 48.5 us); all 64 unrolled gain little more and compile longer.
CHAINS_PER_ITER = 8


def incident_keys(other, is_src):
    """One int32 table for the kernel: ``2·other + is_src`` per entry."""
    return other.astype(jnp.int32) * 2 + is_src.astype(jnp.int32)


def _delta_kernel(a_ref, b_ref, ci_ref, cj_ref, slots_ref, key_ref, vol_ref,
                  hops_ref, hops_t_ref, o_ref, *, bk: int, n_k: int):
    R, S = slots_ref.shape
    D = key_ref.shape[1]
    C = hops_ref.shape[1]
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (D, S), 1)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (D, C), 1)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    pad = jnp.zeros((n_k * bk - 2 * D, 1), jnp.float32)

    def chain(r):
        a, b, ci, cj = a_ref[r], b_ref[r], ci_ref[r], cj_ref[r]
        slot_row = slots_ref[pl.ds(r, 1), :]                      # [1, S]
        row_i, row_j = hops_ref[pl.ds(ci, 1), :], hops_ref[pl.ds(cj, 1), :]
        col_i = hops_t_ref[pl.ds(ci, 1), :]
        col_j = hops_t_ref[pl.ds(cj, 1), :]
        # the a-b edge: both endpoints move, hops[ci, cj] <-> hops[cj, ci]
        h_ij = jnp.sum(jnp.where(lane_c == cj, row_i, 0.0), keepdims=True)
        h_ji = jnp.sum(jnp.where(lane_c == ci, row_j, 0.0), keepdims=True)

        def half(u, d_row, d_col, moved):
            # node u's entries as a column. An entry's hop after the swap
            # minus before is (d_row if u is the source else d_col) at the
            # partner's core, the exact difference of the two hop values:
            # the one lane that matches carries it, every other adds 0
            key = key_ref[pl.ds(u, 1), :].reshape(D, 1)
            vol = vol_ref[pl.ds(u, 1), :].reshape(D, 1)
            oth, is_src = key >> 1, (key & 1) == 1
            oc = jnp.sum(jnp.where(iota_s == oth, slot_row, 0), axis=1,
                         keepdims=True)                           # [D, 1]
            pick = jnp.where(iota_c == oc, jnp.where(is_src, d_row, d_col),
                             0.0)
            diff = jnp.sum(pick, axis=1, keepdims=True)
            if moved is not None:       # the partner is the other moved node
                diff = jnp.where(oth == b, jnp.where(is_src, *moved), diff)
            # a-b edges count once, in a's half (in a's own half
            # ``oth == a`` only hits padding, already volume 0)
            return jnp.where(oth == a, 0.0, vol) * diff

        prod = jnp.concatenate(
            [half(a, row_j - row_i, col_j - col_i,
                  (h_ji - h_ij, h_ij - h_ji)),
             half(b, row_i - row_j, col_i - col_j, None), pad], axis=0)
        total = jnp.sum(prod[:bk], keepdims=True)
        for t in range(1, n_k):
            total = total + jnp.sum(prod[t * bk:(t + 1) * bk], keepdims=True)
        o_ref[pl.ds(r, 1), :] = jnp.broadcast_to(total, (1, o_ref.shape[1]))

    def group(g, carry):
        for t in range(CHAINS_PER_ITER):
            chain(g * CHAINS_PER_ITER + t)
        return carry

    jax.lax.fori_loop(0, R // CHAINS_PER_ITER, group, 0)
    for r in range(R - R % CHAINS_PER_ITER, R):
        chain(r)


def delta_cost_pallas(slots, i, j, inc_key, inc_vol, hops, hops_t, *, n: int,
                      interpret: bool = False):
    """Per-chain comm-cost deltas ``[R]`` of swapping ``slots[r, i[r]]`` and
    ``slots[r, j[r]]``.

    slots [R, S] int32 core of each slot (entries ``[0, n)`` are the graph's
    nodes, the rest free cores); i, j [R] slot indices; inc_key [n+1, D]
    :func:`incident_keys` of the incident tables and inc_vol [n+1, D] their
    volumes (row ``n`` the all-padding sentinel row a free slot resolves
    to); hops [C, C] the hop matrix and hops_t its transpose. Returns
    float32 ``[R]`` = sum(vol * (hops[after] - hops[before])) per chain.
    """
    slots, i, j = (jnp.asarray(x, jnp.int32) for x in (slots, i, j))
    R, S = slots.shape
    D = inc_key.shape[1]
    C = hops.shape[0]
    assert inc_vol.shape == inc_key.shape == (n + 1, D), (inc_vol.shape, n)
    assert hops.shape == hops_t.shape == (C, C), (hops.shape, hops_t.shape)
    K = 2 * D
    bk = min(256, _round_up(K, 128))
    rows = jnp.arange(R)
    ci, cj = slots[rows, i], slots[rows, j]
    a = jnp.where(i < n, i, n).astype(jnp.int32)    # node id or sentinel n
    b = jnp.where(j < n, j, n).astype(jnp.int32)

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, *_: (0,) * len(shape))

    kern = functools.partial(_delta_kernel, bk=bk, n_k=-(-K // bk))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[whole((R, S)), whole((n + 1, D)), whole((n + 1, D)),
                      whole((C, C)), whole((C, C))],
            out_specs=whole((R, 128))),
        out_shape=jax.ShapeDtypeStruct((R, 128), jnp.float32),
        interpret=interpret,
    )(a, b, ci, cj, slots, *(jnp.asarray(x, t) for x, t in (
        (inc_key, jnp.int32), (inc_vol, jnp.float32), (hops, jnp.float32),
        (hops_t, jnp.float32))))
    return out[:, 0]
