"""Tiled segment-sum kernel for per-link NoC traffic (Pallas, TPU target).

``noc_batch`` reduces per-link traffic to a segment-sum: every edge of every
placement contributes its volume to each directed link on its route, with
routes stored as padded link-id tables (pad id == ``n_links``). The jax
backend's ``.at[ids].add`` scatter lowers poorly on TPU; this kernel recasts
the reduction as a sequence of one-hot matmuls, which map straight onto the
MXU: for each tile of ``bk`` (edge, hop) entries of a row, build the one-hot
matrix ``[bk, n_links_padded]`` from the link ids and accumulate
``w_tile @ one_hot`` into a VMEM accumulator — a [1, bk] × [bk, L] matmul per
row of the block, flushed to the output rows on the last k-step (same
init/flush idiom as ``spike_matmul``).

Blocks hold up to 8 population rows (a TPU block's second-to-last dim must be
a multiple of 8 or the whole axis); a population above 8 rows is padded to a
multiple of 8 with weight-0 rows. The link axis is padded to a lane multiple
(128) with at least one extra column so route padding (id == n_links) lands in
a dropped column; (edge, hop) padding added to reach a block multiple uses
weight 0. On CPU the kernel runs in interpret mode (like the other kernels in
this package); on TPU the same code compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segsum_kernel(ids_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bb, bk = ids_ref.shape                               # [bb, bk] int32
    lanes = acc_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bk, lanes), 1)
    for r in range(bb):                  # static unroll over the row tile
        one_hot = (ids_ref[r:r + 1, :].reshape(bk, 1) == iota).astype(
            jnp.float32)
        # HIGHEST: volumes are large floats, a single bf16 MXU pass would
        # round them; the one-hot side is exact either way
        acc_ref[r:r + 1, :] += jnp.dot(
            w_ref[r:r + 1, :].astype(jnp.float32), one_hot,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _row_tile(rows: int) -> tuple[int, int]:
    """(row block, padded rows): a TPU block's second-to-last dim must be a
    multiple of 8 or the whole axis, so up to 8 rows form one block and more
    are padded to a multiple of 8."""
    if rows <= 8:
        return rows, rows
    return 8, _round_up(rows, 8)


def link_traffic_pallas(ids, w, n_links: int, *, block_k: int = 256,
                        interpret: bool = False):
    """Segment-sum ``w`` over ``ids`` into ``[B, n_links]`` link traffic.

    ids [B, K] int32 link ids in ``[0, n_links]`` (``n_links`` == padding,
    dropped); w [B, K] float weights. Returns float32 ``[B, n_links]``.
    """
    B, K = ids.shape
    assert w.shape == (B, K), (ids.shape, w.shape)
    lanes = _round_up(n_links + 1, 128)                  # pad column survives
    bk = min(block_k, _round_up(max(K, 1), 128))
    Kp = _round_up(max(K, 1), bk)
    bb, Bp = _row_tile(B)
    if (Bp, Kp) != (B, K):
        pad = ((0, Bp - B), (0, Kp - K))
        ids = jnp.pad(ids, pad, constant_values=n_links)
        w = jnp.pad(w, pad)
    n_k = Kp // bk
    kern = functools.partial(_segsum_kernel, n_k=n_k)
    out = pl.pallas_call(
        kern,
        grid=(Bp // bb, n_k),
        in_specs=[pl.BlockSpec((bb, bk), lambda b, k: (b, k)),
                  pl.BlockSpec((bb, bk), lambda b, k: (b, k))],
        out_specs=pl.BlockSpec((bb, lanes), lambda b, k: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, lanes), jnp.float32)],
        interpret=interpret,
    )(ids.astype(jnp.int32), w)
    return out[:B, :n_links]
