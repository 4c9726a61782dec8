"""Pallas TPU kernels for the batched TinyLFU count-min sketch.

TPU adaptation of the paper's hot data structure (DESIGN.md §3): instead of
pointer-chasing per key, a batch of N keys is processed with dense,
lane-aligned VPU work — per width-block one-hot comparisons:

* update: for each table block [ROWS, BW], add the number of keys hashing
  into each cell (broadcasted iota==index compare, summed over keys),
  saturating at ``cap``. Each key's cell falls in exactly one block, so the
  grid over width-blocks partitions the work.
* estimate: per block, accumulate (idx == w) * table[w] into [ROWS, N]
  partials; min over rows taken by the jnp wrapper.
* update+estimate (fused): both of the above in one grid pass — the batch of
  pending increments is applied to each block and the estimate keys gather
  from the *updated* block, so an admission decision's sketch flush and
  victim scoring land in a single kernel launch.

The table block (BW lanes) and the key-index vectors live in VMEM; grids
iterate width-blocks. Both kernels are validated against ref.py in
interpret mode across shape/dtype sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import ROWS

DEFAULT_BLOCK_W = 512


def _update_kernel(idx_ref, table_ref, out_ref, *, cap: int, block_w: int):
    """Grid dim 0 = width blocks. idx [ROWS, N]; table/out block [ROWS, BW]."""
    wstart = pl.program_id(0) * block_w
    idx = idx_ref[...]  # [ROWS, N]
    table = table_ref[...]  # [ROWS, BW]
    local = idx - wstart  # position within this block (may be out of range)
    # counts[r, w] = #keys with local[r, k] == w
    w_iota = jax.lax.broadcasted_iota(jnp.int32, (ROWS, idx.shape[1], block_w), 2)
    hit = (local[:, :, None] == w_iota).astype(table.dtype)  # [ROWS, N, BW]
    counts = hit.sum(axis=1)  # [ROWS, BW]
    out_ref[...] = jnp.minimum(table + counts, cap)


def _estimate_kernel(idx_ref, table_ref, out_ref, *, block_w: int):
    """Accumulates per-block partial estimates into out [ROWS, N]."""
    wi = pl.program_id(0)
    wstart = wi * block_w
    idx = idx_ref[...]  # [ROWS, N]
    table = table_ref[...]  # [ROWS, BW]
    local = idx - wstart
    in_block = (local >= 0) & (local < block_w)
    w_iota = jax.lax.broadcasted_iota(jnp.int32, (ROWS, idx.shape[1], block_w), 2)
    hit = (local[:, :, None] == w_iota).astype(table.dtype)
    vals = (hit * table[:, None, :]).sum(axis=2)  # [ROWS, N]
    vals = jnp.where(in_block, vals, 0)

    @pl.when(wi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += vals


def _update_estimate_kernel(upd_ref, est_ref, table_ref, out_table_ref, out_vals_ref,
                            *, cap: int, block_w: int):
    """Fused flush + score: add the update-batch counts to this width block,
    then gather the estimate keys from the *updated* block. One grid pass
    replaces an update call followed by an estimate call — the admission
    data plane issues exactly one kernel launch per decision."""
    wi = pl.program_id(0)
    wstart = wi * block_w
    table = table_ref[...]  # [ROWS, BW]

    upd = upd_ref[...]  # [ROWS, M]
    u_local = upd - wstart
    u_iota = jax.lax.broadcasted_iota(jnp.int32, (ROWS, upd.shape[1], block_w), 2)
    u_hit = (u_local[:, :, None] == u_iota).astype(table.dtype)  # [ROWS, M, BW]
    new_table = jnp.minimum(table + u_hit.sum(axis=1), cap)
    out_table_ref[...] = new_table

    est = est_ref[...]  # [ROWS, N]
    e_local = est - wstart
    in_block = (e_local >= 0) & (e_local < block_w)
    e_iota = jax.lax.broadcasted_iota(jnp.int32, (ROWS, est.shape[1], block_w), 2)
    e_hit = (e_local[:, :, None] == e_iota).astype(table.dtype)
    vals = (e_hit * new_table[:, None, :]).sum(axis=2)  # [ROWS, N]
    vals = jnp.where(in_block, vals, 0)

    @pl.when(wi == 0)
    def _init():
        out_vals_ref[...] = jnp.zeros_like(out_vals_ref)

    out_vals_ref[...] += vals


def cms_update_pallas(table, idx, *, cap: int = 15, block_w: int = DEFAULT_BLOCK_W,
                      interpret: bool = True):
    """table [ROWS, W] int32; idx [ROWS, N] int32 (precomputed row indexes)."""
    rows, width = table.shape
    block_w = min(block_w, width)
    assert rows == ROWS and width % block_w == 0
    grid = (width // block_w,)
    return pl.pallas_call(
        functools.partial(_update_kernel, cap=cap, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec(idx.shape, lambda w: (0, 0)),  # full idx each block
            pl.BlockSpec((ROWS, block_w), lambda w: (0, w)),
        ],
        out_specs=pl.BlockSpec((ROWS, block_w), lambda w: (0, w)),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        interpret=interpret,
        name="cms_update",
    )(idx, table)


def cms_estimate_pallas(table, idx, *, block_w: int = DEFAULT_BLOCK_W,
                        interpret: bool = True):
    """Returns [ROWS, N] per-row gathered counters (min taken by caller)."""
    rows, width = table.shape
    block_w = min(block_w, width)
    assert rows == ROWS and width % block_w == 0
    grid = (width // block_w,)
    return pl.pallas_call(
        functools.partial(_estimate_kernel, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec(idx.shape, lambda w: (0, 0)),
            pl.BlockSpec((ROWS, block_w), lambda w: (0, w)),
        ],
        out_specs=pl.BlockSpec(idx.shape, lambda w: (0, 0)),  # accumulated
        out_shape=jax.ShapeDtypeStruct(idx.shape, table.dtype),
        interpret=interpret,
        name="cms_estimate",
    )(idx, table)


def cms_update_estimate_pallas(table, upd_idx, est_idx, *, cap: int = 15,
                               block_w: int = DEFAULT_BLOCK_W, interpret: bool = True):
    """Fused update + estimate: apply ``upd_idx`` [ROWS, M] increments, then
    gather ``est_idx`` [ROWS, N] counters from the updated table, in one
    kernel launch. Returns ``(new_table [ROWS, W], vals [ROWS, N])`` (min over
    rows taken by the caller) — identical results to ``cms_update_pallas``
    followed by ``cms_estimate_pallas``."""
    rows, width = table.shape
    block_w = min(block_w, width)
    assert rows == ROWS and width % block_w == 0
    grid = (width // block_w,)
    return pl.pallas_call(
        functools.partial(_update_estimate_kernel, cap=cap, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec(upd_idx.shape, lambda w: (0, 0)),
            pl.BlockSpec(est_idx.shape, lambda w: (0, 0)),
            pl.BlockSpec((ROWS, block_w), lambda w: (0, w)),
        ],
        out_specs=(
            pl.BlockSpec((ROWS, block_w), lambda w: (0, w)),
            pl.BlockSpec(est_idx.shape, lambda w: (0, 0)),  # accumulated
        ),
        out_shape=(
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct(est_idx.shape, table.dtype),
        ),
        interpret=interpret,
        name="cms_update_estimate",
    )(upd_idx, est_idx, table)
