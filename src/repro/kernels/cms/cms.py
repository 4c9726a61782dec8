"""Pallas TPU kernels for the batched TinyLFU count-min sketch.

TPU adaptation of the paper's hot data structure (DESIGN.md §3). The
per-access increment of the chunk scan touches one key's counters only:

* update one (in place): the key's ROWS row indexes and a 0/1 ``valid``
  are scalar-prefetched into SMEM; the table stays in HBM, aliased to the
  output. Each counter's [ROWS, 128] HBM tile (the table's tiling; a single
  row cannot be moved alone) is fetched into VMEM, every row's increment
  that falls in it is applied, and the tiles are written back. Its cost
  does not grow with the width.

Batches of keys use dense, lane-aligned VPU work over the whole table —
per width-block one-hot comparisons:

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

The sweeps keep the table block (BW lanes) and the key-index vectors in
VMEM; their grids iterate width-blocks. All kernels are validated against
ref.py in interpret mode across shape/dtype sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import ROWS

DEFAULT_BLOCK_W = 512
#: lanes of one HBM tile of the table; a tile spans all ROWS rows
TILE_W = 128


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


def _update_one_kernel(idx_ref, valid_ref, table_hbm, out_hbm, tiles, sems, *, cap: int):
    """One key's increment, in place. ``idx_ref`` [ROWS] and ``valid_ref``
    [1] are in SMEM; the table stays in HBM (``out_hbm`` aliases
    ``table_hbm``). Row ``r``'s counter lies in the [ROWS, TILE_W] tile at
    column ``cols[r]``: fetch the four tiles, apply in each every row's
    increment that falls in it (tiles at the same columns then hold the
    same bytes), write them back."""
    del table_hbm  # the same buffer as out_hbm
    cols = [pl.multiple_of(idx_ref[r] // TILE_W * TILE_W, TILE_W) for r in range(ROWS)]

    def copy(r, src, dst):
        return pltpu.make_async_copy(src, dst, sems.at[r])

    reads = [copy(r, out_hbm.at[:, pl.ds(cols[r], TILE_W)], tiles.at[r]) for r in range(ROWS)]
    for c in reads:
        c.start()
    for c in reads:
        c.wait()
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, TILE_W), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, TILE_W), 1)
    valid = valid_ref[0]
    for t in range(ROWS):
        hit = jnp.zeros((ROWS, TILE_W), jnp.bool_)
        for r in range(ROWS):
            at = jnp.where(cols[r] == cols[t], idx_ref[r] - cols[r], -1)
            hit = hit | ((row == r) & (lane == at))
        tile = tiles[t]
        tiles[t] = jnp.where(hit, jnp.minimum(tile + valid, cap), tile)
    writes = [copy(r, tiles.at[r], out_hbm.at[:, pl.ds(cols[r], TILE_W)]) for r in range(ROWS)]
    for c in writes:
        c.start()
    for c in writes:
        c.wait()


def cms_update_one_pallas(table, idx, valid, *, cap: int = 15, interpret: bool = True):
    """Increment one key's ``ROWS`` counters in place: ``idx`` [ROWS] int32
    holds its row indexes, ``valid`` (0 or 1) the increment. Reads and
    writes only the [ROWS, TILE_W] tiles that hold the four counters, so
    its cost does not grow with the width. Returns the table (the input
    buffer, aliased), equal to ``cms_update_pallas`` of that key when
    ``valid`` and unchanged otherwise (counters at most ``cap``, as every
    increment leaves them)."""
    rows, width = table.shape
    assert rows == ROWS and width % TILE_W == 0
    return pl.pallas_call(
        functools.partial(_update_one_kernel, cap=cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((ROWS, ROWS, TILE_W), table.dtype),
                            pltpu.SemaphoreType.DMA((ROWS,))],
        ),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="cms_update_one",
    )(idx.astype(jnp.int32), jnp.asarray(valid, jnp.int32).reshape(1), table)
