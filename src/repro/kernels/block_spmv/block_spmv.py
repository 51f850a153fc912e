"""Block-sparse SpMV Pallas TPU kernel — the PageRank pull hot-spot on the MXU.

Hardware adaptation (DESIGN.md §2): a CPU/GPU CSR gather loop has no MXU
mapping.  Instead the adjacency is partitioned into dense B×B tiles and only
non-empty tiles are stored.  Per destination row-block, the kernel walks its
(padded) tile list via *scalar-prefetched* indices and accumulates

    acc[rows of i] += A_tile(i, j) @ c[cols of tile j]

entirely in VMEM, writing each output block exactly once.  The same kernel in
the OR-semiring (saturating accumulation) implements the Dynamic Frontier
expansion ("mark out-neighbors of changed vertices") on the transposed tiles.

Grid = (K row-blocks of one launch, max_tiles_per_row); the tile loop is
innermost so the output block stays resident in VMEM across the
accumulation (standard Pallas revisiting pattern).  Padded slots carry
column id -1 and are masked.

Every operand is lane-dense.  A tile is stored as its B² entries in rows
of L = 128 lanes (``ops.tile_shape``: B=64 → [32, 128]), x of a column
block as a [g, L] operand with g = L / B copies of its B entries on the
diagonal, and one contraction ``x_op · tileᵀ`` yields the block's output
as [g, R] (row q·g + h at [h, q]).  A [B, B] tile or a [B, 1] x slice
would pad to 128 lanes on a TPU.

Scalar memory (SMEM) is 1 MiB per core, so what a launch prefetches must
scale with the launch, never with the graph.  Before the call, XLA gathers
the slot-table rows of the launch's K row-blocks into two flat 1-D int32
tables of K·max_tiles entries (tile ids, column blocks); a launch whose
tables would exceed :data:`SMEM_PREFETCH_BUDGET` bytes is split into
sequential launches of :func:`launch_rows` row-blocks each.  Every index map
returns int32, so the kernels compile whether or not the process enables
x64.

VMEM working set per grid step: one tile + one x operand + one accumulator
≈ (B² + 2·g·L)·4 bytes → B=256 ⇒ ~260 KiB, far below the ~16 MiB VMEM
budget; B is kept a parameter (tests sweep 8..128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of scalar-prefetched slot tables one launch may hold: a quarter of
# the 1 MiB SMEM, leaving the rest to the compiler's own scalars
SMEM_PREFETCH_BUDGET = 256 * 1024


def launch_rows(max_tiles: int, smem_budget: int = SMEM_PREFETCH_BUDGET
                ) -> int:
    """Row-blocks per launch: the largest power of two K whose two int32
    prefetch tables (K·max_tiles entries each) fit ``smem_budget`` bytes."""
    per_row = 2 * max_tiles * 4
    k = 1
    while 2 * k * per_row <= smem_budget:
        k *= 2
    return k


def _accumulate(o_ref, part, j, *, semiring: str):
    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if semiring == "sum":
        o_ref[...] += part
    elif semiring == "or":
        # saturating OR: any positive contribution marks the row
        o_ref[...] = jnp.maximum(o_ref[...], jnp.minimum(part, 1.0))
    else:
        raise ValueError(semiring)


def _acc_dtype(dtype) -> jnp.dtype:
    """MXU accumulation dtype: f32 for f32/bf16 inputs, f64 for f64 ranks
    (f64 is the CPU/interpret validation path — TPU MXU has no f64)."""
    return jnp.dtype(jnp.float64) if dtype == jnp.float64 else jnp.float32


def _kernel(idx_ref, cols_ref, tiles_ref, w_ref, o_ref, *, semiring: str,
            max_tiles: int):
    j = pl.program_id(1)
    valid = cols_ref[pl.program_id(0) * max_tiles + j] >= 0
    tile = tiles_ref[...]        # [R, L]: the B×B tile, row-major, lane-dense
    w = w_ref[...]               # [g, L]: x of the tile's column block
    # part[h, q] = Σ_l w[h, l]·tile[q, l] = (A_tile @ x)[q·g + h].  HIGHEST:
    # the MXU's default single bf16 pass would round x = r/deg to 8 bits
    part = lax.dot_general(w, tile, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=_acc_dtype(w.dtype))
    part = jnp.where(valid, part, 0.0).astype(o_ref.dtype)
    _accumulate(o_ref, part, j, semiring=semiring)


def _x_operand(x, block: int, lanes: int):
    """x per column block as the kernel's [g, L] operand (g = L / B): row h
    holds the block's B entries in lanes [h·B, (h+1)·B) and zeros
    elsewhere, so one contraction with a lane-dense tile yields its g row
    groups at once."""
    g = lanes // block
    eye = jnp.eye(g, dtype=x.dtype)[None, :, :, None]
    return (eye * x.reshape(-1, 1, 1, block)).reshape(-1, g, lanes)


def _launch(idx, cols, tiles, xw, *, max_tiles: int, semiring: str,
            interpret: bool):
    """One pallas_call over K = len(idx) / max_tiles row-blocks whose slot
    rows are ``idx`` / ``cols`` (flat, int32).  Returns [K, g, R]."""
    k = idx.shape[0] // max_tiles
    rows, lanes = tiles.shape[1:]
    g = xw.shape[1]
    z = lambda: jnp.int32(0)        # int32 literals: x64 would make them i64

    def tile_map(i, j, idx, cols):
        return (idx[i * max_tiles + j], z(), z())

    def x_map(i, j, idx, cols):
        return (jnp.maximum(cols[i * max_tiles + j], 0), z(), z())

    def o_map(i, j, idx, cols):
        return (i, z(), z())

    gspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k, max_tiles),
        in_specs=[
            pl.BlockSpec((pl.squeezed, rows, lanes), tile_map),
            pl.BlockSpec((pl.squeezed, g, lanes), x_map),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, g, rows), o_map),
    )
    return pl.pallas_call(
        functools.partial(_kernel, semiring=semiring, max_tiles=max_tiles),
        grid_spec=gspec,
        out_shape=jax.ShapeDtypeStruct((k, g, rows), xw.dtype),
        interpret=interpret,
    )(idx, cols, tiles, xw)


def _chunked(idx, cols, tiles, x, *, block: int, max_tiles: int,
             semiring: str, interpret: bool, smem_budget: int):
    """Run the kernel over the K row-blocks named by the flat slot rows
    ``idx`` / ``cols`` ([K·max_tiles] int32), in launches of at most
    :func:`launch_rows` row-blocks.  Returns y_rows [K, B] (0/1 for "or")."""
    k = idx.shape[0] // max_tiles
    kc = launch_rows(max_tiles, smem_budget)
    run = functools.partial(
        _launch, tiles=tiles, xw=_x_operand(x, block, tiles.shape[2]),
        max_tiles=max_tiles, semiring=semiring, interpret=interpret)
    if k <= kc:
        y = run(idx, cols)
    else:
        n_ch = -(-k // kc)
        pad = (n_ch * kc - k) * max_tiles
        idx = jnp.pad(idx, (0, pad)).reshape(n_ch, kc * max_tiles)
        cols = jnp.pad(cols, (0, pad),
                       constant_values=-1).reshape(n_ch, kc * max_tiles)
        # one compiled kernel, launched once per chunk
        y = lax.map(lambda t: run(*t), (idx, cols))
        y = y.reshape((n_ch * kc,) + y.shape[2:])[:k]
    # [K, g, R] → [K, B]: entry (h, q) is row q·g + h of the block
    y = jnp.swapaxes(y, 1, 2).reshape(k, block)
    if semiring == "or":
        y = (y > 0).astype(x.dtype)
    return y


@functools.partial(jax.jit, static_argnames=("block", "max_tiles",
                                             "semiring", "interpret",
                                             "smem_budget"))
def block_spmv_pallas(tile_idx: jnp.ndarray,    # [n_rb * max_tiles] i32
                      tile_cols: jnp.ndarray,   # [n_rb, max_tiles]  i32 (-1 pad)
                      tiles: jnp.ndarray,       # [n_tiles, R, L]    f32
                      x: jnp.ndarray,           # [n_cb * B]         f32
                      *, block: int, max_tiles: int, semiring: str = "sum",
                      interpret: bool = False,
                      smem_budget: int = SMEM_PREFETCH_BUDGET
                      ) -> jnp.ndarray:
    """Returns y [n_rb * B] with y = A @ x (sum) or y = (A @ x > 0) (or)."""
    y = _chunked(tile_idx.astype(jnp.int32),
                 tile_cols.reshape(-1).astype(jnp.int32), tiles, x,
                 block=block, max_tiles=max_tiles, semiring=semiring,
                 interpret=interpret, smem_budget=smem_budget)
    return y.reshape(-1)


@functools.partial(jax.jit, static_argnames=("block", "max_tiles",
                                             "semiring", "interpret",
                                             "smem_budget"))
def block_spmv_active_pallas(active_ids: jnp.ndarray,  # [K] i32, -1 pad
                             tile_idx: jnp.ndarray,    # [n_rb * max_tiles] i32
                             tile_cols: jnp.ndarray,   # [n_rb, max_tiles] i32
                             tiles: jnp.ndarray,       # [n_tiles, R, L]
                             x: jnp.ndarray,           # [n_cb * B]
                             *, block: int, max_tiles: int,
                             semiring: str = "sum",
                             interpret: bool = False,
                             smem_budget: int = SMEM_PREFETCH_BUDGET
                             ) -> jnp.ndarray:
    """Frontier-compacted SpMV: only the row-blocks named in ``active_ids``
    are computed.  ``active_ids`` is a compacted slot list (active block ids
    first, then -1 padding) so the grid walks frontier blocks only.  XLA
    gathers the K slot rows first; a padded slot's row is all -1 / tile 0,
    so its block indices never change and the pipeline re-fetches nothing.

    Rows of blocks outside ``active_ids`` come back zero here, but callers
    must not rely on it: mask with the active-block indicator before use
    (the fused engine does).
    """
    n_rb = tile_cols.shape[0]
    ids = active_ids.astype(jnp.int32)
    live = ids >= 0
    rb = jnp.maximum(ids, 0)
    cols = jnp.where(live[:, None], tile_cols[rb], -1).astype(jnp.int32)
    idx = jnp.where(cols >= 0, tile_idx.reshape(n_rb, max_tiles)[rb],
                    0).astype(jnp.int32)
    y_act = _chunked(idx.reshape(-1), cols.reshape(-1), tiles, x,
                     block=block, max_tiles=max_tiles, semiring=semiring,
                     interpret=interpret, smem_budget=smem_budget)
    # padded slots land in the trash row n_rb
    out = jnp.zeros((n_rb + 1, block), x.dtype)
    out = out.at[jnp.where(live, ids, n_rb)].set(y_act)
    return out[:n_rb].reshape(-1)
