"""Block-sparse SpMV Pallas TPU kernel — the PageRank pull hot-spot.

Hardware adaptation (DESIGN.md §2): a CPU/GPU CSR gather loop has no
TPU mapping.  Instead the adjacency is partitioned into dense B×B tiles
and only non-empty tiles are stored.  Per destination row-block the
kernel walks the block's tile list via *scalar-prefetched* indices and
accumulates

    acc[rows of i] += A_tile(i, j) @ c[cols of tile j]

entirely in VMEM, writing each output block exactly once.  The same
kernel in the OR-semiring (saturating accumulation) implements the
Dynamic Frontier expansion ("mark out-neighbors of changed vertices") on
the transposed tiles.

Every operand is lane-dense.  A tile is stored as its B² entries in rows
of L lanes (``ops.tile_shape``: B=64 → [R, L] = [32, 128]); lane l of
tile row q holds entry (q·g + l // B, l % B) with g = L / B.  x of a
column block is a [1, L] row holding its B entries g times over, so
``tile * x_row`` is every product of the tile at once.  A [B, B] tile or
a [B, 1] x slice would pad to 128 lanes on a TPU.

**Grid and walk.**  The grid runs over the launch's K row-blocks,
:func:`rows_per_step` of them per step.  A row-block's live slots are a
prefix of its slot row (``ops._slot_tables`` gives slot ``t -
row_start``; deletions keep a tile, never open a hole), so the step walks
slots 0, 1, … of each of its rows and stops at the first column id -1.
A row with no live slot — empty, or the -1 padding of a ladder bucket —
costs no copy and writes zeros.  The tile loop multiplies on the VPU
(f32 products, exactly rounded) into an [R, L] accumulator; one
``Precision.HIGHEST`` contraction per row-block sums each lane group
into the block's [g, R] output (row q·g + h at [h, q]).

**DMA ring.**  ``tiles`` and the x rows stay in HBM (``pl.ANY``).  Each
live tile and its x row are copied (``make_async_copy``) into a VMEM ring
of :func:`ring_depth` slots.  One cursor walks the launch's live tiles
ahead of the compute, across row and step boundaries (SMEM scratch keeps
it between steps), so the copy of tile t + D - 1 is started before tile
t is consumed and HBM latency stays hidden; the ring restarts only per
launch.

**VMEM working set** per launch: the ring, D·(R·L + 8·L)·4 bytes (B=64,
D=8: 160 KiB), the double-buffered [rows, g, R] output blocks and the
[R, L] accumulator — constant in n and far below the 16 MiB scoped VMEM.

Scalar memory (SMEM) is 1 MiB per core, so what a launch prefetches must
scale with the launch, never with the graph.  Before the call, XLA
gathers the slot-table rows of the launch's K row-blocks into two flat
1-D int32 tables of K·max_tiles entries (tile ids, column blocks); a
launch whose tables would exceed :data:`SMEM_PREFETCH_BUDGET` bytes is
split into sequential launches of :func:`launch_rows` row-blocks each.
Every index is int32, so the kernel compiles whether or not the process
enables x64.
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

# tile slots one grid step covers: whole row-blocks of max_tiles slots
# (a step costs about a tile's walk; 8 rows of 16 slots amortise it)
STEP_SLOTS = 128

# bytes of tiles in flight: the ring holds this much, in 2 to 16 slots.
# On a v5e the walk hides HBM latency from 8 slots of 16 KiB on; a deeper
# ring gains nothing, as the per-tile copy issue then sets the pace
RING_BYTES = 128 * 1024


def launch_rows(max_tiles: int, smem_budget: int = SMEM_PREFETCH_BUDGET
                ) -> int:
    """Row-blocks per launch: the largest power of two K whose two int32
    prefetch tables (K·max_tiles entries each) fit ``smem_budget`` bytes."""
    per_row = 2 * max_tiles * 4
    k = 1
    while 2 * k * per_row <= smem_budget:
        k *= 2
    return k


def rows_per_step(max_tiles: int, k: int) -> int:
    """Row-blocks one grid step covers: the largest power of two whose
    slots fit :data:`STEP_SLOTS`, at most the launch's ``k``."""
    r = 1
    while 2 * r * max_tiles <= STEP_SLOTS and 2 * r <= k:
        r *= 2
    return r


def ring_depth(tile_shape, dtype) -> int:
    """Slots of the DMA ring: :data:`RING_BYTES` of tiles, 2 to 16."""
    rows, lanes = tile_shape
    tile_bytes = rows * lanes * jnp.dtype(dtype).itemsize
    return min(16, max(2, RING_BYTES // tile_bytes))


def _acc_dtype(dtype) -> jnp.dtype:
    """Accumulation dtype: f32 for f32/bf16 inputs, f64 for f64 ranks (f64
    is the CPU/interpret validation path — a TPU has no f64)."""
    return jnp.dtype(jnp.float64) if dtype == jnp.float64 else jnp.float32


def _kernel(idx_ref, cols_ref, tiles_hbm, x_hbm, o_ref, tbuf, xbuf, tsem,
            xsem, cur, *, semiring: str, block: int, max_tiles: int, k: int,
            rows: int, depth: int):
    """One grid step: ``rows`` row-blocks of the launch.  The copy cursor
    (row and slot of the next tile to copy, copies started) and the count
    of tiles consumed run through the step's loops as values and wait in
    the SMEM scratch ``cur`` between steps."""
    mt = max_tiles
    i32 = jnp.int32         # x64 would make Python ints i64 in the loops
    acc_t = _acc_dtype(o_ref.dtype)
    n_tiles, n_cb = tiles_hbm.shape[0], x_hbm.shape[0]

    def live(r, s):
        # slot s < mt of launch row r holds a tile (rows past k hold none)
        return (r < k) & (cols_ref[jnp.minimum(r, k - 1) * mt + s] >= 0)

    def skip_empty(r):
        return lax.while_loop(lambda r: (r < k) & ~live(r, 0),
                              lambda r: r + 1, r)

    def copies(slot, t=i32(0), c=i32(0)):
        return (pltpu.make_async_copy(tiles_hbm.at[t], tbuf.at[slot],
                                      tsem.at[slot]),
                pltpu.make_async_copy(x_hbm.at[c], xbuf.at[slot],
                                      xsem.at[slot]))

    def advance(cursor):
        # start the copies of the cursor's tile into ring slot p % D, then
        # move the cursor to the launch's next live (row, slot)
        def start(cursor):
            r, s, p = cursor
            n = r * mt + s
            # ids are clamped: a corrupted table entry must not address
            # outside the pool (the integrity scrub reports it)
            for cp in copies(lax.rem(p, i32(depth)),
                             lax.clamp(i32(0), idx_ref[n], i32(n_tiles - 1)),
                             lax.clamp(i32(0), cols_ref[n], i32(n_cb - 1))):
                cp.start()
            same = (s + 1 < mt) & live(r, jnp.minimum(s + 1, mt - 1))
            r = lax.cond(same, lambda: r, lambda: skip_empty(r + 1))
            return r, jnp.where(same, s + 1, i32(0)), p + 1
        return lax.cond(cursor[0] < k, start, lambda c: c, cursor)

    def prologue():
        cursor = (skip_empty(i32(0)), i32(0), i32(0))
        cursor = lax.fori_loop(i32(0), i32(depth - 1),
                               lambda _, cu: advance(cu), cursor)
        return cursor + (i32(0),)

    state = lax.cond(pl.program_id(0) == 0, prologue,
                     lambda: (cur[0], cur[1], cur[2], cur[3]))

    # lane l of a tile row belongs to output row group l // B
    g, lanes = o_ref.shape[1], tbuf.shape[2]
    lane = lax.broadcasted_iota(jnp.int32, (g, lanes), 1)
    lo = lax.broadcasted_iota(jnp.int32, (g, lanes), 0) * i32(block)
    seg = jnp.where((lane >= lo) & (lane < lo + i32(block)),
                    jnp.ones((g, lanes), acc_t), jnp.zeros((g, lanes), acc_t))

    def row(rr, state):
        r = pl.program_id(0) * rows + rr

        def more(st):
            j = st[0]
            return (j < mt) & live(r, jnp.minimum(j, mt - 1))

        def tile(st):
            j, acc, cursor, done = st
            cursor = advance(cursor)
            slot = lax.rem(done, i32(depth))
            for cp in copies(slot):
                cp.wait()
            prod = tbuf[slot].astype(acc_t) * xbuf[slot].astype(acc_t)
            if semiring == "sum":
                acc = acc + prod
            else:
                # saturating OR: any positive product marks its row
                acc = jnp.maximum(acc, prod)
            return j + 1, acc, cursor, done + 1

        _, acc, cursor, done = lax.while_loop(
            more, tile, (i32(0), jnp.zeros(tbuf.shape[1:], acc_t),
                         state[:3], state[3]))
        # part[h, q] = Σ_{l in group h} acc[q, l]; HIGHEST keeps the f32
        # sums exact where the MXU's default bf16 pass would round them
        part = lax.dot_general(seg, acc, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=acc_t)
        o_ref[rr] = part.astype(o_ref.dtype)
        return cursor + (done,)

    state = lax.fori_loop(i32(0), i32(rows), row, state)
    for i, v in enumerate(state):
        cur[i] = v


def _x_rows(x, block: int, lanes: int):
    """x per column block as the kernel's [1, L] row: the block's B
    entries repeated L / B times, matching the lanes of a tile row."""
    xb = x.reshape(-1, block)
    return jnp.tile(xb, (1, lanes // block)).reshape(-1, 1, lanes)


def _launch(idx, cols, tiles, xr, *, block: int, max_tiles: int,
            semiring: str, interpret: bool):
    """One pallas_call over K = len(idx) / max_tiles row-blocks whose slot
    rows are ``idx`` / ``cols`` (flat, int32).  Returns [K, g, R]."""
    k = idx.shape[0] // max_tiles
    R, L = tiles.shape[1:]
    g = L // block
    per_step = rows_per_step(max_tiles, k)
    steps = -(-k // per_step)
    depth = ring_depth((R, L), tiles.dtype)

    gspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        # int32 literals: under x64 a Python 0 would index as i64
        out_specs=pl.BlockSpec((per_step, g, R), lambda i, idx, cols: (
            i, jnp.int32(0), jnp.int32(0))),
        scratch_shapes=[pltpu.VMEM((depth, R, L), tiles.dtype),
                        pltpu.VMEM((depth, 1, L), xr.dtype),
                        pltpu.SemaphoreType.DMA((depth,)),
                        pltpu.SemaphoreType.DMA((depth,)),
                        pltpu.SMEM((4,), jnp.int32)],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, semiring=semiring, block=block,
                          max_tiles=max_tiles, k=k, rows=per_step,
                          depth=depth),
        grid_spec=gspec,
        out_shape=jax.ShapeDtypeStruct((steps * per_step, g, R), xr.dtype),
        # the copy cursor carries from step to step: steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # the ring's copies and semaphores need the TPU interpreter
        interpret=pltpu.InterpretParams() if interpret else False,
    )(idx, cols, tiles, xr)
    return y if steps * per_step == k else y[:k]


def _chunked(idx, cols, tiles, x, *, block: int, max_tiles: int,
             semiring: str, interpret: bool, smem_budget: int):
    """Run the kernel over the K row-blocks named by the flat slot rows
    ``idx`` / ``cols`` ([K·max_tiles] int32), in launches of at most
    :func:`launch_rows` row-blocks.  Returns y_rows [K, B] (0/1 for "or")."""
    if semiring not in ("sum", "or"):
        raise ValueError(semiring)
    k = idx.shape[0] // max_tiles
    kc = launch_rows(max_tiles, smem_budget)
    run = functools.partial(
        _launch, tiles=tiles, xr=_x_rows(x, block, tiles.shape[2]),
        block=block, max_tiles=max_tiles, semiring=semiring,
        interpret=interpret)
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
    gathers the K slot rows first; a padded slot's row is all -1, so the
    kernel copies nothing for it.

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
