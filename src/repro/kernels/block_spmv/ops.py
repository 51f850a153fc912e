"""Host-side block-sparse builder + jit'd SpMV wrappers + PageRank step op.

The builder is fully vectorized (one flat ``np.add.at`` scatter for tile
values, one argsort-free slot assignment for the per-row tile lists) and has
an incremental sibling: :func:`apply_delta` patches only the tiles an edge
batch touches, so a dynamic-graph stream pays O(batch) per snapshot instead
of O(m) rebuilds.

Streaming runtime additions (docs/ENGINES.md §Streaming):

* **capacity padding** — the tile pool and the per-row slot tables can be
  preallocated on a doubling *growth ladder* (:func:`capacity_bucket`), so
  ``tiles.shape`` / ``max_tiles`` stay stable while a dynamic stream patches
  the matrix.  Stable shapes + stable pytree aux = the fused driver is never
  retraced by a delta batch (zero post-warmup recompiles).
* **device-side delta scatter** — :func:`apply_delta` applies the values of
  an edge batch with one jitted per-edge scatter-add whose operand shapes
  are bucketed, so the hot part of a stream step runs on-device with a
  bounded jit cache.  Only the tiny slot-table bookkeeping stays on host.
* **two SpMV backends** — the Pallas kernels (``backend="pallas"``: MXU path
  on TPU, interpreter-validated elsewhere) and an XLA tile path
  (``backend="xla"``: gather + ``einsum`` over the *same* tile layout) that
  gives CPU containers real engine-relative performance instead of the
  ~200× interpret-mode penalty.  :func:`default_backend` picks per platform.
* **frontier-proportional dispatch** — :func:`block_spmv_active_bucketed`
  launches the active-row-block SpMV through a ``lax.switch`` over a static
  ladder of grid sizes, so the Pallas grid (and the interpret-mode loop, and
  the XLA gather) scales with the *actual* frontier instead of ``n_rb``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.block_spmv.block_spmv import (block_spmv_pallas,
                                                 block_spmv_active_pallas,
                                                 _acc_dtype)


TILE_CAP_BASE = 8        # minimum tile-pool capacity bucket
SLOT_CAP_BASE = 4        # minimum per-row slot-table width bucket
DELTA_BATCH_BUCKET = 64  # minimum padded edge-batch length for the scatter
ACTIVE_LADDER_BASE = 8   # smallest active-block grid bucket

I32_MAX = np.iinfo(np.int32).max


def check_i32(count: int, what: str) -> None:
    """Guard for the int32 index diet: slot tables, tile ids and block
    indices are stored 32-bit (half the slot-table footprint of int64),
    which is sufficient below 2^31 entries.  Past that the narrow layout
    would silently alias — fail loudly at the boundary instead."""
    if count > I32_MAX:
        raise OverflowError(
            f"{what} count {count} exceeds the int32 index range "
            f"({I32_MAX}); the 32-bit slot-table/index layout cannot "
            "address it — shard the graph (topology='sharded') or raise "
            "block_size so per-structure counts stay below 2^31")


def capacity_bucket(n: int, base: int = TILE_CAP_BASE) -> int:
    """Smallest power-of-two multiple of ``base`` ≥ n (doubling ladder).
    Growth through buckets bounds reallocation *and* the jit cache: a
    streamed matrix only ever exposes O(log) distinct shapes."""
    cap = base
    while cap < n:
        cap *= 2
    return cap


def tile_shape(block: int) -> Tuple[int, int]:
    """Stored shape of one B×B tile: its B² entries, row-major, as rows of
    a lane-dense last dimension — 128 lanes where B² allows (B=64 →
    [32, 128]), else [B, B].  Entry (r, c) is at flat offset r·B + c either
    way.  A TPU pads a 64-wide last dimension to 128 lanes and keeps such
    an array transposed, so a [cap, 64, 64] pool would be copied into the
    kernels' layout (at twice its size) on every launch."""
    lanes = max(block, min(128, block * block))
    return block * block // lanes, lanes


def active_ladder(n_rb: int, base: int = ACTIVE_LADDER_BASE
                  ) -> Tuple[int, ...]:
    """Static ladder of active-block grid sizes for bucketed SpMV dispatch:
    (base, 2·base, …, n_rb).  O(log n_rb) entries → O(log n_rb) compiled
    branches, each with a grid proportional to its bucket."""
    out = []
    K = base
    while K < n_rb:
        out.append(K)
        K *= 2
    out.append(n_rb)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class BlockSparse:
    """Block-sparse matrix A [n_rows_pad, n_cols_pad] in B×B dense tiles.

    ``tiles[k]`` is the dense tile for the k-th stored (row-block, col-block)
    pair, stored row-major in :func:`tile_shape` (``tiles.reshape(-1, B, B)``
    is the B×B view); ``tile_cols[i, j]`` is the column-block of the j-th
    tile of row-block i (or -1 padding); ``tile_idx`` flat-indexes into
    ``tiles``.

    ``tiles.shape[0]`` is a *capacity*, not a count: trailing tiles that no
    slot references are zero padding from the growth ladder.  The live tile
    count is recoverable from the slot tables (every allocated tile stays
    referenced even when deletions empty it).

    Registered as a pytree so it can flow through ``jax.jit`` / ``lax``
    control flow (the fused Pallas engine carries one through its driver).
    """
    n_rows: int
    n_cols: int
    block: int
    max_tiles: int
    tiles: jnp.ndarray       # [tile_capacity, *tile_shape(B)]
    tile_cols: jnp.ndarray   # [n_rb, max_tiles] i32
    tile_idx: jnp.ndarray    # [n_rb * max_tiles] i32

    @property
    def n_rb(self) -> int:
        return (self.n_rows + self.block - 1) // self.block

    @property
    def n_cb(self) -> int:
        return (self.n_cols + self.block - 1) // self.block

    @property
    def tile_capacity(self) -> int:
        return int(self.tiles.shape[0])

    def n_tiles(self) -> int:
        """Live tile count (host sync on the small index table only)."""
        occ = np.asarray(self.tile_cols) >= 0
        if not occ.any():
            return 0
        return int(np.asarray(self.tile_idx).reshape(
            occ.shape)[occ].max()) + 1

    def tree_flatten(self):
        children = (self.tiles, self.tile_cols, self.tile_idx)
        aux = (self.n_rows, self.n_cols, self.block, self.max_tiles)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        n_rows, n_cols, block, max_tiles = aux
        tiles, tile_cols, tile_idx = children
        return cls(n_rows=n_rows, n_cols=n_cols, block=block,
                   max_tiles=max_tiles, tiles=tiles, tile_cols=tile_cols,
                   tile_idx=tile_idx)


jax.tree_util.register_pytree_node(
    BlockSparse, BlockSparse.tree_flatten, BlockSparse.tree_unflatten)


def _slot_tables(tiles_rb: np.ndarray, tiles_cb: np.ndarray, n_rb: int,
                 min_max_tiles: int = 1) -> Tuple[np.ndarray, np.ndarray, int]:
    """Per-row tile lists from sorted-by-(rb, cb) tile coordinates.

    Tiles of one row-block are contiguous (the caller sorts by the flat key
    rb * n_cb + cb), so the slot of tile t within its row is just
    ``t - row_start[rb(t)]`` — no Python loop.
    """
    n_tiles = len(tiles_rb)
    check_i32(n_tiles, "tile")
    per_row = np.bincount(tiles_rb, minlength=n_rb)
    max_tiles = max(min_max_tiles, int(per_row.max(initial=1)))
    row_start = np.zeros(n_rb + 1, dtype=np.int64)
    np.cumsum(per_row, out=row_start[1:])
    # int32 diet: tile ids and in-row slots are < 2^31 (guarded above), so
    # the O(n_tiles) bookkeeping intermediates stay 32-bit like the tables
    slot = (np.arange(n_tiles, dtype=np.int32)
            - row_start[tiles_rb].astype(np.int32))
    tile_cols = np.full((n_rb, max_tiles), -1, dtype=np.int32)
    tile_idx = np.zeros((n_rb, max_tiles), dtype=np.int32)
    tile_cols[tiles_rb, slot] = tiles_cb
    tile_idx[tiles_rb, slot] = np.arange(n_tiles, dtype=np.int32)
    return tile_cols, tile_idx, max_tiles


def build_block_sparse(rows: np.ndarray, cols: np.ndarray, n_rows: int,
                       n_cols: int, *, block: int = 128,
                       values: Optional[np.ndarray] = None,
                       dtype=np.float32, padded: bool = False,
                       to_device: bool = True) -> BlockSparse:
    """Build tiles from an edge list: A[rows[k], cols[k]] = values[k] (or 1).

    ``padded=True`` preallocates the tile pool and the slot tables on the
    growth ladder (:func:`capacity_bucket`), the layout a dynamic stream
    should use: :func:`apply_delta` can then add tiles without changing
    ``tiles.shape`` / ``max_tiles`` until a bucket overflows.

    ``to_device=False`` keeps the tile pool and slot tables as numpy
    arrays — the **host tier** layout of :mod:`repro.core.tiering`, where
    the full pool never touches the device and only a bounded hot set of
    row-blocks is gathered into a device slab.
    """
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = (np.ones_like(rows, dtype=dtype) if values is None
            else np.asarray(values, dtype))
    n_rb = (n_rows + block - 1) // block
    n_cb = (n_cols + block - 1) // block

    rb, cb = rows // block, cols // block
    key = rb * n_cb + cb
    order = np.argsort(key, kind="stable")
    rows, cols, vals, key = rows[order], cols[order], vals[order], key[order]
    uniq = np.unique(key)

    n_tiles = max(1, len(uniq))
    cap = capacity_bucket(n_tiles) if padded else n_tiles
    tiles = np.zeros((cap, block, block), dtype=dtype)
    # one flat scatter for every entry: tile position × B² + local offset
    tpos = np.searchsorted(uniq, key)
    flat = tpos * (block * block) + (rows % block) * block + (cols % block)
    np.add.at(tiles.reshape(-1), flat, vals)
    tiles = tiles.reshape((cap,) + tile_shape(block))

    tiles_rb = (uniq // n_cb).astype(np.int64)
    tiles_cb = (uniq % n_cb).astype(np.int64)
    min_mt = 1
    if padded:
        per_row = np.bincount(tiles_rb, minlength=n_rb) if len(tiles_rb) \
            else np.zeros(n_rb, np.int64)
        min_mt = capacity_bucket(int(per_row.max(initial=1)), SLOT_CAP_BASE)
    tile_cols, tile_idx, max_tiles = _slot_tables(tiles_rb, tiles_cb, n_rb,
                                                  min_max_tiles=min_mt)

    if not to_device:
        return BlockSparse(
            n_rows=n_rows, n_cols=n_cols, block=block, max_tiles=max_tiles,
            tiles=tiles, tile_cols=tile_cols,
            tile_idx=tile_idx.reshape(-1))
    return BlockSparse(
        n_rows=n_rows, n_cols=n_cols, block=block, max_tiles=max_tiles,
        tiles=jnp.asarray(tiles), tile_cols=jnp.asarray(tile_cols),
        tile_idx=jnp.asarray(tile_idx.reshape(-1)))


@dataclasses.dataclass
class DeltaPlan:
    """Host-side bookkeeping for one delta batch against a block-sparse
    structure: where every edge lands (``tid``) plus the rebuilt slot
    tables when the batch opened new (row-block, col-block) pairs.

    The plan is *scatter-agnostic*: :func:`apply_delta` feeds it to the
    jitted device scatter, the host tier
    (:class:`repro.core.tiering.HostTilePool`) to a numpy ``add.at`` —
    the two tiers share one bookkeeping path so they cannot diverge."""
    tid: np.ndarray                    # [b] target tile id per edge
    n_old: int                         # live tiles before the batch
    n_new: int                         # tiles the batch appends
    tile_cols: Optional[np.ndarray]    # rebuilt [n_rb, mt'] (None: unchanged)
    tile_idx: Optional[np.ndarray]     # rebuilt [n_rb, mt'] (None: unchanged)
    max_tiles: int                     # post-batch slot width
    touched_rb: np.ndarray             # unique row-blocks the batch lands in

    @property
    def n_live(self) -> int:
        return self.n_old + self.n_new


def plan_delta(tile_cols_h: np.ndarray, tile_idx_h: np.ndarray,
               rows: np.ndarray, cols: np.ndarray, *, n_cb: int,
               block: int, max_tiles: int) -> DeltaPlan:
    """Resolve a delta batch against host copies of the slot tables:
    per-edge target tile ids, appended-tile count, and (when new tiles
    appear) merged slot tables on the :data:`SLOT_CAP_BASE` width ladder.
    Index-sized work only — never touches tile data."""
    n_rb = tile_cols_h.shape[0]
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    key = (rows // block) * n_cb + (cols // block)

    occ = tile_cols_h >= 0
    ex_rb, ex_slot = np.nonzero(occ)
    ex_key = ex_rb * n_cb + tile_cols_h[ex_rb, ex_slot]
    ex_tid = tile_idx_h[ex_rb, ex_slot]
    order = np.argsort(ex_key)
    sk, st = ex_key[order], ex_tid[order]

    pos = np.searchsorted(sk, key)
    pos_c = np.clip(pos, 0, max(len(sk) - 1, 0))
    found = (sk[pos_c] == key) if len(sk) else np.zeros(len(key), bool)

    # live tile count: capacity padding means tiles.shape[0] is an upper
    # bound, but every live tile is referenced by some slot
    n_old = int(ex_tid.max()) + 1 if len(ex_tid) else 0
    new_keys = np.unique(key[~found])
    check_i32(n_old + len(new_keys), "tile")
    tid = np.where(found, st[pos_c] if len(sk) else 0,
                   n_old + np.searchsorted(new_keys, key))

    tile_cols_np = tile_idx_np = None
    out_mt = max_tiles
    if len(new_keys):
        # merge old + new coordinates, re-deriving slots (cheap: index-sized)
        all_key = np.concatenate([ex_key, new_keys])
        all_tid = np.concatenate([ex_tid, n_old + np.arange(len(new_keys))])
        order = np.argsort(all_key)
        all_key, all_tid = all_key[order], all_tid[order]
        t_rb = (all_key // n_cb).astype(np.int32)
        t_cb = (all_key % n_cb).astype(np.int32)
        per_row_max = int(np.bincount(t_rb, minlength=n_rb).max(initial=1))
        min_mt = max_tiles if per_row_max <= max_tiles else \
            capacity_bucket(per_row_max, SLOT_CAP_BASE)
        tile_cols_np, idx_pos, out_mt = _slot_tables(
            t_rb, t_cb, n_rb, min_max_tiles=min_mt)
        # _slot_tables numbers tiles 0..n-1 in sorted order; map to real ids
        tile_idx_np = np.zeros_like(idx_pos)
        occ2 = tile_cols_np >= 0
        tile_idx_np[occ2] = all_tid[idx_pos[occ2]]

    return DeltaPlan(
        tid=tid, n_old=n_old, n_new=len(new_keys),
        tile_cols=tile_cols_np, tile_idx=tile_idx_np, max_tiles=out_mt,
        touched_rb=np.unique(rows // block).astype(np.int32))


@functools.partial(jax.jit, static_argnames=("block",))
def _scatter_delta(tiles: jnp.ndarray, tid: jnp.ndarray, rloc: jnp.ndarray,
                   cloc: jnp.ndarray, vals: jnp.ndarray, *, block: int
                   ) -> jnp.ndarray:
    """Jitted per-edge scatter-add of a (bucketed-length) delta batch into
    the tile pool.  Padded entries carry val 0 against tile 0 (inert)."""
    flat = tid * (block * block) + rloc * block + cloc
    return tiles.reshape(-1).at[flat].add(vals).reshape(tiles.shape)


def apply_delta(mat: BlockSparse, rows: np.ndarray, cols: np.ndarray,
                values: np.ndarray) -> BlockSparse:
    """Patch A with A[rows[k], cols[k]] += values[k], touching only the
    tiles the delta lands in.

    Value application is a single jitted device scatter over a
    bucket-padded edge batch (:func:`_scatter_delta`) — no host round-trip
    through the tile pool.  Entirely new (row-block, col-block) pairs are
    appended into the preallocated capacity; the pool / slot tables are
    rewidened (to the next :func:`capacity_bucket`) only when a bucket
    overflows, so shapes are stable across a stream.  Tiles emptied by
    deletions are kept (structure grows monotonically) — their dense B×B
    block is all-zero and contributes nothing.

    Raises ``ValueError`` for coordinates outside the matrix grid: the block
    grid is fixed for the lifetime of a stream (rebuild via
    ``build_block_sparse`` / ``IncrementalPullMatrix.from_snapshot`` when
    the vertex set outgrows it).
    """
    B = mat.block
    n_rb, n_cb = mat.n_rb, mat.n_cb
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(values, dtype=np.dtype(mat.tiles.dtype))
    if len(rows) == 0:
        return mat
    if (rows.min() < 0 or cols.min() < 0 or rows.max() >= mat.n_rows
            or cols.max() >= mat.n_cols):
        raise ValueError(
            f"delta coordinates (rows in [{rows.min()}, {rows.max()}], cols "
            f"in [{cols.min()}, {cols.max()}]) fall outside the fixed "
            f"{mat.n_rows}x{mat.n_cols} block grid ({n_rb}x{n_cb} blocks of "
            f"{B}); a grid-size change requires a rebuild with "
            f"build_block_sparse / IncrementalPullMatrix.from_snapshot")

    # host bookkeeping shared with the host tier (repro.core.tiering)
    plan = plan_delta(
        np.asarray(mat.tile_cols),
        np.asarray(mat.tile_idx).reshape(n_rb, mat.max_tiles),
        rows, cols, n_cb=n_cb, block=B, max_tiles=mat.max_tiles)

    tiles = mat.tiles
    if plan.n_live > tiles.shape[0]:
        # tile-pool bucket overflow → grow to the next capacity bucket
        cap = capacity_bucket(plan.n_live)
        tiles = jnp.concatenate(
            [tiles, jnp.zeros((cap - tiles.shape[0],) + tiles.shape[1:],
                              tiles.dtype)])

    # one bucketed device scatter applies every delta value
    b_pad = capacity_bucket(len(rows), DELTA_BATCH_BUCKET)
    pad = b_pad - len(rows)
    z = np.zeros(pad, np.int32)
    tiles = _scatter_delta(
        tiles,
        jnp.asarray(np.concatenate([plan.tid.astype(np.int32), z])),
        jnp.asarray(np.concatenate([(rows % B).astype(np.int32), z])),
        jnp.asarray(np.concatenate([(cols % B).astype(np.int32), z])),
        jnp.asarray(np.concatenate([vals, np.zeros(pad, vals.dtype)])),
        block=B)

    tile_cols_out, tile_idx_out = mat.tile_cols, mat.tile_idx
    if plan.tile_cols is not None:
        tile_cols_out = jnp.asarray(plan.tile_cols)
        tile_idx_out = jnp.asarray(plan.tile_idx.reshape(-1))

    return BlockSparse(
        n_rows=mat.n_rows, n_cols=mat.n_cols, block=B,
        max_tiles=plan.max_tiles, tiles=tiles, tile_cols=tile_cols_out,
        tile_idx=tile_idx_out)


# ---------------------------------------------------------------------------
# SpMV backends
# ---------------------------------------------------------------------------

BACKENDS = ("pallas", "xla")


def default_backend() -> str:
    """Tile-SpMV backend when a caller passes ``backend=None``: the Pallas
    kernels on TPU, the XLA gather/einsum path elsewhere (CPU containers
    would otherwise pay the ~200× interpret-mode penalty).  Override with
    ``REPRO_TILE_BACKEND=pallas|xla`` — an invalid override fails here,
    eagerly, with the valid-value list (it is also checked at
    ``repro.api.EngineConfig`` construction) instead of surfacing only when
    a kernel is launched."""
    env = os.environ.get("REPRO_TILE_BACKEND")
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"REPRO_TILE_BACKEND={env!r} is not a valid tile backend; "
                f"expected one of {list(BACKENDS)}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def default_interpret() -> bool:
    """Pallas interpret mode on anything that is not a real TPU.  Every
    kernel entry below resolves ``interpret=None`` through this, so a
    caller that leaves the flag out compiles the kernels on the chip."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def _resolve_backend(backend: Optional[str]) -> str:
    backend = backend or default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown tile backend {backend!r} "
                         f"(expected one of {list(BACKENDS)})")
    return backend


@functools.partial(jax.jit,
                   static_argnames=("block", "max_tiles", "semiring"))
def _block_spmv_xla(tile_idx: jnp.ndarray, tile_cols: jnp.ndarray,
                    tiles: jnp.ndarray, x: jnp.ndarray, *, block: int,
                    max_tiles: int, semiring: str = "sum") -> jnp.ndarray:
    """XLA tile backend: gather each row-block's tiles and x-slices over the
    same layout the Pallas kernel prefetches, contract with one einsum
    (batched B×B matvecs — dense MXU/AVX-friendly work, no interpreter)."""
    n_rb = tile_cols.shape[0]
    xb = x.reshape(-1, block)                              # [n_cb, B]
    T = tiles.reshape(-1, block, block)[
        tile_idx.reshape(n_rb, max_tiles)]                 # [n_rb, mt, B, B]
    X = xb[jnp.maximum(tile_cols, 0)]                      # [n_rb, mt, B]
    X = jnp.where((tile_cols >= 0)[:, :, None], X, 0)
    y = jnp.einsum("rmab,rmb->ra", T, X,
                   preferred_element_type=_acc_dtype(x.dtype))
    if semiring == "or":
        y = (y > 0)
    elif semiring != "sum":
        raise ValueError(semiring)
    return y.astype(x.dtype).reshape(-1)


@functools.partial(jax.jit,
                   static_argnames=("block", "max_tiles", "semiring"))
def _block_spmv_active_xla(active_ids: jnp.ndarray, tile_idx: jnp.ndarray,
                           tile_cols: jnp.ndarray, tiles: jnp.ndarray,
                           x: jnp.ndarray, *, block: int, max_tiles: int,
                           semiring: str = "sum") -> jnp.ndarray:
    """Active-row-block XLA tile SpMV: work ∝ len(active_ids) · max_tiles.
    Same contract as the Pallas kernel: rows of inactive blocks are
    *defined as zero* here but callers must still mask (the Pallas backend
    leaves them undefined)."""
    n_rb = tile_cols.shape[0]
    rb = jnp.maximum(active_ids, 0)
    cols = tile_cols[rb]                                   # [k, mt]
    T = tiles.reshape(-1, block, block)[
        tile_idx.reshape(n_rb, max_tiles)[rb]]             # [k, mt, B, B]
    xb = x.reshape(-1, block)
    X = xb[jnp.maximum(cols, 0)]                           # [k, mt, B]
    live = (active_ids >= 0)[:, None] & (cols >= 0)
    X = jnp.where(live[:, :, None], X, 0)
    y_act = jnp.einsum("kmab,kmb->ka", T, X,
                       preferred_element_type=_acc_dtype(x.dtype))
    if semiring == "or":
        y_act = (y_act > 0)
    elif semiring != "sum":
        raise ValueError(semiring)
    y_act = y_act.astype(x.dtype)
    # padded slots write the trash row n_rb (mirrors the Pallas kernel)
    out = jnp.zeros((n_rb + 1, block), x.dtype)
    out = out.at[jnp.where(active_ids >= 0, active_ids, n_rb)].set(y_act)
    return out[:n_rb].reshape(-1)


def block_spmv(mat: BlockSparse, x: jnp.ndarray, *, semiring: str = "sum",
               interpret: Optional[bool] = None,
               backend: Optional[str] = None) -> jnp.ndarray:
    """y = A @ x over the requested semiring; x is zero-padded to block size.

    ``backend`` selects the Pallas kernels or the XLA tile path
    (:func:`default_backend` when None).  ``interpret`` applies to the
    Pallas backend only: True executes the kernel body under the
    interpreter (CPU validation), False compiles for TPU, None
    (:func:`default_interpret`) follows the platform.
    """
    backend = _resolve_backend(backend)
    interpret = _resolve_interpret(interpret)
    n_cb_pad = mat.n_cb * mat.block
    xp = jnp.zeros((n_cb_pad,), x.dtype).at[:x.shape[0]].set(x)
    if backend == "xla":
        y = _block_spmv_xla(mat.tile_idx, mat.tile_cols, mat.tiles, xp,
                            block=mat.block, max_tiles=mat.max_tiles,
                            semiring=semiring)
    else:
        y = block_spmv_pallas(mat.tile_idx, mat.tile_cols, mat.tiles, xp,
                              block=mat.block, max_tiles=mat.max_tiles,
                              semiring=semiring, interpret=interpret)
    return y[:mat.n_rows]


def block_spmv_active(mat: BlockSparse, x: jnp.ndarray,
                      active_ids: jnp.ndarray, *, semiring: str = "sum",
                      interpret: Optional[bool] = None,
                      backend: Optional[str] = None) -> jnp.ndarray:
    """Frontier-compacted y = A @ x restricted to the row-blocks in
    ``active_ids`` (compacted, -1-padded).  Rows of inactive blocks are
    UNDEFINED — mask with the active-block indicator before consuming."""
    backend = _resolve_backend(backend)
    interpret = _resolve_interpret(interpret)
    n_cb_pad = mat.n_cb * mat.block
    xp = jnp.zeros((n_cb_pad,), x.dtype).at[:x.shape[0]].set(x)
    if backend == "xla":
        y = _block_spmv_active_xla(active_ids.astype(jnp.int32),
                                   mat.tile_idx, mat.tile_cols, mat.tiles,
                                   xp, block=mat.block,
                                   max_tiles=mat.max_tiles, semiring=semiring)
    else:
        y = block_spmv_active_pallas(active_ids.astype(jnp.int32),
                                     mat.tile_idx, mat.tile_cols, mat.tiles,
                                     xp, block=mat.block,
                                     max_tiles=mat.max_tiles,
                                     semiring=semiring, interpret=interpret)
    return y[:mat.n_rows]


def block_spmv_active_bucketed(mat: BlockSparse, x: jnp.ndarray,
                               active_ids: jnp.ndarray, n_active: jnp.ndarray,
                               *, semiring: str = "sum",
                               interpret: Optional[bool] = None,
                               backend: Optional[str] = None,
                               ladder: Optional[Sequence[int]] = None
                               ) -> jnp.ndarray:
    """Frontier-proportional active SpMV dispatch.

    ``active_ids`` is the full compacted slot list ([n_rb], -1-padded) and
    ``n_active`` the (traced) count of real entries.  The call selects the
    smallest ladder bucket K ≥ n_active with a ``lax.switch`` and launches
    the K-slot kernel on ``active_ids[:K]`` — so the Pallas grid / the XLA
    gather scales with the actual frontier, not ``n_rb``.  Trace-safe inside
    the fused driver's ``while_loop`` (the switch index is a traced scalar;
    every branch has static shapes).  O(log n_rb) branches are compiled once;
    each runs under ``jax.named_scope("spmv.<semiring>.k<K>")``, so a trace's
    op metadata tells the buckets and semirings apart.
    On the Pallas backend a bucket whose slot tables exceed the SMEM
    prefetch budget runs as several launches
    (:func:`repro.kernels.block_spmv.block_spmv.launch_rows`).
    """
    backend = _resolve_backend(backend)
    interpret = _resolve_interpret(interpret)
    n_rb = mat.n_rb
    lad = tuple(ladder) if ladder is not None else active_ladder(n_rb)
    n_cb_pad = mat.n_cb * mat.block
    xp = jnp.zeros((n_cb_pad,), x.dtype).at[:x.shape[0]].set(x)
    ids32 = active_ids.astype(jnp.int32)

    def run(ids_k):
        if backend == "xla":
            return _block_spmv_active_xla(
                ids_k, mat.tile_idx, mat.tile_cols, mat.tiles, xp,
                block=mat.block, max_tiles=mat.max_tiles, semiring=semiring)
        return block_spmv_active_pallas(
            ids_k, mat.tile_idx, mat.tile_cols, mat.tiles, xp,
            block=mat.block, max_tiles=mat.max_tiles, semiring=semiring,
            interpret=interpret)

    def run_at(K):
        # the scope names the bucket in the ops' metadata; the kernel's
        # own instruction name is left as the compiler gives it
        with jax.named_scope(f"spmv.{semiring}.k{K}"):
            return run(ids32[:K])

    if len(lad) == 1:
        y = run_at(lad[0])
    else:
        branches = [functools.partial(run_at, K) for K in lad]
        bidx = sum((n_active > K).astype(jnp.int32) for K in lad[:-1])
        y = lax.switch(bidx, branches)
    return y[:mat.n_rows]


def block_spmv_push_bucketed(mat: BlockSparse, x: jnp.ndarray,
                             src_cb: jnp.ndarray,
                             active_ids: jnp.ndarray, n_active: jnp.ndarray,
                             *, interpret: Optional[bool] = None,
                             backend: Optional[str] = None,
                             ladder: Optional[Sequence[int]] = None
                             ) -> jnp.ndarray:
    """Scatter-semiring push step on the pull tile layout.

    Forward push moves each selected source's residual along its
    *out*-edges: ``y[v] = Σ_{u→v, u ∈ S} x[u]``.  On the pull layout
    (``A[v, u] = 1`` iff edge u→v) that scatter is exactly ``A @ (x ⊙ 1_S)``
    — so the push reuses the same tiles, slot tables and bucketed dispatch
    as the pull, with the operand masked to the selected source
    column-blocks (``src_cb``, a [n_cb] indicator) and the launch restricted
    to the candidate *destination* row-blocks (``active_ids`` compacted,
    -1-padded; ``n_active`` traced — the tile-presence adjacency gives the
    exact candidate set, so no destination outside it can receive mass).

    Same output contract as :func:`block_spmv_active_bucketed`: rows of
    blocks outside ``active_ids`` are UNDEFINED on the Pallas backend —
    mask with the candidate indicator before consuming.  Its launches run
    under ``jax.named_scope("spmv.push")``, above the bucket scopes."""
    xm = jnp.where(jnp.repeat(src_cb, mat.block)[:x.shape[0]], x, 0)
    with jax.named_scope("spmv.push"):
        return block_spmv_active_bucketed(
            mat, xm, active_ids, n_active, semiring="sum",
            interpret=interpret, backend=backend, ladder=ladder)


def block_adjacency(mat: BlockSparse) -> jnp.ndarray:
    """Boolean [n_rb, n_cb] tile-presence matrix: which row-blocks own a tile
    in each column-block.  Drives candidate-block selection for the OR-pass
    (a changed column-block can only mark rows of these row-blocks).

    A dynamic stream should *maintain* this incrementally
    (:class:`repro.core.incremental.IncrementalPullMatrix` caches it and
    ORs in each batch's touched blocks) instead of recomputing per run."""
    occ = mat.tile_cols >= 0
    rb = jnp.arange(mat.n_rb, dtype=jnp.int32)[:, None]
    cb = jnp.where(occ, mat.tile_cols, mat.n_cb)
    out = jnp.zeros((mat.n_rb, mat.n_cb + 1), bool)
    out = out.at[jnp.broadcast_to(rb, cb.shape), cb].set(True)
    return out[:, :mat.n_cb]


def pagerank_pull_step(mat: BlockSparse, ranks: jnp.ndarray,
                       inv_out_deg: jnp.ndarray, n: int, *,
                       alpha: float = 0.85, interpret: Optional[bool] = None,
                       backend: Optional[str] = None) -> jnp.ndarray:
    """One PageRank pull iteration with the tile SpMV:
    r' = (1-α)/n + α · A @ (r ⊙ 1/outdeg).  A[v,u] = 1 iff edge u→v."""
    contrib = ranks * inv_out_deg
    pulled = block_spmv(mat, contrib, semiring="sum", interpret=interpret,
                        backend=backend)
    return (1.0 - alpha) / n + alpha * pulled


def frontier_expand_op(mat_t: BlockSparse, changed: jnp.ndarray, *,
                       interpret: Optional[bool] = None,
                       backend: Optional[str] = None) -> jnp.ndarray:
    """DF expansion: indicator of out-neighbors of ``changed`` vertices.
    ``mat_t`` must hold A[v,u]=1 iff edge u→v (same layout as the pull)."""
    return block_spmv(mat_t, changed.astype(jnp.float32), semiring="or",
                      interpret=interpret, backend=backend)
