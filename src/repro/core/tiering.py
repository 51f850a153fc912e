"""Tiered graph storage — host-paged cold tiles + a frontier-biased device
hot set (docs/SCALE.md).

Every subsystem below the streaming session assumed the whole block-sparse
tile pool fits on the device.  This module removes that assumption with a
two-tier layout:

* :class:`HostTilePool` — the **host tier**: the full tile pool and slot
  tables as plain numpy arrays (the ``to_device=False`` layout of
  :func:`repro.kernels.block_spmv.ops.build_block_sparse`).  Delta batches
  are applied host-side through the *same* bookkeeping path the device
  scatter uses (:func:`ops.plan_delta` + one ``np.add.at``), so the two
  tiers cannot diverge structurally.  This is durable truth: ``save()`` /
  ``restore()`` and the integrity scrubber key off it, never off the slab.

* :class:`HotSetManager` — the **device tier**: a fixed-capacity tile slab
  (sized from ``EngineConfig.device_budget_bytes``) plus device slot tables
  that indirect *through the existing BlockSparse layout*: the manager's
  :meth:`HotSetManager.view` is an ordinary :class:`ops.BlockSparse` whose
  ``tiles`` is the slab and whose ``tile_idx`` maps each occupied slot of a
  **resident** row-block to its slab slot.  Non-resident blocks map to the
  reserved all-zero slab slot 0, and a per-row-block residency indicator
  (``rb_res``) tells the fused driver which rows it may update — a sweep
  touching a non-resident block *defers* it (re-marks the whole block for
  the next drive, mirroring the paper's helping mechanism) instead of
  paying a mid-sweep host sync.

Admission is **frontier-biased**: before each drive the session admits the
row-blocks touched by the delta batch, the seed frontier and their
tile-adjacent candidates in ONE batched host→device gather (payload length
bucketed on the capacity ladder, so post-warmup retraces stay 0).  Eviction
is clock/second-chance over a per-block last-touched counter: a block
referenced since the hand last passed gets a second chance; cold blocks are
reclaimed oldest-first.  Counters (hits / misses / evictions / transfer
bytes / refill drives) surface through ``session.report()["tiering"]``.

:class:`EdgePager` gives the blocked Gauss–Seidel oracle the analogous
facility over its per-block edge extents, so ``run_blocked`` can cross-check
tiered results at sizes whose edge slabs exceed the budget too.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.block_spmv import ops


def slab_tiles_for_budget(budget_bytes: int, block: int, dtype) -> int:
    """Tile capacity of a device slab under ``budget_bytes``: the budget is
    spent on B×B dense tiles (slot tables and the residency indicator are
    index-sized and not charged).  Slot 0 is the reserved zero tile, so the
    usable capacity is one less than what is returned here."""
    tile_bytes = block * block * np.dtype(dtype).itemsize
    return max(int(budget_bytes) // tile_bytes, 0)


def budget_hint(block: int, dtype, *, max_tiles_rb: int) -> str:
    """Sizing rule rendered for error messages (docs/SCALE.md §Budget)."""
    tile_bytes = block * block * np.dtype(dtype).itemsize
    need = (max_tiles_rb + 1) * tile_bytes
    return (f"one {block}x{block} {np.dtype(dtype).name} tile is "
            f"{tile_bytes} bytes and the widest row-block holds "
            f"{max_tiles_rb} tiles, so the floor is "
            f"(max_tiles_per_row_block + 1) * tile_bytes = {need} bytes; "
            "size the budget at >= 2x the expected frontier working set")


class HostTilePool:
    """Host tier: the full padded tile pool + slot tables (numpy).

    ``mat`` is a numpy-backed :class:`ops.BlockSparse` on the same growth
    ladder as the device layout; :meth:`apply_delta` patches it in O(batch)
    through :func:`ops.plan_delta` and returns the plan so callers can
    invalidate / re-admit exactly the touched row-blocks."""

    def __init__(self, mat: ops.BlockSparse):
        if not isinstance(mat.tiles, np.ndarray):
            raise TypeError(
                "HostTilePool wraps the numpy layout — build the matrix "
                "with build_block_sparse(..., to_device=False)")
        self.mat = mat

    @classmethod
    def from_edges(cls, rows: np.ndarray, cols: np.ndarray, n_rows: int,
                   n_cols: int, *, block: int, dtype=np.float32
                   ) -> "HostTilePool":
        return cls(ops.build_block_sparse(
            rows, cols, n_rows, n_cols, block=block, dtype=dtype,
            padded=True, to_device=False))

    # -- structure accessors -------------------------------------------------
    @property
    def n_rb(self) -> int:
        return self.mat.n_rb

    @property
    def block(self) -> int:
        return self.mat.block

    @property
    def tile_cols(self) -> np.ndarray:
        return self.mat.tile_cols

    @property
    def tile_idx2d(self) -> np.ndarray:
        return self.mat.tile_idx.reshape(self.mat.tile_cols.shape)

    @property
    def nbytes(self) -> int:
        return int(self.mat.tiles.nbytes + self.mat.tile_cols.nbytes
                   + self.mat.tile_idx.nbytes)

    def apply_delta(self, rows: np.ndarray, cols: np.ndarray,
                    values: np.ndarray) -> ops.DeltaPlan:
        """Host-tier sibling of :func:`ops.apply_delta`: same plan, same
        ladder growth, one ``np.add.at`` instead of the device scatter."""
        mat = self.mat
        B, n_rb, n_cb = mat.block, mat.n_rb, mat.n_cb
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(values, dtype=mat.tiles.dtype)
        if len(rows) == 0:
            return ops.DeltaPlan(tid=rows, n_old=0, n_new=0, tile_cols=None,
                                 tile_idx=None, max_tiles=mat.max_tiles,
                                 touched_rb=np.zeros(0, np.int32))
        if (rows.min() < 0 or cols.min() < 0 or rows.max() >= mat.n_rows
                or cols.max() >= mat.n_cols):
            raise ValueError(
                f"delta coordinates outside the fixed {mat.n_rows}x"
                f"{mat.n_cols} host-tier block grid; rebuild the pool")
        plan = ops.plan_delta(mat.tile_cols, self.tile_idx2d, rows, cols,
                              n_cb=n_cb, block=B, max_tiles=mat.max_tiles)
        tiles = mat.tiles
        if plan.n_live > tiles.shape[0]:
            cap = ops.capacity_bucket(plan.n_live)
            tiles = np.concatenate(
                [tiles, np.zeros((cap - tiles.shape[0],) + tiles.shape[1:],
                                 tiles.dtype)])
        # flat offsets stay int64: capacity * B^2 can exceed 2^31
        flat = (plan.tid.astype(np.int64) * (B * B)
                + (rows % B) * B + (cols % B))
        np.add.at(tiles.reshape(-1), flat, vals)
        tile_cols, tile_idx = mat.tile_cols, mat.tile_idx
        max_tiles = mat.max_tiles
        if plan.tile_cols is not None:
            tile_cols = plan.tile_cols
            tile_idx = plan.tile_idx.reshape(-1)
            max_tiles = plan.max_tiles
        self.mat = ops.BlockSparse(
            n_rows=mat.n_rows, n_cols=mat.n_cols, block=B,
            max_tiles=max_tiles, tiles=tiles, tile_cols=tile_cols,
            tile_idx=tile_idx)
        return plan

    def row_sums(self) -> np.ndarray:
        """Per-row-block sum of live tile entries (the host-truth side of
        the integrity scrubber's ``tile_sums`` check)."""
        tc = self.mat.tile_cols
        occ_rb, occ_slot = np.nonzero(tc >= 0)
        tid = self.tile_idx2d[occ_rb, occ_slot]
        per_tile = self.mat.tiles.reshape(self.mat.tiles.shape[0], -1).sum(1)
        out = np.zeros(self.n_rb, per_tile.dtype)
        np.add.at(out, occ_rb, per_tile[tid])
        return out

    def copy(self) -> "HostTilePool":
        m = self.mat
        return HostTilePool(ops.BlockSparse(
            n_rows=m.n_rows, n_cols=m.n_cols, block=m.block,
            max_tiles=m.max_tiles, tiles=m.tiles.copy(),
            tile_cols=m.tile_cols.copy(), tile_idx=m.tile_idx.copy()))


ADMIT_BUCKET = 8     # minimum padded admit-payload length (tiles)


@jax.jit
def _admit_scatter(slab: jnp.ndarray, payload: jnp.ndarray,
                   slots: jnp.ndarray) -> jnp.ndarray:
    """One batched host→device gather landing: padded payload entries carry
    slot == slab capacity and are dropped by the out-of-bounds scatter."""
    return slab.at[slots].set(payload, mode="drop")


class HotSetManager:
    """Fixed-budget device slab of hot row-blocks over a host tile pool.

    Residency is per **row-block** (a block is resident iff every occupied
    tile of its slot row is in the slab) — the granularity the fused
    driver's frontier compaction already works at.  Slab slot 0 is a
    permanent all-zero tile that every non-resident slot maps to, so the
    device view is always a well-formed :class:`ops.BlockSparse` and the
    SpMV kernels need no tiering awareness at all.
    """

    def __init__(self, pool: HostTilePool, device_budget_bytes: int):
        B = pool.block
        dtype = pool.mat.tiles.dtype
        self.pool = pool
        self.budget_bytes = int(device_budget_bytes)
        self.tile_bytes = B * B * np.dtype(dtype).itemsize
        cap = slab_tiles_for_budget(device_budget_bytes, B, dtype)
        max_rb = int((pool.tile_cols >= 0).sum(axis=1).max(initial=1))
        if cap < max_rb + 1:
            raise ValueError(
                f"device_budget_bytes={device_budget_bytes} holds only "
                f"{cap} tile(s) — too small to make a single row-block "
                f"resident: {budget_hint(B, dtype, max_tiles_rb=max_rb)}")
        self.slab_cap = cap
        n_rb = pool.n_rb
        # host bookkeeping
        self.resident = np.zeros(n_rb, bool)
        self.last_touch = np.zeros(n_rb, np.int64)
        self._last_admit = np.zeros(n_rb, np.int64)
        self._ref = np.zeros(n_rb, bool)          # second-chance bit
        self._step = 0
        self._slot_of_tile = np.zeros(pool.mat.tiles.shape[0], np.int32)
        self._free: List[int] = list(range(cap - 1, 0, -1))  # slot 0 reserved
        self._rb_slots: Dict[int, List[int]] = {}
        self._tables_dirty = True
        # device state
        self._slab = jnp.zeros((cap,) + pool.mat.tiles.shape[1:], dtype)
        self._dev_tile_cols = jnp.asarray(pool.tile_cols)
        self._dev_tile_idx = jnp.zeros((n_rb * pool.mat.max_tiles,),
                                       jnp.int32)
        self._rb_res = jnp.zeros((n_rb,), bool)
        self.counters = {"hits": 0, "misses": 0, "evictions": 0,
                         "admitted_tiles": 0, "transfer_bytes": 0,
                         "refill_drives": 0, "refill_stalls": 0}

    # -- device view ---------------------------------------------------------
    def view(self) -> ops.BlockSparse:
        """The slab as an ordinary BlockSparse (what the fused driver and
        the SpMV kernels consume — same slot-table indirection, slab-slot
        tile ids)."""
        m = self.pool.mat
        return ops.BlockSparse(
            n_rows=m.n_rows, n_cols=m.n_cols, block=m.block,
            max_tiles=m.max_tiles, tiles=self._slab,
            tile_cols=self._dev_tile_cols, tile_idx=self._dev_tile_idx)

    @property
    def rb_res(self) -> jnp.ndarray:
        return self._rb_res

    def adopt_view(self, mat: ops.BlockSparse) -> None:
        """Re-adopt a functionally patched view (e.g. after a corruption
        injection rebinding ``tiles`` / ``tile_cols``) so the manager's
        device handles stay the scrubber's single source of slab state."""
        self._slab = mat.tiles
        self._dev_tile_cols = mat.tile_cols
        self._dev_tile_idx = mat.tile_idx

    # -- invalidation --------------------------------------------------------
    def invalidate(self, touched_rb: np.ndarray, *,
                   structure_changed: bool = False) -> None:
        """Drop residency of delta-touched row-blocks (their slab tiles are
        stale); the next :meth:`admit` re-gathers them from host truth.
        ``structure_changed`` additionally marks the slot tables dirty (the
        pool rewidened or appended tiles)."""
        rbs = np.asarray(touched_rb, np.int64).reshape(-1)
        # grow the tile→slot map FIRST: _drop reads post-growth tile ids
        # from the pool's (possibly just-rewidened) tile_idx2d
        cap = self.pool.mat.tiles.shape[0]
        if cap > len(self._slot_of_tile):
            grown = np.zeros(cap, np.int32)
            grown[:len(self._slot_of_tile)] = self._slot_of_tile
            self._slot_of_tile = grown
            self._tables_dirty = True
        for rb in rbs.tolist():
            self._drop(int(rb))
        if len(rbs) or structure_changed:
            self._tables_dirty = True

    def invalidate_all(self) -> None:
        self.invalidate(np.nonzero(self.resident)[0],
                        structure_changed=True)

    def _drop(self, rb: int) -> None:
        if not self.resident[rb]:
            return
        for slot in self._rb_slots.pop(rb, ()):
            self._free.append(slot)
        self.resident[rb] = False
        self._ref[rb] = False
        # tiles of rb fall back to the zero slot
        tc = self.pool.tile_cols[rb]
        tid = self.pool.tile_idx2d[rb][tc >= 0]
        self._slot_of_tile[tid] = 0

    # -- eviction (clock / second-chance over last_touch) --------------------
    def _evict_until(self, need: int, protected: np.ndarray) -> None:
        """Free slab slots until ``need`` fit, walking resident blocks
        oldest-touch-first; a block whose reference bit is set since the
        hand last passed is skipped once (second chance)."""
        while len(self._free) < need:
            cand = np.nonzero(self.resident & ~protected)[0]
            if len(cand) == 0:
                return                      # nothing evictable; caller defers
            order = cand[np.argsort(self.last_touch[cand], kind="stable")]
            evicted = False
            for rb in order.tolist():
                if self._ref[rb]:
                    self._ref[rb] = False   # second chance
                    continue
                self._drop(int(rb))
                self.counters["evictions"] += 1
                evicted = True
                break
            if not evicted:
                # every candidate spent its second chance this pass; the
                # next pass evicts the oldest unconditionally
                self._ref[order] = False

    # -- admission -----------------------------------------------------------
    def admit(self, want_rb: np.ndarray) -> int:
        """Make the requested row-blocks device-resident (as many as fit):
        one batched, bucket-padded tile gather + one slot-table upload.
        Returns the number admitted (misses that fit).  Blocks that do not
        fit stay non-resident — the driver defers them and the session's
        refill loop retries after this admission freed/landed others."""
        self._step += 1
        want = np.unique(np.asarray(want_rb, np.int64).reshape(-1))
        want = want[(want >= 0) & (want < self.pool.n_rb)]
        if len(want) == 0:
            if self._tables_dirty:
                self._upload_tables()
            return 0
        hit = self.resident[want]
        self.counters["hits"] += int(hit.sum())
        self.counters["misses"] += int((~hit).sum())
        self.last_touch[want] = self._step
        self._ref[want] = True
        missing = want[~hit]
        # fairness: least-recently-admitted first, else a want set larger
        # than the slab starves its tail forever (sorted order would hand
        # the same leading blocks the slab on every refill round)
        missing = missing[np.argsort(self._last_admit[missing],
                                     kind="stable")]
        protected = np.zeros(self.pool.n_rb, bool)
        protected[want] = True
        admitted = 0
        tids: List[np.ndarray] = []
        slots: List[int] = []
        tc = self.pool.tile_cols
        ti = self.pool.tile_idx2d
        for rb in missing.tolist():
            rb_tid = ti[rb][tc[rb] >= 0]
            need = len(rb_tid)
            if need > len(self._free):
                self._evict_until(need, protected)
            if need > len(self._free):
                continue                    # defer: retried next refill
            rb_slots = [self._free.pop() for _ in range(need)]
            self._rb_slots[rb] = rb_slots
            self._slot_of_tile[rb_tid] = np.asarray(rb_slots, np.int32)
            self.resident[rb] = True
            self._last_admit[rb] = self._step
            tids.append(rb_tid)
            slots.extend(rb_slots)
            admitted += 1
        if tids:
            tid_all = np.concatenate(tids)
            payload = self.pool.mat.tiles[tid_all]      # host gather
            k = len(slots)
            k_pad = ops.capacity_bucket(k, ADMIT_BUCKET)
            pay = np.zeros((k_pad,) + payload.shape[1:], payload.dtype)
            pay[:k] = payload
            # padded slots target the (dropped) out-of-bounds slot
            sl = np.full(k_pad, self.slab_cap, np.int32)
            sl[:k] = np.asarray(slots, np.int32)
            self._slab = _admit_scatter(self._slab, jnp.asarray(pay),
                                        jnp.asarray(sl))
            self.counters["admitted_tiles"] += k
            self.counters["transfer_bytes"] += k * self.tile_bytes
            self._tables_dirty = True
        if self._tables_dirty:
            self._upload_tables()
        return admitted

    def _upload_tables(self) -> None:
        """Re-derive + upload the device slot tables and residency from the
        host bookkeeping (index-sized; counted in transfer_bytes)."""
        pool = self.pool
        dev_idx = self._slot_of_tile[pool.tile_idx2d.reshape(-1)]
        self._dev_tile_cols = jnp.asarray(pool.tile_cols)
        self._dev_tile_idx = jnp.asarray(dev_idx)
        self._rb_res = jnp.asarray(self.resident)
        self.counters["transfer_bytes"] += (
            pool.tile_cols.nbytes + dev_idx.nbytes + self.resident.nbytes)
        self._tables_dirty = False

    # -- introspection -------------------------------------------------------
    def device_bytes(self) -> int:
        return int(self._slab.nbytes + self._dev_tile_cols.nbytes
                   + self._dev_tile_idx.nbytes + self._rb_res.nbytes)

    def stats(self) -> dict:
        c = self.counters
        lookups = c["hits"] + c["misses"]
        return {
            "slab_tiles": int(self.slab_cap),
            "slab_bytes": int(self.slab_cap * self.tile_bytes),
            "budget_bytes": int(self.budget_bytes),
            "pool_tiles": int(self.pool.mat.tiles.shape[0]),
            "pool_bytes": int(self.pool.nbytes),
            "resident_blocks": int(self.resident.sum()),
            "hit_rate": (c["hits"] / lookups) if lookups else 1.0,
            **{k: int(v) for k, v in c.items()},
        }

    def scrub(self, slab_tiles: Optional[np.ndarray] = None) -> List[dict]:
        """CRC the slab's resident tiles against the host tier (the twin
        the integrity scrubber checksums).  Returns failure dicts in the
        ``_integrity_check`` shape; empty list = clean."""
        slab = (np.asarray(self._slab) if slab_tiles is None
                else np.asarray(slab_tiles))
        bad: List[int] = []
        for rb, slots in self._rb_slots.items():
            tc = self.pool.tile_cols[rb]
            tid = self.pool.tile_idx2d[rb][tc >= 0]
            for t, s in zip(tid.tolist(), slots):
                a = zlib.crc32(np.ascontiguousarray(
                    self.pool.mat.tiles[t]).tobytes())
                b = zlib.crc32(np.ascontiguousarray(slab[s]).tobytes())
                if a != b:
                    bad.append(rb)
                    break
        if bad:
            return [{"check": "hot_slab", "row_blocks": sorted(bad)[:8]}]
        return []

    def fork(self, pool: HostTilePool) -> "HotSetManager":
        """Twin over a copied pool: shares the immutable slab arrays,
        copies every mutable host table and the counters."""
        new = object.__new__(HotSetManager)
        new.__dict__.update(self.__dict__)
        new.pool = pool
        new.resident = self.resident.copy()
        new.last_touch = self.last_touch.copy()
        new._last_admit = self._last_admit.copy()
        new._ref = self._ref.copy()
        new._slot_of_tile = self._slot_of_tile.copy()
        new._free = list(self._free)
        new._rb_slots = {k: list(v) for k, v in self._rb_slots.items()}
        new.counters = dict(self.counters)
        return new


def host_block_adjacency(tile_cols: np.ndarray, n_cb: int) -> np.ndarray:
    """Numpy twin of :func:`ops.block_adjacency` for the host tier (the
    stream keeps ``MatrixAux`` host-side; tiered init must not round-trip
    the table through the device just to OR it)."""
    n_rb = tile_cols.shape[0]
    out = np.zeros((n_rb, n_cb), bool)
    rb, slot = np.nonzero(tile_cols >= 0)
    out[rb, tile_cols[rb, slot]] = True
    return out


# ---------------------------------------------------------------------------
# EdgePager — the blocked oracle's analogue over per-block edge extents
# ---------------------------------------------------------------------------

#: the 8-tuple ``ensure`` returns, in sweep-operand order:
#: (src, dst, osrc, odst, in_lo, in_len, out_lo, out_len)
EdgeView = Tuple


@dataclasses.dataclass
class _HostEdges:
    """Host copies of a snapshot's per-block edge extents."""
    src: np.ndarray
    dst: np.ndarray
    in_ptr: np.ndarray
    osrc: np.ndarray
    odst: np.ndarray
    out_ptr: np.ndarray


class EdgePager:
    """Host-paged per-block edge extents for :func:`run_blocked`.

    The oracle's sweep reads each active block's in-edge slice (pull) and
    out-edge slice (expansion).  The pager keeps both on host and stages
    the active set's slices into two fixed device slabs before each sweep;
    per-block ``lo``/``len`` tables (full-length, index-sized) redirect the
    sweep into the slab.  A sweep whose active set outgrows the slab
    *repacks*: blocks outside the requested set are dropped (counted as
    evictions) and the slab is rebuilt from the want set; a want set that
    cannot fit at all raises with the sizing rule.  The blocked engine
    already pays a host sync per sweep, so the staging adds no new
    synchronization points.
    """

    def __init__(self, g, budget_bytes: int):
        self.h = _HostEdges(
            src=np.asarray(g.src), dst=np.asarray(g.dst),
            in_ptr=np.asarray(g.in_block_ptr, np.int64),
            osrc=np.asarray(g.osrc), odst=np.asarray(g.odst),
            out_ptr=np.asarray(g.out_block_ptr, np.int64))
        self.n_blocks = len(self.h.in_ptr) - 1
        # 4 slab arrays (in src/dst + out src/dst) of int32
        cap = int(budget_bytes) // (4 * 4)
        sizes = (np.diff(self.h.in_ptr) + np.diff(self.h.out_ptr))
        if cap < int(sizes.max(initial=1)) + 1:
            raise ValueError(
                f"edge budget {budget_bytes} bytes holds {cap} edges per "
                f"slab but the largest block needs {int(sizes.max())} — "
                "raise the budget above max_block_edges * 16 bytes")
        self.cap = cap
        guard = 1024                       # dynamic_slice tail guard
        self._hsrc = np.zeros(cap + guard, np.int32)
        self._hdst = np.zeros(cap + guard, np.int32)
        self._hosrc = np.zeros(cap + guard, np.int32)
        self._hodst = np.zeros(cap + guard, np.int32)
        self._in_lo = np.zeros(self.n_blocks + 1, np.int32)
        self._in_len = np.zeros(self.n_blocks, np.int32)
        self._out_lo = np.zeros(self.n_blocks + 1, np.int32)
        self._out_len = np.zeros(self.n_blocks, np.int32)
        self._resident = np.zeros(self.n_blocks, bool)
        self._cursor = 0                   # bump allocator over the slab
        self._dirty = True
        self._dev = None
        self.counters = {"hits": 0, "misses": 0, "evictions": 0,
                         "repacks": 0, "transfer_bytes": 0}

    def _stage(self, b: int) -> bool:
        h = self.h
        ilo, ihi = int(h.in_ptr[b]), int(h.in_ptr[b + 1])
        olo, ohi = int(h.out_ptr[b]), int(h.out_ptr[b + 1])
        need = max(ihi - ilo, ohi - olo)
        if self._cursor + need > self.cap:
            return False
        at = self._cursor
        self._hsrc[at:at + ihi - ilo] = h.src[ilo:ihi]
        self._hdst[at:at + ihi - ilo] = h.dst[ilo:ihi]
        self._hosrc[at:at + ohi - olo] = h.osrc[olo:ohi]
        self._hodst[at:at + ohi - olo] = h.odst[olo:ohi]
        self._in_lo[b], self._in_len[b] = at, ihi - ilo
        self._out_lo[b], self._out_len[b] = at, ohi - olo
        self._cursor = at + need
        self._resident[b] = True
        self._dirty = True
        return True

    def ensure(self, block_ids: np.ndarray):
        """Stage the given blocks, repacking the slab if they do not fit;
        returns the device EdgeView (stable shapes) for the sweep."""
        ids = np.unique(np.asarray(block_ids, np.int64).reshape(-1))
        ids = ids[(ids >= 0) & (ids < self.n_blocks)]
        hit = self._resident[ids]
        self.counters["hits"] += int(hit.sum())
        self.counters["misses"] += int((~hit).sum())
        missing = ids[~hit].tolist()
        for b in list(missing):
            if self._stage(int(b)):
                missing.remove(b)
        if missing:
            # repack: keep only the want set, then stage the rest
            self.counters["repacks"] += 1
            self.counters["evictions"] += int(
                (self._resident & ~np.isin(np.arange(self.n_blocks),
                                           ids)).sum())
            keep = [int(b) for b in ids if self._resident[b]]
            self._resident[:] = False
            self._cursor = 0
            for b in keep + [int(b) for b in missing]:
                if not self._stage(b):
                    raise ValueError(
                        "active set does not fit the edge slab even after "
                        "a repack — raise the pager budget")
        if self._dirty:
            self._dev = tuple(jnp.asarray(a) for a in (
                self._hsrc, self._hdst, self._hosrc, self._hodst,
                self._in_lo[:-1], self._in_len,
                self._out_lo[:-1], self._out_len))
            self.counters["transfer_bytes"] += sum(
                a.nbytes for a in (self._hsrc, self._hdst, self._hosrc,
                                   self._hodst))
            self._dirty = False
        return self._dev

    def stats(self) -> dict:
        c = self.counters
        lookups = c["hits"] + c["misses"]
        return {"slab_edges": int(self.cap),
                "hit_rate": (c["hits"] / lookups) if lookups else 1.0,
                **{k: int(v) for k, v in c.items()}}


def paged_snapshot(g):
    """A twin of ``g`` whose O(m) edge arrays are 1-element stubs — pass it
    to ``run_blocked(..., pager=EdgePager(g, budget))`` so the device never
    holds the full CSR: the pager's bounded slab becomes the only O(edges)
    device allocation.  The index-sized per-block ptr tables and per-vertex
    arrays are kept (the sweep still reads ``vertex_valid``/``out_deg``).
    Build the :class:`EdgePager` from the *original* snapshot first — it
    copies the edge arrays to host in its constructor."""
    z = jnp.zeros((1,), jnp.int32)
    return dataclasses.replace(g, src=z, dst=z, osrc=z, odst=z)
