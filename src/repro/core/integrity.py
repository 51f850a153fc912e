"""State integrity under silent corruption (the corruption fault domain).

The paper's fault model — and the repo's first four domains
(thread/shard/process/session) — covers *visible* failures: a crashed
thread, a dead shard, a SIGKILLed process, a hung serving slot.  This
module covers the failure the fleet does not announce: a flipped bit in
the tile pool, a torn operand-mirror scatter, a drifted rank vector.
Three layers:

* **Invariant checks on the live iterate** (:func:`invariant_vec`): a
  correct (near-)converged PageRank iterate conserves rank mass
  (|Σx − 1| ≤ ε — every vertex carries a self-loop so no mass leaks
  through dangling nodes), is non-negative, is finite, and between two
  drives is *bit-identical* to the last verified iterate (queries never
  write ranks), so any L∞ drift without an intervening update is
  corruption.  The vector is computed on device and fetched fused with
  the driver's stats vector — one ``block_until_ready`` per drive, no
  extra host sync (`session._drive`).
* **Checksummed device state** (:func:`compare_digests`,
  :func:`tile_row_sums`, :func:`check_slot_tables`): chunked CRC32
  digests of the operand mirrors (``out_deg``/``rb_in``/``rb_out``/
  ``bmat``) against their host-truth twins (`MatrixAux` + the host
  graph), a per-row-block tile-pool sum check (every stored entry of the
  pull matrix is 1.0, so the live entries of row-block *i* must sum to
  exactly ``rb_in[i]``), and structural validation of the slot tables
  against the block-adjacency truth.  A background scrubber thread in
  ``PageRankService`` runs these on idle slots.
* **A repair ladder** (driven by ``PageRankSession.verify``): re-mark
  corrupted rows into the DF frontier and re-converge via the helping
  path (rung ``"frontier"`` — the paper's mechanism, repairing
  corruption instead of crashes), escalate to an operand-mirror /
  tile-pool rebuild from the host slot tables (rung ``"rebuild"``), and
  finally to a checkpoint+WAL restore (rung ``"restore"``).  Each rung
  emits a ``RecoveryRecord(domain="corruption")``.

Detection guarantees are calibrated, not absolute: sign/exponent-range
bit flips (the flips that change a value by ≥ 2×) are always caught;
a mantissa-tail flip in a *rank* is caught by the exact drift check,
while a mantissa-tail flip in a tile value below the 0.25 count
tolerance is the documented blind spot of the sum check.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp


#: Checks run by ``session.verify()`` (docs/FAULTS.md has the tolerances).
INTEGRITY_CHECKS = ("rank_mass", "rank_negativity", "rank_finite",
                    "rank_drift", "mirror_digest", "tile_sums",
                    "slot_tables", "graph_digest")

#: Repair-ladder rungs, cheapest first.
REPAIR_RUNGS = ("frontier", "rebuild", "restore")

#: Fields of the fused invariant vector, in order.
INVARIANT_FIELDS = ("mass_error", "negative", "nonfinite", "drift")
N_INVARIANTS = len(INVARIANT_FIELDS)


@dataclasses.dataclass(frozen=True)
class IntegrityConfig:
    """The ``EngineConfig(integrity=…)`` axis.

    ``mass_tol`` bounds |Σx − 1| on a converged iterate (the residual of
    an *unconverged* sweep-capped iterate contributes ≤ n·τ, so the
    default 1e-6 is safe for n up to ~10⁴ at τ=1e-10; scale it with n·τ
    for larger streams).  ``drift_tol`` bounds L∞ movement of the ranks
    *between* drives — legitimately zero, so the default is tight.
    ``scrub_interval_s`` paces the service scrubber per slot;
    ``scrub_chunk_bytes`` sizes the CRC chunks (smaller chunks localize
    a corrupted region at more digest overhead).  ``auto_repair`` lets
    a failed check climb the repair ladder automatically; ``fused``
    keeps the per-drive invariant fetch on (it rides the existing
    stats sync, so the cost is a handful of device FLOPs).
    """
    mass_tol: float = 1e-6
    drift_tol: float = 1e-9
    scrub_interval_s: float = 0.25
    scrub_chunk_bytes: int = 1 << 20
    auto_repair: bool = True
    fused: bool = True

    def __post_init__(self):
        if not (self.mass_tol > 0):
            raise ValueError(f"mass_tol must be > 0, got {self.mass_tol}")
        if not (self.drift_tol > 0):
            raise ValueError(f"drift_tol must be > 0, got {self.drift_tol}")
        if not (self.scrub_interval_s > 0):
            raise ValueError("scrub_interval_s must be > 0, got "
                             f"{self.scrub_interval_s}")
        if int(self.scrub_chunk_bytes) < 64:
            raise ValueError("scrub_chunk_bytes must be >= 64, got "
                             f"{self.scrub_chunk_bytes}")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def coerce(cls, value: Any) -> Optional["IntegrityConfig"]:
        """None | IntegrityConfig | kwargs-dict → IntegrityConfig (or None).
        The dict form is what ``SessionStore`` meta round-trips."""
        if value is None or isinstance(value, cls):
            return value
        if isinstance(value, dict):
            return cls(**value)
        raise TypeError(
            f"integrity must be an IntegrityConfig or a kwargs dict, got "
            f"{type(value).__name__}")


# ---------------------------------------------------------------------------
# invariant checks on the live iterate
# ---------------------------------------------------------------------------

@jax.jit
def invariant_vec(R: jnp.ndarray, R_ref: jnp.ndarray,
                  valid: jnp.ndarray) -> jnp.ndarray:
    """[mass_error, negative_count, nonfinite_count, linf_drift] of the
    iterate, on device.  Fuse the fetch with the driver's stats vector
    (concatenate, one ``block_until_ready``) to keep the drive at a
    single host sync.  ``R_ref`` is the last verified iterate; pass
    ``R`` itself to skip the drift term (it is then exactly 0)."""
    x = jnp.where(valid, R, 0.0)
    finite = jnp.isfinite(R)
    # a non-finite iterate would poison the mass sum: mask it out so the
    # mass and drift terms stay informative alongside the finite count
    xf = jnp.where(finite, x, 0.0)
    mass_err = jnp.abs(jnp.sum(xf) - 1.0)
    neg = jnp.sum((xf < 0) & valid)
    nonfinite = jnp.sum(valid & ~finite)
    ref = jnp.where(valid & jnp.isfinite(R_ref), R_ref, 0.0)
    drift = jnp.max(jnp.abs(xf - ref))
    return jnp.stack([mass_err, neg.astype(R.dtype),
                      nonfinite.astype(R.dtype), drift])


# ---------------------------------------------------------------------------
# chunked checksums: device state vs host truth
# ---------------------------------------------------------------------------

def chunked_crc32(arr: np.ndarray, *,
                  chunk_bytes: int = 1 << 20) -> Tuple[int, ...]:
    """CRC32 digest of an array in fixed-size byte chunks (the repo's
    checkpoint idiom, ``ckpt/checkpoint.py``, chunked so a mismatch
    localizes the corrupted region)."""
    b = np.ascontiguousarray(arr).tobytes()
    step = max(64, int(chunk_bytes))
    if not b:
        return (0,)
    return tuple(zlib.crc32(b[i:i + step]) & 0xFFFFFFFF
                 for i in range(0, len(b), step))


def compare_digests(device_arr, host_arr, *,
                    chunk_bytes: int = 1 << 20) -> List[int]:
    """Chunk indices where a device mirror's digest disagrees with its
    host-truth twin (empty list = clean).  The host side is normalized
    to the device dtype first so the comparison is value-exact, not
    representation-accidental."""
    a = np.asarray(device_arr)
    b = np.asarray(host_arr)
    if a.shape != b.shape:
        return [-1]
    da = chunked_crc32(a, chunk_bytes=chunk_bytes)
    db = chunked_crc32(b.astype(a.dtype, copy=False),
                       chunk_bytes=chunk_bytes)
    if len(da) != len(db):
        return [-1]
    return [i for i, (x, y) in enumerate(zip(da, db)) if x != y]


@jax.jit
def _tile_row_sums(tiles: jnp.ndarray, tile_cols: jnp.ndarray,
                   tile_idx: jnp.ndarray) -> jnp.ndarray:
    n_rb, mt = tile_cols.shape
    T = tiles[tile_idx.reshape(n_rb, mt)]          # [n_rb, mt, *tile]
    occ = (tile_cols >= 0)[:, :, None, None]
    return jnp.sum(jnp.where(occ, T, 0), axis=(1, 2, 3))


def tile_row_sums(mat, *, chunk_rb: int = 0) -> np.ndarray:
    """Per-row-block sum of the live tiles of a pull matrix.  Every
    stored entry is 1.0 (one per in-edge incl. the self-loop), so row-
    block *i* must sum to exactly ``rb_in[i]`` — an aggregate checksum
    of the tile pool that needs no host twin of the tiles themselves.
    ``chunk_rb`` bounds the per-call gather footprint (0 = one call)."""
    tile_cols = mat.tile_cols
    n_rb = int(tile_cols.shape[0])
    mt = int(tile_cols.shape[1])
    tidx = mat.tile_idx.reshape(n_rb, mt)
    if chunk_rb <= 0 or chunk_rb >= n_rb:
        return np.asarray(_tile_row_sums(mat.tiles, tile_cols,
                                         mat.tile_idx))
    out = []
    for i in range(0, n_rb, chunk_rb):
        out.append(np.asarray(_tile_row_sums(
            mat.tiles, tile_cols[i:i + chunk_rb],
            tidx[i:i + chunk_rb].reshape(-1))))
    return np.concatenate(out)


def check_slot_tables(tile_cols: np.ndarray, tile_idx: np.ndarray,
                      bmat: np.ndarray, tile_capacity: int) -> List[dict]:
    """Structural validation of the slot tables against the host
    block-adjacency truth.  Catches bit flips in ``tile_cols`` /
    ``tile_idx``: out-of-range columns or tile ids, duplicate columns in
    one row, and occupancy that disagrees with ``bmat`` (occupancy and
    block adjacency grow in lock-step — tiles emptied by deletions stay
    referenced, `kernels/block_spmv/ops.py`)."""
    problems: List[dict] = []
    tile_cols = np.asarray(tile_cols)
    tile_idx = np.asarray(tile_idx).reshape(tile_cols.shape)
    bmat = np.asarray(bmat, bool)
    n_rb, n_cb = bmat.shape
    occ = tile_cols >= 0
    if tile_cols.min(initial=0) < -1 or \
            (occ & (tile_cols >= n_cb)).any():
        problems.append({"check": "slot_tables", "what": "col_range"})
    tid = tile_idx[occ]
    if len(tid) and (tid.min() < 0 or tid.max() >= tile_capacity
                     or len(np.unique(tid)) != len(tid)):
        problems.append({"check": "slot_tables", "what": "tile_idx"})
    # occupancy vs block adjacency (and duplicate columns, via counting)
    cols = np.clip(tile_cols, 0, n_cb - 1)
    counts = np.zeros((n_rb, n_cb), np.int64)
    rb = np.broadcast_to(np.arange(n_rb)[:, None], tile_cols.shape)
    np.add.at(counts, (rb[occ], cols[occ]), 1)
    if (counts > 1).any():
        problems.append({"check": "slot_tables", "what": "col_dup"})
    mism = np.nonzero((counts > 0) != bmat)
    if len(mism[0]):
        problems.append({"check": "slot_tables", "what": "bmat_mismatch",
                         "row_blocks": sorted(set(int(r)
                                                  for r in mism[0]))[:8]})
    return problems


# ---------------------------------------------------------------------------
# corruption injection primitives (chaos harness + tests)
# ---------------------------------------------------------------------------

def flipped_float(value, bit: int) -> float:
    """``value`` with IEEE bit ``bit`` flipped (f32 or f64).  Exponent /
    sign bits (52..63 for f64) produce the ≥2× perturbations the
    invariant and sum checks are calibrated to always catch."""
    dt = np.dtype(np.asarray(value).dtype)
    if dt.itemsize == 8:
        u = np.asarray(value, dt).view(np.uint64) ^ np.uint64(1 << bit)
        return float(u.view(dt))
    u = np.asarray(value, np.float32).view(np.uint32) ^ np.uint32(1 << bit)
    return float(u.view(np.float32))


def exponent_bit(dtype, rng: np.random.Generator) -> int:
    """A deterministic exponent-range bit index for ``dtype``."""
    if np.dtype(dtype).itemsize == 8:
        return int(rng.integers(52, 62))
    return int(rng.integers(23, 30))


# ---------------------------------------------------------------------------
# verify() result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IntegrityReport:
    """Result of one ``session.verify()`` pass: what was checked, what
    failed (before any repair), which ladder rungs ran, and whether the
    final state is clean."""
    ok: bool
    checks_run: int
    failures: List[Dict[str, Any]]
    repairs: List[str]                  # rungs applied, in order
    mass_error: float
    drift: float
    wall_time_s: float

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ok": bool(self.ok),
            "checks_run": int(self.checks_run),
            "failures": list(self.failures),
            "repairs": list(self.repairs),
            "mass_error": float(self.mass_error),
            "drift": float(self.drift),
            "wall_time_s": round(float(self.wall_time_s), 6),
        }
