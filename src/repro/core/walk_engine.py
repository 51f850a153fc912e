"""Monte Carlo walk engine — sweep-free (personalized) PageRank.

Implements the Bahmani et al. *Fast Incremental and Personalized PageRank*
scheme on top of the repo's incremental-graph discipline: the engine state
is ``R`` fixed-length-capped random-walk segments per vertex, resident on
device in capacity-padded buffers, plus a per-vertex visit counter folded
incrementally.  There is no sweep loop anywhere:

  * **estimate** — a walk from ``v`` continues with probability ``alpha``
    and picks a uniform out-neighbor (the snapshot's implicit self-loop
    included, so the stationary target matches the pull engines' graph)
    until it terminates or hits the ``L``-step cap.  With ``X_u`` = total
    visits to ``u`` over all ``n*R`` walks, ``PR(u) ≈ X_u (1-α) / (nR)``;
    restricting to the walks started at a seed set ``S`` gives
    ``PPR_S(u) ≈ X_u^S (1-α) / (|S| R)``.  Both are O(read) queries over
    device-resident state.
  * **update** — an edge delta only changes the trajectories of walks that
    *visit a touched vertex* (a source endpoint of an effective edge
    change): every per-walk random draw is a pure function of
    ``(walk_seed, walk id)`` and adjacency rows are kept **sorted**, so an
    untouched walk is bit-identical under the old and new graph, and
    delete+reinsert of the same edge restores the walk buffers exactly.
    A host-side reverse index (vertex → walks visiting it) selects the
    touched walks in O(touched-walk mass); the regeneration batch is
    padded onto a doubling ladder (same discipline as the tile pool) and
    rebuilt by one bucketed scatter — never a global regeneration, which
    :meth:`WalkState.apply_batch` asserts.

Adjacency lives in CSR-style per-vertex slabs (``[n+1, cap]`` with a
sentinel row/values at ``n``), patched O(batch) per delta on the host twin
and scattered to the device mirror at a bucketed batch width.  The slab
width ``cap`` sits on its own capacity ladder and widens (one legitimate
bucket compile) when a vertex outgrows it.

Registered through :mod:`repro.api.registry` as the builtin ``walk``
engine with ``supports={"ppr"}`` — the only engine that accepts
personalization; the config layer rejects walk fields on every other
engine (:class:`repro.api.registry.CapabilityError`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Set, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import blocked as blk
from repro.core.graph import GraphSnapshot, HostGraph
from repro.kernels.block_spmv import ops

# Capacity-ladder bases (doubling discipline; see ops.capacity_bucket).
WALK_BATCH_BUCKET = 64     # regeneration scatter-batch floor
ADJ_SLOT_BASE = 8          # per-vertex adjacency slab-width floor

# Defaults EngineConfig resolves its None walk fields to.
DEFAULT_WALKS_PER_VERTEX = 16
DEFAULT_WALK_LENGTH = 48
DEFAULT_WALK_SEED = 0


# ---------------------------------------------------------------------------
# jitted kernels (shapes ride the capacity ladders; cache growth outside a
# first bucket visit is a retrace bug, counted via cache_size())
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("R",))
def _regen_step(walks, counts, adj, deg, wids, alpha, key, *, R: int):
    """Regenerate the walks named by ``wids`` and fold the visit counters.

    ``walks [n*R+1, L]`` i32 vertex ids (sentinel ``n`` past termination;
    row ``n*R`` is the inert scratch row padding scatters land on);
    ``counts [n+1]`` i32 (slot ``n`` absorbs sentinel visits and is reset);
    ``adj [n+1, cap]`` / ``deg [n+1]`` the adjacency slabs; ``wids [B]``
    i32 walk ids, padded with ``n*R``.  Each walk's draws come from
    ``fold_in(key, wid)`` only, so a trajectory is a pure function of
    (seed, walk id, adjacency rows it visits) — the delta-localization
    property rests on exactly this.
    """
    L = walks.shape[1]
    n = counts.shape[0] - 1
    nr = walks.shape[0] - 1
    sent = jnp.int32(n)
    starts = jnp.where(wids < nr, wids // R, nr // R).astype(jnp.int32)
    old = walks[wids]                                        # [B, L]
    keys = jax.vmap(lambda w: jax.random.fold_in(key, w))(wids)
    u = jax.vmap(lambda k: jax.random.uniform(k, (2, L)))(keys)
    r_term = jnp.swapaxes(u[:, 0, :], 0, 1)                  # [L, B]
    r_nbr = jnp.swapaxes(u[:, 1, :], 0, 1)

    def step(carry, rnd):
        cur, alive = carry
        rt, rn = rnd
        d = deg[cur]
        # uniform over the d real out-neighbors plus the implicit
        # self-loop (index d) — matches the snapshot's self-loop semantics
        j = jnp.minimum((rn * (d + 1).astype(rn.dtype)).astype(jnp.int32),
                        d)
        nxt = jnp.where(j == d, cur, adj[cur, j])
        alive = alive & (rt < alpha)
        cur = jnp.where(alive, nxt, cur)
        return (cur, alive), jnp.where(alive, nxt, sent)

    (_, _), tail = lax.scan(step, (starts, starts < sent),
                            (r_term[:L - 1], r_nbr[:L - 1]))
    traj = jnp.concatenate([starts[:, None],
                            jnp.swapaxes(tail, 0, 1)], axis=1)
    clip = lambda a: jnp.minimum(a, sent).ravel()            # noqa: E731
    counts = (counts.at[clip(old)].add(-1)
                    .at[clip(traj)].add(1)
                    .at[n].set(0))
    return walks.at[wids].set(traj), counts


@jax.jit
def _patch_rows(adj, deg, idx, rows, degs):
    """Scatter patched adjacency rows (bucketed; padding targets the
    sentinel row ``n`` with sentinel content, which is a no-op)."""
    return adj.at[idx].set(rows), deg.at[idx].set(degs)


@partial(jax.jit, static_argnames=("R", "dtype"))
def _ppr_full(walks, seeds, alpha, *, R: int, dtype):
    """Personalized PageRank estimate for a uniform restart over ``seeds``:
    fold the visit counts of the seeds' own walks — O(|S|·R·L) device
    work, independent of the batch history."""
    L = walks.shape[1]
    nr = walks.shape[0] - 1
    n = nr // R
    s = seeds.shape[0]
    rows = (seeds.astype(jnp.int32)[:, None] * R
            + jnp.arange(R, dtype=jnp.int32)[None, :]).reshape(-1)
    t = walks[rows]                                          # [s*R, L]
    visits = jnp.zeros(n + 1, jnp.int32).at[
        jnp.minimum(t, jnp.int32(n)).ravel()].add(1)[:n]
    scale = (1.0 - alpha).astype(dtype) / (s * R)
    return visits.astype(dtype) * scale


@partial(jax.jit, static_argnames=("R", "k", "dtype"))
def _ppr_topk(walks, seeds, alpha, *, R: int, k: int, dtype):
    ppr = _ppr_full(walks, seeds, alpha, R=R, dtype=dtype)
    return lax.top_k(ppr, k)


@partial(jax.jit, static_argnames=("R", "dtype"))
def _pr_estimate(counts, alpha, *, R: int, dtype):
    n = counts.shape[0] - 1
    scale = (1.0 - alpha).astype(dtype) / (n * R)
    return counts[:n].astype(dtype) * scale


def cache_size() -> int:
    """Total jit-cache entries of the walk hot-path kernels (the walk
    engine's analog of the fused driver's cache; query kernels are
    excluded — they legitimately compile per (|S|, k) shape)."""
    return int(_regen_step._cache_size()) + int(_patch_rows._cache_size())


# ---------------------------------------------------------------------------
# walk store
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WalkUpdateStats:
    """Per-delta localization accounting (the acceptance instrument)."""
    touched_vertices: int       # distinct src endpoints of effective edges
    touched_walk_mass: int      # Σ_u |walks visiting u| over touched u
    regenerated_walks: int      # |union| — walks actually rebuilt
    total_walks: int            # n * R
    steps: int                  # walk steps recomputed (work metric)
    batch_bucket: int           # padded regeneration width (ladder bucket)
    adj_cap: int                # adjacency slab width after the batch
    new_bucket: bool            # first visit to a ladder bucket this batch


class WalkState:
    """Device-resident Monte Carlo walk store over an incremental
    adjacency.  One instance backs one walk-engine session; ``fork()``
    shares the (immutable) device buffers and copies the host twins."""

    def __init__(self, hg: HostGraph, *,
                 R: int = DEFAULT_WALKS_PER_VERTEX,
                 L: int = DEFAULT_WALK_LENGTH,
                 seed: int = DEFAULT_WALK_SEED,
                 alpha: float = 0.85,
                 dtype=np.float64):
        if int(R) < 1:
            raise ValueError(f"walks_per_vertex={R} must be >= 1")
        if int(L) < 2:
            raise ValueError(f"walk_length={L} must be >= 2 (a walk is its "
                             "start vertex plus at least one step slot)")
        self.n = int(hg.n)
        self.R = int(R)
        self.L = int(L)
        self.seed = int(seed)
        self.alpha = float(alpha)
        self.dtype = np.dtype(dtype)
        self._key = jax.random.PRNGKey(self.seed)
        self._alpha_op = jnp.float32(self.alpha)
        n, nr = self.n, self.n * self.R

        # -- adjacency slabs: host truth + device mirror ------------------
        # rows are kept SORTED by destination id so a delete+reinsert of
        # the same edge restores the row (and thus every walk through it)
        # bit-for-bit — hg.edges is already (src, dst)-sorted
        src = hg.edges[:, 0].astype(np.int64)
        dst = hg.edges[:, 1].astype(np.int64)
        degs = np.bincount(src, minlength=n).astype(np.int64) if hg.m \
            else np.zeros(n, np.int64)
        self._cap = int(ops.capacity_bucket(max(int(degs.max()) if hg.m
                                                else 1, 1), ADJ_SLOT_BASE))
        self._adj_host = np.full((n + 1, self._cap), n, np.int32)
        self._deg_host = np.zeros(n + 1, np.int32)
        if hg.m:
            col = np.arange(hg.m) - np.repeat(np.cumsum(degs) - degs, degs)
            self._adj_host[src, col] = dst.astype(np.int32)
            self._deg_host[:n] = degs.astype(np.int32)
        self.adj = jnp.asarray(self._adj_host)
        self.deg = jnp.asarray(self._deg_host)

        # -- walk buffers + counters: generate everything once ------------
        self.walks = jnp.full((nr + 1, self.L), n, jnp.int32)
        self.counts = jnp.zeros(n + 1, jnp.int32)
        self.walks, self.counts = _regen_step(
            self.walks, self.counts, self.adj, self.deg,
            jnp.arange(nr, dtype=jnp.int32), self._alpha_op, self._key,
            R=self.R)
        self._buckets: Set[Tuple] = set()   # ladder buckets seen post-init
        self._build_index()

    # -- reverse index (host): vertex -> set of walk ids visiting it -------
    def _build_index(self) -> None:
        nr = self.n * self.R
        w = np.asarray(self.walks[:nr])
        ids = np.repeat(np.arange(nr, dtype=np.int64), self.L)
        vs = w.ravel().astype(np.int64)
        keep = vs < self.n
        pairs = np.unique(vs[keep] * nr + ids[keep])
        self._index: List[Set[int]] = [set() for _ in range(self.n)]
        for v, wid in zip((pairs // nr).tolist(), (pairs % nr).tolist()):
            self._index[v].add(wid)

    def _see_bucket(self, key: Tuple) -> bool:
        """Record a ladder-bucket visit; True when it is the first."""
        new = key not in self._buckets
        self._buckets.add(key)
        return new

    # -- O(batch) delta application ----------------------------------------
    def apply_batch(self, dels: np.ndarray, ins: np.ndarray
                    ) -> WalkUpdateStats:
        """Apply one **effective** edge batch (``core.incremental.
        effective_batch`` output: every edge genuinely changes the graph)
        and regenerate exactly the walks passing through touched vertices.
        """
        n, R, nr = self.n, self.R, self.n * self.R
        dels = np.asarray(dels, np.int64).reshape(-1, 2)
        ins = np.asarray(ins, np.int64).reshape(-1, 2)
        touched = np.unique(np.concatenate([dels[:, 0], ins[:, 0]])) \
            if (len(dels) + len(ins)) else np.zeros(0, np.int64)
        new_bucket = False

        if touched.size:
            # host patch of the touched rows (sorted-set semantics)
            rows_new = []
            widest = 0
            for uu in touched.tolist():
                row = self._adj_host[uu, :self._deg_host[uu]].astype(
                    np.int64)
                du = dels[dels[:, 0] == uu, 1]
                iu = ins[ins[:, 0] == uu, 1]
                if du.size:
                    row = np.setdiff1d(row, du)
                if iu.size:
                    row = np.union1d(row, iu)
                rows_new.append(row)
                widest = max(widest, row.size)
            if widest > self._cap:      # slab ladder: widen (one compile)
                self._widen(int(ops.capacity_bucket(widest, ADJ_SLOT_BASE)))
                new_bucket = True
            for uu, row in zip(touched.tolist(), rows_new):
                self._adj_host[uu, :] = n
                self._adj_host[uu, :row.size] = row.astype(np.int32)
                self._deg_host[uu] = row.size
            # bucketed device scatter of just the touched rows
            b = int(ops.capacity_bucket(touched.size,
                                        ops.DELTA_BATCH_BUCKET))
            idx = np.full(b, n, np.int32)
            idx[:touched.size] = touched.astype(np.int32)
            vals = np.full((b, self._cap), n, np.int32)
            vals[:touched.size] = self._adj_host[touched]
            dvals = np.zeros(b, np.int32)
            dvals[:touched.size] = self._deg_host[touched]
            if self._see_bucket(("adj", b, self._cap)):
                new_bucket = True
            self.adj, self.deg = _patch_rows(
                self.adj, self.deg, jnp.asarray(idx), jnp.asarray(vals),
                jnp.asarray(dvals))

        # touched walks via the reverse index — never a buffer scan
        wset: Set[int] = set()
        mass = 0
        for uu in touched.tolist():
            s = self._index[uu]
            mass += len(s)
            wset |= s
        regen = len(wset)
        if regen > mass:        # structurally impossible; guard regardless
            raise AssertionError(
                f"regenerated-walk count {regen} exceeds the touched-walk "
                f"mass {mass}: regeneration is no longer delta-localized")

        steps = 0
        b_pad = 0
        if regen:
            wids = np.fromiter(wset, np.int64, regen)
            wids.sort()
            b_pad = int(ops.capacity_bucket(regen, WALK_BATCH_BUCKET))
            wids_pad = np.full(b_pad, nr, np.int32)
            wids_pad[:regen] = wids.astype(np.int32)
            wdev = jnp.asarray(wids.astype(np.int32))
            old_rows = np.asarray(self.walks[wdev])
            if self._see_bucket(("regen", b_pad, self._cap)):
                new_bucket = True
            self.walks, self.counts = _regen_step(
                self.walks, self.counts, self.adj, self.deg,
                jnp.asarray(wids_pad), self._alpha_op, self._key, R=self.R)
            new_rows = np.asarray(self.walks[wdev])
            steps = int((new_rows < n).sum())
            for wid, orow, nrow in zip(wids.tolist(), old_rows, new_rows):
                for v in np.unique(orow).tolist():
                    if v < n:
                        self._index[v].discard(wid)
                for v in np.unique(nrow).tolist():
                    if v < n:
                        self._index[v].add(wid)
        return WalkUpdateStats(
            touched_vertices=int(touched.size), touched_walk_mass=mass,
            regenerated_walks=regen, total_walks=nr, steps=steps,
            batch_bucket=b_pad, adj_cap=self._cap, new_bucket=new_bucket)

    def _widen(self, cap_new: int) -> None:
        """Grow the adjacency slab width to the next ladder bucket."""
        wide = np.full((self.n + 1, cap_new), self.n, np.int32)
        wide[:, :self._cap] = self._adj_host
        self._adj_host = wide
        self._cap = cap_new
        self.adj = jnp.asarray(wide)

    @property
    def total_steps(self) -> int:
        """Live (non-sentinel) walk positions across every buffer — the
        total step count a full regeneration recomputes."""
        nr = self.n * self.R
        return int(np.asarray((self.walks[:nr] < self.n).sum()))

    # -- O(read) queries ----------------------------------------------------
    def pagerank(self) -> jnp.ndarray:
        """Global PR estimate [n] from the incrementally folded counters."""
        return _pr_estimate(self.counts, self._alpha_op, R=self.R,
                            dtype=self.dtype)

    def ppr(self, seeds) -> jnp.ndarray:
        """Full personalized-PageRank estimate [n] for a uniform restart
        over ``seeds`` (int array of vertex ids)."""
        s = jnp.asarray(np.asarray(seeds, np.int64).reshape(-1)
                        .astype(np.int32))
        return _ppr_full(self.walks, s, self._alpha_op, R=self.R,
                         dtype=self.dtype)

    def ppr_top_k(self, seeds, k: int):
        """(values, vertex ids) of the k highest PPR estimates."""
        s = jnp.asarray(np.asarray(seeds, np.int64).reshape(-1)
                        .astype(np.int32))
        return _ppr_topk(self.walks, s, self._alpha_op, R=self.R,
                         k=int(k), dtype=self.dtype)

    # -- lifecycle -----------------------------------------------------------
    def warmup(self) -> None:
        """Compile the hot-path kernels at the ladder base buckets with
        inert (all-padding) operands — state is untouched."""
        n, nr = self.n, self.n * self.R
        self.walks, self.counts = _regen_step(
            self.walks, self.counts, self.adj, self.deg,
            jnp.full(WALK_BATCH_BUCKET, nr, jnp.int32), self._alpha_op,
            self._key, R=self.R)
        self._buckets.add(("regen", WALK_BATCH_BUCKET, self._cap))
        b = int(ops.DELTA_BATCH_BUCKET)
        self.adj, self.deg = _patch_rows(
            self.adj, self.deg, jnp.full(b, n, jnp.int32),
            jnp.full((b, self._cap), n, jnp.int32), jnp.zeros(b, jnp.int32))
        self._buckets.add(("adj", b, self._cap))

    def fork(self) -> "WalkState":
        """Share the immutable device buffers; copy the host-mutable twins
        (adjacency truth + reverse index + bucket set)."""
        new = object.__new__(WalkState)
        new.__dict__.update(self.__dict__)
        new._adj_host = self._adj_host.copy()
        new._deg_host = self._deg_host.copy()
        new._index = [s.copy() for s in self._index]
        new._buckets = set(self._buckets)
        return new


# ---------------------------------------------------------------------------
# repro.api engine adapter (Engine protocol; loaded lazily by the registry)
# ---------------------------------------------------------------------------

class WalkEngine:
    """Registry adapter for the Monte Carlo walk engine — the sweep-free
    estimator.  ``supports`` declares the personalization capability the
    config layer gates walk fields on; the snapshot-level ``run`` builds a
    throwaway walk store at the default (R, L, seed) and returns the
    global estimate (sessions use :class:`WalkState` directly through the
    walk mode and carry the configured parameters)."""

    name = "walk"
    fault_domains = ("process",)
    supports = frozenset({"ppr"})

    def run(self, g, R0, affected0, *, mode="lf", expand=True, alpha=0.85,
            tau=1e-10, tau_f=None, max_iterations=500, faults=None,
            tile=512, active_policy="affected", mat=None, aux=None,
            backend=None, interpret=None, shards=None):
        from repro.api.registry import (reject_shard_spec,
                                        reject_tile_operands)
        reject_tile_operands(self.name, mat, aux, backend)
        reject_shard_spec(self.name, shards)
        if faults is not None:
            raise ValueError(
                "the walk engine hosts no thread fault domain (declares "
                f"{self.fault_domains}); faults must be None")
        src, dst = g.in_edges_host()
        keep = src != dst           # snapshot self-loops are re-implied
        hg = HostGraph(g.n, np.stack([src[keep], dst[keep]], 1))
        st = WalkState(hg, alpha=alpha, dtype=np.dtype(R0.dtype))
        ranks = jnp.zeros((g.n_pad,), st.dtype).at[:g.n].set(st.pagerank())
        est_len = min(1.0 / (1.0 - alpha), float(st.L))
        stats = blk.SweepStats(
            sweeps=1, iterations=1, converged=True,
            edges_processed=int(g.n * st.R * est_len))
        return jax.block_until_ready(ranks), stats


def as_engine() -> WalkEngine:
    return WalkEngine()
