"""Fused residual forward-push driver — work ∝ residual mass, not sweeps.

The pull driver (:mod:`repro.core.pallas_engine`) re-pulls every active
row-block until the whole iterate converges: a localized delta batch still
pays ~``log(tau)/log(alpha)`` sweeps over the full frontier, so per-batch
edge work is frontier cardinality × sweep count.  Forward push (Zhang et
al., *Two Parallel PageRank Algorithms via Improving Forward Push*;
Andersen–Chung–Lang style residuals) inverts the accounting: the session
keeps an explicit **residual vector** ``r`` next to the rank estimate
``p``, maintaining the exact invariant

    r = b + M·p − p,      b = (1−α)/n on valid vertices,
                          M = α · A · D⁻¹  (pull matrix, self-loops incl.)

and each sweep *pushes* only the residual of blocks still holding an
above-tolerance entry.  Pushing source set S moves
``p ← p + r·1_S`` and ``r ← r − r·1_S + α·A·D⁻¹·(r·1_S)``, which
preserves the invariant exactly and shrinks ``‖r‖₁`` by
``(1−α)·‖r·1_S‖₁`` — so total edge work is proportional to the seeded
residual mass (O(batch-sized) after a delta), while the fixed point
``p = b + M·p`` is the same PageRank vector the pull driver converges to,
with L∞ error bounded by ``‖r‖₁ · α/(1−α)`` at exit.

Everything rides the existing streaming machinery:

* the push is :func:`repro.kernels.block_spmv.ops.block_spmv_push_bucketed`
  — the scatter semiring realized on the SAME capacity-padded
  ``BlockSparse`` tile pool and slot tables as the pull (``A @ (x ⊙ 1_S)``),
  launched over the candidate destination row-blocks from the
  tile-presence adjacency at the static active-count ladder;
* source selection is bucketed top-mass: the smallest ladder bucket
  K ≥ |pushable| picks the K heaviest blocks by residual mass through a
  ``lax.switch`` (K ≥ |pushable|, so selection is complete — the bucket
  bounds the top-k cost and keeps every launch shape static and
  retrace-free);
* one ``lax.while_loop`` with zero host syncs; convergence is a
  per-vertex residual bound scaled by out-degree,
  ``|r[v]| ≤ tau·deg(v)/deg_max`` — so ``max|r| ≤ tau`` holds at exit,
  as under the pull driver's ``maxdr ≤ tau`` stop, and the error left,
  ``(I − M)⁻¹·r``, is at most ``tau/(1−α)`` at every vertex of an
  undirected graph.  A uniform ``|r[v]| ≤ tau`` would let the sub-tau
  residuals of many low-degree vertices add up at the hubs of a
  power-law graph (hundreds of tau at ``rmat-s15``'s degree-5,969 hub);
  the degree scaling gives the residual the shape the pull driver's
  sweeps leave.  Plus the ulp-floor escape, per vertex
  (``|r[v]| ≤ 16·eps·|p[v]|`` — pushes no longer representable in
  ``p[v]``; a hub's rank rounds far coarser than a leaf's, so one floor
  at ``max|p|`` would stop the leaves early); ``‖r‖₁`` is still
  reported, giving the computable a-posteriori L∞ bound
  ``‖r‖₁·α/(1−α)``;
* tiering composes without mid-sweep syncs: a push delivers to the
  device-*resident* candidate destination rows only; a pushed-to
  non-resident row goes **stale** and is recorded in the PR-9 deferred
  bitmap.  Nothing is lost: the rank estimate ``p`` is always globally
  exact (advancing ``p`` needs no tiles), so a stale row's residual is
  recomputed *exactly* from the invariant — ``r = b + M·p − p`` needs
  only the row's own tile row, which IS resident once the session's
  refill loop admits it (:func:`residual_refresh_blocks`).

Delta seeding is O(batch·deg): a batch changing M → M' shifts the
residual by exactly ``Δr = (M' − M)·p``, which touches only the changed
source columns — :func:`residual_seed_host` enumerates it from the sorted
host key sets and one bucketed device scatter applies it
(:func:`scatter_residual`).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import frontier as fr
from repro.core.blocked import SweepStats
from repro.core.graph import HostGraph
from repro.kernels.block_spmv import ops

# stats vector layout returned by _push_driver
STATS_LEN = 7   # sweeps, pushed_blocks, cand_blocks, edges, l1, maxr,
#                 converged


@partial(jax.jit, static_argnames=("n", "block_size", "max_iterations",
                                   "interpret", "backend", "tiered"))
def _push_driver(mat: ops.BlockSparse, P0, R0, valid, out_deg, rb_out,
                 bmat, rb_res, alpha, tau, *,
                 n: int, block_size: int, max_iterations: int,
                 interpret: bool, backend: str, tiered: bool = False):
    """The fused push loop.  Returns (p [n_pad], r [n_pad], stats vector
    [STATS_LEN], deferred row-block indicator [n_rb]).

    ``P0`` is the rank estimate and ``R0`` the residual satisfying
    ``r = b + M·p − p`` (the caller maintains it via seeding or full
    recompute).  Operand shapes are stable across a stream — same
    zero-retrace contract as the pull driver.  A sweep's phases carry the
    pull driver's scope names: ``df.expand`` (residual scan, source and
    candidate selection), ``df.sweep`` (the push) and ``df.account``.

    ``tiered=True``: ``rb_res`` marks resident row-blocks.  Pushes deliver
    to resident candidate destination rows only; a pushed-to non-resident
    row goes stale and is marked in ``deferred`` (never a mid-sweep sync)
    — the caller's refill loop admits it and rebuilds its residual exactly
    via :func:`residual_refresh_blocks` (``p`` stays globally exact, so
    staleness is confined to ``r`` on marked rows).
    """
    dtype = P0.dtype
    B = block_size
    n_pad = valid.shape[0]
    n_rb = n_pad // B
    cdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    ladder = ops.active_ladder(n_rb)
    eps = float(np.finfo(np.dtype(dtype)).eps)

    deg = jnp.maximum(out_deg, 1).astype(dtype)
    inv_deg = jnp.where(valid, 1.0 / deg, 0).astype(dtype)
    alpha_c = alpha.astype(dtype)
    tau_c = tau.astype(dtype)
    base_floor = (1.0 - alpha_c) / n
    # per-vertex threshold: tau at the widest vertex, scaled by out-degree
    # (module docstring) — never above tau, so max|r| ≤ tau at exit
    tau_v = tau_c * deg / jnp.where(valid, deg, 0).max()

    P = jnp.where(valid, P0[:n_pad], 0).astype(dtype)
    Rr = jnp.where(valid, R0[:n_pad], 0).astype(dtype)

    def cond(state):
        (_, _, it, converged, _, _) = state
        return ~converged & (it < max_iterations)

    def body(state):
        P, Rr, it, converged, deferred, ctr = state
        with jax.named_scope("df.expand"):
            aRr = jnp.abs(Rr)
            # ulp-floor escape (the pull's maxdr analogue), per vertex: a
            # residual below the rounding granularity of p[v] cannot move
            # p[v]
            lim = jnp.maximum(tau_v, 16.0 * eps * jnp.maximum(jnp.abs(P),
                                                              base_floor))
            over = valid & (aRr > lim)
            rb_mass = aRr.reshape(n_rb, B).sum(axis=1)
            pushable = over.reshape(n_rb, B).any(axis=1)
            n_push = pushable.sum(dtype=jnp.int32)
            # per-vertex exit: pushing v moves p[v] by exactly r[v], so no
            # vertex's next move exceeds its threshold
            conv_now = n_push == 0
            do = ~conv_now

            # -- bucketed top-mass source selection: smallest ladder
            #    bucket K ≥ |pushable|, top-K blocks by residual mass via
            #    lax.switch.  K ≥ |pushable| makes selection complete; the
            #    bucket bounds the top-k cost and keeps the trace static
            #    (retrace-free). ---------------------------------------------
            mass_m = jnp.where(pushable, rb_mass, -1.0)

            def sel_at(K):
                vals, ids = lax.top_k(mass_m, K)
                keep = vals > 0
                sel_p = jnp.zeros((n_rb + 1,), bool)
                sel_p = sel_p.at[jnp.where(keep, ids, n_rb)].set(True)
                return sel_p[:n_rb]

            if len(ladder) == 1:
                sel = sel_at(ladder[0])
            else:
                branches = [partial(sel_at, K) for K in ladder]
                bidx = sum((n_push > K).astype(jnp.int32)
                           for K in ladder[:-1])
                sel = lax.switch(bidx, branches)
            sel = sel & do

            # candidate destination row-blocks of the selected sources
            cand = (bmat & sel[None, :]).any(axis=1)
            if tiered:
                # deliver to resident destination rows only; a pushed-to
                # non-resident row goes stale → deferred bitmap (the refill
                # loop admits it and recomputes r = b + M·p − p exactly —
                # never a mid-sweep sync).  sel is already zero on converged
                # iterations, so cand carries the ~conv gate.
                deferred = deferred | (cand & ~rb_res)
                cand_rb = cand & rb_res
            else:
                cand_rb = cand
            n_cand = jnp.where(do, cand_rb.sum(dtype=jnp.int32), 0)
            cids = jnp.where(do, fr.compact_block_ids(cand_rb, n_rb), -1)

        # -- the push: scatter-semiring SpMV over candidate dst blocks.
        #    Per-vertex threshold (Andersen–Chung–Lang form): only entries
        #    above their threshold move — the rest stay in r, which is
        #    exactly what the exit permits — so edge work is Σ out-deg over
        #    *pushed vertices*, not over whole blocks. ----------------------
        with jax.named_scope("df.sweep"):
            sel_v = jnp.repeat(sel, B) & over
            moved = jnp.where(sel_v, Rr, 0)
            pushed = ops.block_spmv_push_bucketed(
                mat, moved * inv_deg, sel, cids, n_cand,
                interpret=interpret, backend=backend, ladder=ladder)
            pushed = jnp.where(jnp.repeat(cand_rb, B) & valid & do, pushed,
                               0)
            P1 = P + moved
            R1 = Rr - moved + alpha_c * pushed

        with jax.named_scope("df.account"):
            sweeps, pushed_b, cand_b, edges = ctr
            # edge work = out-edges of the vertices pushed this sweep
            e_sweep = jnp.where(sel_v, out_deg, 0).astype(cdt).sum()
            ctr1 = (sweeps + jnp.where(do, 1, 0).astype(cdt),
                    pushed_b + jnp.where(do, n_push, 0).astype(cdt),
                    cand_b + n_cand.astype(cdt),
                    edges + e_sweep)
        return (P1, R1, it + 1, converged | conv_now, deferred, ctr1)

    zero = jnp.zeros((), cdt)
    init = (P, Rr, jnp.int32(0), jnp.asarray(False),
            jnp.zeros((n_rb,), bool), (zero, zero, zero, zero))
    P, Rr, _, converged, deferred, ctr = lax.while_loop(cond, body, init)
    sweeps, pushed_b, cand_b, edges = ctr
    fdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    stats = jnp.stack([
        sweeps.astype(fdt), pushed_b.astype(fdt), cand_b.astype(fdt),
        edges.astype(fdt), jnp.abs(Rr).sum().astype(fdt),
        jnp.abs(Rr).max().astype(fdt), converged.astype(fdt)])
    return P, Rr, stats, deferred


def push_stats_from_vec(sv: np.ndarray) -> Tuple[SweepStats, dict]:
    """Split the driver's stats vector into the engine-common
    :class:`SweepStats` plus the push-specific extras."""
    stats = SweepStats(
        sweeps=int(sv[0]), iterations=int(sv[0]),
        blocks_processed=int(sv[2]), edges_processed=int(sv[3]),
        sim_time_ms=0.0, converged=bool(sv[6] > 0), dnf=False)
    extras = {"pushed_blocks": int(sv[1]),
              "residual_l1": float(sv[4]),
              "max_residual": float(sv[5])}
    return stats, extras


def push_cache_size() -> int:
    """Jit-cache entries of the push driver (the push session's retrace
    yardstick — separate from the pull driver's cache)."""
    return int(_push_driver._cache_size())


# ---------------------------------------------------------------------------
# residual maintenance: O(batch·deg) delta seeding + full recompute
# ---------------------------------------------------------------------------

def residual_seed_host(hg_prev: HostGraph, hg_cur: HostGraph,
                       sources: np.ndarray, p_src: np.ndarray,
                       deg_old: np.ndarray, deg_new: np.ndarray,
                       alpha: float) -> Tuple[np.ndarray, np.ndarray]:
    """Exact residual shift for one delta batch, enumerated host-side.

    A batch changes M → M' only in the columns of its (effective) source
    vertices, so ``Δr = (M' − M)·p`` is, per source u:

        r[v] −= α·p[u]/deg_old(u)   for v ∈ N_old(u) ∪ {u}
        r[v] += α·p[u]/deg_new(u)   for v ∈ N_new(u) ∪ {u}

    (the ∪{u} term is the per-vertex self-loop every device graph
    carries; ``deg_*`` already count it).  Neighbor lists come from the
    sorted host key sets — O(batch·deg) work, no snapshot.  Returns a
    flat (indices, values) scatter list for :func:`scatter_residual`."""
    sources = np.asarray(sources, np.int64).reshape(-1)
    p_src = np.asarray(p_src)
    idx_parts, val_parts = [], []
    for hg, deg, sgn in ((hg_prev, deg_old, -1.0), (hg_cur, deg_new, 1.0)):
        n = np.int64(hg.n)
        keys = hg._keys
        lo = np.searchsorted(keys, sources * n)
        hi = np.searchsorted(keys, (sources + 1) * n)
        counts = (hi - lo).astype(np.int64)
        total = int(counts.sum())
        flat = np.empty(total, np.int64)
        off = 0
        for k0, k1 in zip(lo.tolist(), hi.tolist()):
            if k1 > k0:
                flat[off:off + (k1 - k0)] = keys[k0:k1] % n
                off += k1 - k0
        scale = (sgn * alpha) * p_src / np.maximum(
            np.asarray(deg, p_src.dtype), 1)
        idx_parts += [flat, sources]
        val_parts += [np.repeat(scale, counts), scale]
    return (np.concatenate(idx_parts),
            np.concatenate(val_parts).astype(p_src.dtype))


@jax.jit
def _scatter_residual(Rr, idx, vals):
    n_pad = Rr.shape[0]
    tmp = jnp.zeros((n_pad + 1,), Rr.dtype).at[:n_pad].set(Rr)
    tmp = tmp.at[idx].add(vals.astype(Rr.dtype))
    return tmp[:n_pad]


def scatter_residual(Rr, idx: np.ndarray, vals: np.ndarray):
    """Apply a host-enumerated residual shift with one bucketed device
    scatter: the index/value lists are padded to the delta-batch bucket
    (pad slots target the guard row), so only O(batch·deg) crosses
    host→device and the jit cache stays O(log) in batch size."""
    idx = np.asarray(idx, np.int64).reshape(-1)
    vals = np.asarray(vals).reshape(-1)
    n_pad = int(Rr.shape[0])
    k = ops.capacity_bucket(max(len(idx), 1), ops.DELTA_BATCH_BUCKET)
    pi = np.full(k, n_pad, np.int64)
    pv = np.zeros(k, np.dtype(Rr.dtype))
    pi[:len(idx)] = idx
    pv[:len(vals)] = vals
    return _scatter_residual(Rr, jnp.asarray(pi), jnp.asarray(pv))


@jax.jit
def _gather(x, idx):
    return x[idx]


def gather_host(x, idx: np.ndarray) -> np.ndarray:
    """``x[idx]`` read back to the host through one bucketed device
    gather: the index list is padded to the delta-batch bucket, so a
    stream of batches with varying source counts reuses one compiled
    gather rather than compiling one per count."""
    idx = np.asarray(idx, np.int64).reshape(-1)
    k = ops.capacity_bucket(max(len(idx), 1), ops.DELTA_BATCH_BUCKET)
    pi = np.zeros(k, np.int64)
    pi[:len(idx)] = idx
    return np.asarray(_gather(x, jnp.asarray(pi)))[:len(idx)]


@partial(jax.jit, static_argnames=("n", "interpret", "backend"))
def residual_full(mat: ops.BlockSparse, P, valid, out_deg, alpha, *,
                  n: int, interpret: bool, backend: str):
    """Full residual recompute on the device matrix:
    ``r = b + α·A·D⁻¹·p − p`` (the nd / restore / repair path — O(m),
    exact, no seeding history needed)."""
    dtype = P.dtype
    deg = jnp.maximum(out_deg, 1).astype(dtype)
    inv_deg = jnp.where(valid, 1.0 / deg, 0).astype(dtype)
    alpha_c = alpha.astype(dtype)
    base = (1.0 - alpha_c) / n
    Pm = jnp.where(valid, P, 0).astype(dtype)
    pulled = ops.block_spmv(mat, Pm * inv_deg, semiring="sum",
                            interpret=interpret, backend=backend)
    return jnp.where(valid, base + alpha_c * pulled - Pm, 0)


@partial(jax.jit, static_argnames=("n", "block_size", "interpret",
                                   "backend"))
def residual_refresh_blocks(mat: ops.BlockSparse, P, Rr, valid, out_deg,
                            alpha, ids, n_ids, *, n: int, block_size: int,
                            interpret: bool, backend: str):
    """Exact residual rebuild restricted to the given row-blocks:
    ``r[rb] = b + α·(A·D⁻¹·p)[rb] − p[rb]`` for each id (the tiered
    refill path — a stale, just-admitted block needs only its OWN tile
    row, and ``p`` is always globally exact).  ``ids`` is a [n_rb]
    -1-padded compact list, ``n_ids`` the traced live count; launches ride
    the same bucketed active-SpMV ladder as the drives, so admitting any
    number of blocks stays retrace-free."""
    dtype = P.dtype
    n_rb = valid.shape[0] // block_size
    deg = jnp.maximum(out_deg, 1).astype(dtype)
    inv_deg = jnp.where(valid, 1.0 / deg, 0).astype(dtype)
    alpha_c = alpha.astype(dtype)
    base = (1.0 - alpha_c) / n
    Pm = jnp.where(valid, P, 0).astype(dtype)
    pulled = ops.block_spmv_active_bucketed(
        mat, Pm * inv_deg, ids, n_ids, semiring="sum",
        interpret=interpret, backend=backend)
    sel = jnp.zeros((n_rb + 1,), bool)
    sel = sel.at[jnp.where(ids >= 0, ids, n_rb)].set(True)[:n_rb]
    rows = jnp.repeat(sel, block_size) & valid
    return jnp.where(rows, base + alpha_c * pulled - Pm, Rr)


def residual_from_host(hg: HostGraph, out_deg: np.ndarray, p: np.ndarray,
                       alpha: float) -> np.ndarray:
    """Full residual recompute from host truth (tiered sessions: the
    device matrix is only a partial hot-set view, so the O(m) recompute
    walks the host key set instead — self-loops added explicitly)."""
    n = hg.n
    keys = hg._keys
    src = (keys // n).astype(np.int64)
    dst = (keys % n).astype(np.int64)
    p = np.asarray(p)
    deg = np.maximum(np.asarray(out_deg[:n], np.float64), 1)
    contrib = float(alpha) * np.asarray(p[:n], np.float64) / deg
    pulled = np.bincount(dst, weights=contrib[src], minlength=n)
    pulled += contrib           # the per-vertex self-loops
    r = (1.0 - float(alpha)) / n + pulled - np.asarray(p[:n], np.float64)
    out = np.zeros(p.shape[0], p.dtype)
    out[:n] = r.astype(p.dtype)
    return out
