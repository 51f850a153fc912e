"""Distributed Dynamic-Frontier PageRank via shard_map (DESIGN.md §2, §5).

1-D vertex partition: device d owns the contiguous vertex range
[d·n_loc, (d+1)·n_loc).  In-edges are partitioned by destination owner (pull),
out-edges by source owner (frontier expansion).  Per sweep:

    1. contribution exchange — one of
         "full"  : all-gather of the n-float contribution vector
         "bf16"  : the same, cast to bf16 on the wire (½ the collective bytes,
                   f32 master kept locally) — gradient-compression analogue
         "delta" : *sparse delta all-gather* — only the ≤K contributions that
                   changed since the last exchange travel, as (idx, val)
                   pairs; overflow falls back to a full exchange.  This is the
                   frontier-aware collective that makes the DF approach pay
                   off at the wire level (beyond-paper optimization);
    2. local update of affected vertices (Jacobi, or ``local_gs_sweeps`` > 1
       block-Gauss–Seidel sweeps against *stale* remote contributions — the
       TPU analogue of the paper's lock-free staleness tolerance);
    3. frontier expansion: local out-edge OR-scatter, then a pmax exchange of
       the mark vector;
    4. convergence: psum of outstanding per-vertex RC flags.

A straggling device simply delivers one-sweep-stale contributions; all other
devices keep making progress — the paper's helping/stale-read argument,
re-expressed as stale-synchronous data flow.

Two ways in:

* :class:`DistRuntime` — the **incremental** sharded runtime behind
  ``repro.api.PageRankSession(topology="sharded")``: device-resident edge
  slabs and degree vectors patched by O(batch) scatters per update batch,
  one compiled sweep reused across every batch (zero post-warmup
  retraces).  This is the supported path.
* :func:`run_distributed` / :func:`build_dist_graph` — the one-shot
  rebuild-everything driver.  **Deprecated for direct use**: construct a
  session with ``EngineConfig(topology="sharded")`` instead (docs/API.md
  migration table); the ``distributed`` engine adapter and the tests keep
  calling it internally.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.graph import HostGraph


@dataclasses.dataclass(frozen=True)
class DistGraph:
    """Device-partitioned dynamic-graph snapshot (host-built)."""
    n: int
    n_pad: int
    n_dev: int
    # in-edges grouped by destination owner; [n_dev, m_in_pad]
    src_in: jnp.ndarray
    dst_in: jnp.ndarray
    # out-edges grouped by source owner; [n_dev, m_out_pad]
    src_out: jnp.ndarray
    dst_out: jnp.ndarray
    inv_deg: jnp.ndarray       # [n_pad] f32/f64 (0 on invalid)
    vertex_valid: jnp.ndarray  # [n_pad] bool
    # ring layout (exchange="ring"): this device's in-edges re-grouped by
    # SOURCE owner — [n_dev, n_dev_owners, ring_cap]; hop k consumes the
    # slice of the owner whose chunk just arrived
    src_in_ring: Optional[jnp.ndarray] = None
    dst_in_ring: Optional[jnp.ndarray] = None

    @property
    def n_loc(self) -> int:
        return self.n_pad // self.n_dev


def build_dist_graph(hg: HostGraph, n_dev: int, *, dtype=jnp.float32,
                     ring: bool = False) -> DistGraph:
    n = hg.n
    n_loc = -(-n // n_dev)
    n_pad = n_loc * n_dev
    e = hg.edges
    loops = np.arange(n, dtype=np.int64)
    src = np.concatenate([e[:, 0], loops])
    dst = np.concatenate([e[:, 1], loops])
    out_deg = np.bincount(src, minlength=n_pad)

    def partition(owner: np.ndarray, a: np.ndarray, b: np.ndarray):
        dev = owner // n_loc
        order = np.argsort(dev, kind="stable")
        a, b, dev = a[order], b[order], dev[order]
        counts = np.bincount(dev, minlength=n_dev)
        cap = int(counts.max(initial=1))
        A = np.full((n_dev, cap), n_pad, dtype=np.int32)
        B = np.full((n_dev, cap), n_pad, dtype=np.int32)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        for d in range(n_dev):
            s, c = starts[d], counts[d]
            A[d, :c] = a[s:s + c]
            B[d, :c] = b[s:s + c]
        return jnp.asarray(A), jnp.asarray(B)

    src_in, dst_in = partition(dst, src, dst)
    src_out, dst_out = partition(src, src, dst)

    sir = dir_ = None
    if ring:
        # per (dst-owner device, src-owner) edge slabs for the ring schedule
        ddev = dst // n_loc
        sdev = src // n_loc
        key = ddev * n_dev + sdev
        order = np.argsort(key, kind="stable")
        s_s, d_s, key_s = src[order], dst[order], key[order]
        counts = np.bincount(key_s, minlength=n_dev * n_dev)
        cap = max(8, int(counts.max(initial=1)))
        SIR = np.full((n_dev, n_dev, cap), n_pad, dtype=np.int32)
        DIR = np.full((n_dev, n_dev, cap), n_pad, dtype=np.int32)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        for kk in np.nonzero(counts)[0]:
            dd, so = divmod(int(kk), n_dev)
            lo, c = starts[kk], counts[kk]
            SIR[dd, so, :c] = s_s[lo:lo + c]
            DIR[dd, so, :c] = d_s[lo:lo + c]
        sir, dir_ = jnp.asarray(SIR), jnp.asarray(DIR)

    vv = np.zeros(n_pad, dtype=bool)
    vv[:n] = True
    inv = np.where(vv, 1.0 / np.maximum(out_deg, 1), 0.0)
    return DistGraph(n=n, n_pad=n_pad, n_dev=n_dev,
                     src_in=src_in, dst_in=dst_in,
                     src_out=src_out, dst_out=dst_out,
                     inv_deg=jnp.asarray(inv, dtype),
                     vertex_valid=jnp.asarray(vv),
                     src_in_ring=sir, dst_in_ring=dir_)


def make_sweep(dg: DistGraph, mesh: Mesh, axis, *, alpha: float,
               tau: float, tau_f: float, expand: bool,
               exchange: str = "full", delta_capacity: int = 1024,
               local_gs_sweeps: int = 1, local_blocks: int = 4,
               marks_dtype=jnp.int32):
    """Build the jitted shard_map sweep.  State carried across sweeps:
    (R_loc, affected_loc, rc_loc, contrib_cache_loc_view).

    ``axis`` may be one mesh axis name or a tuple of axis names — the
    production mesh partitions vertices over all of ("pod","data","model").
    """
    n, n_pad, n_dev, n_loc = dg.n, dg.n_pad, dg.n_dev, dg.n_loc
    dt = dg.inv_deg.dtype
    base = (1.0 - alpha) / n
    delta_capacity = min(delta_capacity, n_loc)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def _flat_index():
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + lax.axis_index(a)
        return idx

    def local_update(R_loc, contrib_full, aff_loc, vv_loc, src, dst, off,
                     inv_loc):
        """One (or several, Gauss–Seidel) local pull updates."""
        dst_loc = jnp.clip(dst - off, 0, n_loc)   # pad edges → bin n_loc

        def one(R_loc, contrib_full):
            pulled = jax.ops.segment_sum(
                contrib_full[jnp.minimum(src, n_pad - 1)]
                * (src < n_pad),
                dst_loc, num_segments=n_loc + 1)[:n_loc]
            r_new = base + alpha * pulled.astype(dt)
            return jnp.where(aff_loc & vv_loc, r_new, R_loc)

        if local_gs_sweeps <= 1:
            return one(R_loc, contrib_full)
        # block-Gauss–Seidel against stale remote contributions: refresh the
        # *local* slice of the contribution vector between inner sweeps
        for _ in range(local_gs_sweeps):
            R_loc = one(R_loc, contrib_full)
            contrib_full = lax.dynamic_update_slice(
                contrib_full, R_loc * inv_loc, (off,))
        return R_loc

    def sweep(R_loc, aff_loc, rc_loc, cache_slab,
              src_in, dst_in, src_out, dst_out, inv_loc, vv_loc,
              *ring_slabs):
        # squeeze the leading device dim shard_map leaves on the slabs
        src_in, dst_in = src_in[0], dst_in[0]
        src_out, dst_out = src_out[0], dst_out[0]
        # frontier-proportional work metric: in-edges whose destination is
        # in this sweep's affected set (the edges the pull actually uses)
        idx0 = _flat_index()
        dst_l0 = jnp.clip(dst_in - idx0 * n_loc, 0, n_loc - 1)
        edges_active = ((src_in < n_pad) & (dst_in < n_pad)
                        & aff_loc[dst_l0]).sum()
        # the delta-exchange cache is each device's PRIVATE view of the
        # global contribution vector: it travels as a [n_dev, n] slab so no
        # output collective is ever needed (a replicated [n] output spec
        # costs a hidden full all-gather per sweep — measured, see §Perf)
        cache_loc = cache_slab[0]
        idx = _flat_index()
        off = idx * n_loc

        contrib_loc = R_loc * inv_loc
        if exchange == "ring":
            # ring schedule: n_dev−1 collective_permute hops; hop k consumes
            # the chunk of owner (me−k) against the pre-sliced edge slab for
            # that owner.  On TPU the next hop's DMA overlaps the current
            # hop's partial SpMV — the lock-free paper's "never wait at a
            # barrier" insight applied to the exchange itself.
            src_ring, dst_ring = ring_slabs[0][0], ring_slabs[1][0]
            me = idx
            perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

            def hop(k, state):
                acc, chunk = state
                owner = (me - k) % n_dev
                sl = lax.dynamic_index_in_dim(src_ring, owner, 0,
                                              keepdims=False)
                dl = lax.dynamic_index_in_dim(dst_ring, owner, 0,
                                              keepdims=False)
                dloc = jnp.clip(dl - off, 0, n_loc)
                c = jnp.where(
                    sl < n_pad,
                    chunk[jnp.clip(sl - owner * n_loc, 0, n_loc - 1)], 0)
                acc = acc + jax.ops.segment_sum(
                    c, dloc, num_segments=n_loc + 1)[:n_loc]
                chunk = lax.ppermute(chunk, axes, perm)
                return acc, chunk

            pulled, _ = lax.fori_loop(
                0, n_dev, hop, (jnp.zeros((n_loc,), dt), contrib_loc))
            r_new = base + alpha * pulled.astype(dt)
            R_new = jnp.where(aff_loc & vv_loc, r_new, R_loc)
            overflow = jnp.zeros((), bool)
        elif exchange == "full":
            contrib_full = lax.all_gather(contrib_loc, axes, tiled=True)
            overflow = jnp.zeros((), bool)
        elif exchange == "bf16":
            # the barrier pins the bf16 convert BEFORE the gather: XLA is
            # otherwise free to sink it past the collective (same values,
            # 2× the wire bytes — observed; see EXPERIMENTS.md §Perf)
            wire = lax.optimization_barrier(
                contrib_loc.astype(jnp.bfloat16))
            contrib_full = lax.all_gather(wire, axes, tiled=True
                                          ).astype(dt)
            overflow = jnp.zeros((), bool)
        elif exchange == "delta":
            delta = contrib_loc - lax.dynamic_slice(cache_loc, (off,),
                                                    (n_loc,))
            n_changed = (delta != 0).sum()
            overflow = n_changed > delta_capacity
            mag, pos = lax.top_k(jnp.abs(delta), delta_capacity)
            vals = contrib_loc[pos]
            live = mag > 0
            gidx = jnp.where(live, pos + off, n_pad)
            all_idx = lax.all_gather(gidx, axes).reshape(-1)
            all_val = lax.all_gather(jnp.where(live, vals, 0), axes
                                     ).reshape(-1)
            patched = jnp.concatenate([cache_loc, jnp.zeros((1,), dt)])
            patched = patched.at[all_idx].set(all_val)
            contrib_delta = patched[:n_pad]
            # overflow anywhere → fall back to a full gather (correctness).
            # The fallback lives under lax.cond so its all-gather only
            # executes on overflow sweeps — every device agrees on the
            # branch (any_ovf is pmax'd), keeping the SPMD program uniform.
            any_ovf = lax.pmax(overflow.astype(jnp.int32), axes) > 0
            contrib_full = lax.cond(
                any_ovf,
                lambda: lax.all_gather(contrib_loc, axes, tiled=True),
                lambda: contrib_delta)
            overflow = any_ovf
        else:
            raise ValueError(exchange)

        if exchange != "ring":
            R_new = local_update(R_loc, contrib_full, aff_loc, vv_loc,
                                 src_in, dst_in, off, inv_loc)
        dr = jnp.abs(R_new - R_loc)
        changed = aff_loc & (dr > tau_f)
        rc_new = jnp.where(aff_loc & vv_loc, dr > tau, rc_loc)

        if expand:
            # local out-edges: src are owned here; mark global dst
            src_loc = jnp.clip(src_out - off, 0, n_loc - 1)
            flag = (src_out < n_pad) & changed[src_loc]
            # frontier marks travel as marks_dtype on the wire (int8 is
            # the compressed §Perf variant — 4× fewer pmax bytes)
            marks = jnp.zeros((n_pad + 1,), marks_dtype).at[
                jnp.where(flag, dst_out, n_pad)].set(1)[:n_pad]
            marks = lax.pmax(marks, axes) > 0
            marks_loc = lax.dynamic_slice(marks, (off,), (n_loc,)) & vv_loc
            aff_loc = aff_loc | marks_loc
            rc_new = rc_new | marks_loc

        outstanding = lax.psum(rc_new.sum(), axes)
        max_dr = lax.pmax(jnp.max(dr), axes)
        edges_total = lax.psum(edges_active, axes)
        cache_new = (contrib_full if exchange == "delta"
                     else cache_loc)
        return (R_new, aff_loc, rc_new, cache_new[None], outstanding,
                max_dr, overflow, edges_total)

    ax = axes if len(axes) > 1 else axes[0]
    specs_state = (P(ax), P(ax), P(ax), P(ax, None))
    specs_graph = (P(ax, None),) * 4 + (P(ax), P(ax))
    if exchange == "ring":
        specs_graph = specs_graph + (P(ax, None, None),) * 2
    fn = jax.shard_map(sweep, mesh=mesh,
                       in_specs=specs_state + specs_graph,
                       out_specs=(P(ax), P(ax), P(ax), P(ax, None), P(),
                                  P(), P(), P()),
                       check_vma=False)
    return jax.jit(fn)


@dataclasses.dataclass
class DistStats:
    sweeps: int = 0
    converged: bool = False
    full_exchanges: int = 0
    delta_exchanges: int = 0
    edges_processed: int = 0      # in-edges with affected dst, summed/sweep


def run_distributed(hg_or_dg, mesh: Mesh, *, axis: str = "data",
                    r_prev: Optional[jnp.ndarray] = None,
                    affected0: Optional[jnp.ndarray] = None,
                    alpha: float = 0.85, tau: float = 1e-10,
                    tau_f: Optional[float] = None, expand: bool = True,
                    exchange: str = "full", delta_capacity: int = 1024,
                    local_gs_sweeps: int = 1, max_sweeps: int = 500,
                    marks_dtype=jnp.int32,
                    dtype=jnp.float64) -> Tuple[jnp.ndarray, DistStats]:
    """Driver: converges the distributed DF sweep to all-RC-clear."""
    if isinstance(hg_or_dg, DistGraph):
        dg = hg_or_dg
    else:
        n_dev = int(np.prod([mesh.shape[a] for a in ([axis] if isinstance(
            axis, str) else axis)]))
        dg = build_dist_graph(hg_or_dg, n_dev, dtype=dtype,
                              ring=(exchange == "ring"))
    if tau_f is None:
        tau_f = tau / 1000.0 if expand else float("inf")

    R = (jnp.full((dg.n_pad,), 1.0 / dg.n, dtype)
         if r_prev is None else jnp.asarray(r_prev, dtype))
    R = jnp.where(dg.vertex_valid, R[:dg.n_pad], 0)
    aff = (dg.vertex_valid if affected0 is None
           else (affected0[:dg.n_pad] & dg.vertex_valid))
    rc = aff
    cache_w = dg.n_pad if exchange == "delta" else 1
    cache = jnp.zeros((dg.n_dev, cache_w), dtype)

    sweep = make_sweep(dg, mesh, axis, alpha=alpha, tau=tau, tau_f=tau_f,
                       expand=expand, exchange=exchange,
                       delta_capacity=delta_capacity,
                       local_gs_sweeps=local_gs_sweeps,
                       marks_dtype=marks_dtype)
    stats = DistStats()
    extra = ((dg.src_in_ring, dg.dst_in_ring)
             if exchange == "ring" else ())
    for i in range(max_sweeps):
        (R, aff, rc, cache, outstanding, max_dr, overflow, edges) = sweep(
            R, aff, rc, cache, dg.src_in, dg.dst_in, dg.src_out, dg.dst_out,
            dg.inv_deg, dg.vertex_valid, *extra)
        stats.sweeps += 1
        stats.edges_processed += int(edges)
        if exchange == "delta":
            if bool(overflow):
                stats.full_exchanges += 1
            else:
                stats.delta_exchanges += 1
        else:
            stats.full_exchanges += 1
        if int(outstanding) == 0:
            stats.converged = True
            break
    return R, stats


# ---------------------------------------------------------------------------
# Topology plumbing for the session API
# ---------------------------------------------------------------------------

EXCHANGES = ("full", "bf16", "delta", "ring")
# exchanges the incremental runtime supports (ring needs the per-owner edge
# slabs re-grouped on every batch — rebuild-only, excluded from sessions)
SESSION_EXCHANGES = ("full", "bf16", "delta")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Topology request handed from ``EngineConfig`` to the distributed
    engine / runtime: how many mesh devices, which partitioner relabels the
    vertex space, and which contribution-exchange variant runs per sweep."""
    n_shards: int
    partitioner: str = "contiguous"
    exchange: str = "full"
    delta_capacity: int = 1024


_SLAB_BUCKET = 64        # batch-pad / slab-capacity growth ladder base
_SEED_BUCKET = 1024      # affected-seed index pad (frontier-sized)


def _bucket(k: int, base: int = _SLAB_BUCKET) -> int:
    cap = base
    while cap < k:
        cap *= 2
    return cap


@jax.jit
def _patch_slab(A, B, dev, slot, a, b):
    """O(batch) device-side slab patch: write (a, b) at [dev, slot].
    Padded entries carry ``slot == capacity`` and are dropped."""
    return (A.at[dev, slot].set(a, mode="drop"),
            B.at[dev, slot].set(b, mode="drop"))


@jax.jit
def _patch_degrees(out_deg, inv_deg, valid, idx, dval):
    """O(batch) update of the out-degree vector and its inverse at the
    touched source vertices (padded entries carry ``idx == n_pad`` and are
    dropped; the gather after the scatter-add makes duplicate sources
    exact)."""
    out_deg = out_deg.at[idx].add(dval, mode="drop")
    n_pad = out_deg.shape[0]
    safe = jnp.minimum(idx, n_pad - 1)
    deg = jnp.maximum(out_deg[safe], 1).astype(inv_deg.dtype)
    new = jnp.where(valid[safe], 1.0 / deg, 0.0).astype(inv_deg.dtype)
    inv_deg = inv_deg.at[idx].set(new, mode="drop")
    return out_deg, inv_deg


@jax.jit
def _scatter_mask(valid, idx):
    """Bucketed index list → [n_pad] bool indicator (device-side scatter;
    only the padded index vector crosses host→device)."""
    m = jnp.zeros(valid.shape, bool).at[idx].set(True, mode="drop")
    return m & valid


class _SlabSet:
    """Host bookkeeping for one [n_dev, cap] edge-slab pair (in-edges
    grouped by dst owner, or out-edges grouped by src owner): where every
    edge lives, which slots are free, when capacity overflows.  The device
    slabs themselves live in the runtime's :class:`DistGraph`; this class
    only stages the O(batch) writes that patch them."""

    def __init__(self, *, by: str, n: int, n_loc: int, sentinel: int):
        assert by in ("src", "dst")
        self.by = by
        self.n = n
        self.n_loc = n_loc
        self.sentinel = sentinel
        self.cap = 0
        self.fill: list = []
        self.free: list = []
        self.slot_of: dict = {}

    def _owner(self, s: int, d: int) -> int:
        return (d if self.by == "dst" else s) // self.n_loc

    def build(self, src: np.ndarray, dst: np.ndarray, n_dev: int,
              *, headroom: int = _SLAB_BUCKET
              ) -> Tuple[np.ndarray, np.ndarray]:
        """(Re)build the numpy slab pair from an edge list, registering
        every edge's slot.  Capacity lands on the growth ladder with
        ``headroom`` slack so steady-state streams never reallocate."""
        owner = ((dst if self.by == "dst" else src) // self.n_loc).astype(
            np.int64)
        counts = np.bincount(owner, minlength=n_dev)
        self.cap = _bucket(int(counts.max(initial=1)) + headroom)
        A = np.full((n_dev, self.cap), self.sentinel, np.int32)
        B = np.full((n_dev, self.cap), self.sentinel, np.int32)
        self.fill = [0] * n_dev
        self.free = [[] for _ in range(n_dev)]
        self.slot_of = {}
        n = self.n
        for s, d, o in zip(src.tolist(), dst.tolist(), owner.tolist()):
            sl = self.fill[o]
            self.fill[o] += 1
            A[o, sl] = s
            B[o, sl] = d
            self.slot_of[s * n + d] = (o, sl)
        return A, B

    def edges(self) -> Tuple[np.ndarray, np.ndarray]:
        """The registered (src, dst) edge set decoded from the slot-table
        keys — the single owner of the ``s*n + d`` key scheme."""
        keys = np.fromiter(self.slot_of.keys(), np.int64,
                           count=len(self.slot_of))
        return keys // self.n, keys % self.n

    def rebuild(self, n_dev: int) -> Tuple[np.ndarray, np.ndarray]:
        """Reconstruct the slabs from the registered edge set at the next
        capacity bucket (the growth event — one sweep retrace)."""
        src, dst = self.edges()
        return self.build(src, dst, n_dev)

    def stage(self, dels: np.ndarray, ins: np.ndarray):
        """Register one effective batch and return the (dev, slot, src,
        dst) writes that realize it on the device slabs, or ``None`` on
        capacity overflow (host state is already consistent — call
        :meth:`rebuild`).  Slots freed by this batch's deletions are not
        recycled until the *next* batch, so one scatter never writes the
        same slot twice."""
        dev, slot, a, b = [], [], [], []
        freed = []
        n, sent = self.n, self.sentinel
        for s, d in np.asarray(dels, np.int64).reshape(-1, 2):
            o, sl = self.slot_of.pop(int(s) * n + int(d))
            dev.append(o)
            slot.append(sl)
            a.append(sent)
            b.append(sent)
            freed.append((o, sl))
        grew = False
        for s, d in np.asarray(ins, np.int64).reshape(-1, 2):
            s, d = int(s), int(d)
            o = self._owner(s, d)
            if self.free[o]:
                sl = self.free[o].pop()
            else:
                sl = self.fill[o]
                self.fill[o] += 1
                if sl >= self.cap:
                    grew = True
            self.slot_of[s * n + d] = (o, sl)
            if not grew:
                dev.append(o)
                slot.append(sl)
                a.append(s)
                b.append(d)
        for o, sl in freed:
            self.free[o].append(sl)
        if grew:
            return None
        return dev, slot, a, b

    def fork(self) -> "_SlabSet":
        new = _SlabSet(by=self.by, n=self.n, n_loc=self.n_loc,
                       sentinel=self.sentinel)
        new.cap = self.cap
        new.fill = list(self.fill)
        new.free = [list(f) for f in self.free]
        new.slot_of = dict(self.slot_of)
        return new


class DistRuntime:
    """Incrementally maintained sharded DF_LF runtime — the sharded
    analogue of the stream-mode operand mirrors: per-device edge slabs and
    the degree vectors are device-resident state patched by O(batch)
    scatters per update batch (never a host gather of ranks, never an
    O(m) rebuild), and the compiled shard_map sweep is built **once** per
    (expand,) variant and re-entered for every batch — zero post-warmup
    retraces, accounted via :meth:`cache_size`.

    Vertex ids are in the runtime's own (partitioner-relabeled) space; the
    session layer owns the relabeling.  The vertex set is fixed for the
    runtime's lifetime; edge capacity grows on a doubling ladder (a growth
    event reallocates the slabs and costs one sweep retrace)."""

    def __init__(self, hg: HostGraph, mesh: Mesh, *, axis="shards",
                 alpha: float = 0.85, tau: float = 1e-10,
                 tau_f: Optional[float] = None, exchange: str = "full",
                 delta_capacity: int = 1024, dtype=jnp.float64,
                 marks_dtype=jnp.int32):
        if exchange not in SESSION_EXCHANGES:
            raise ValueError(
                f"exchange={exchange!r} is not supported by the incremental "
                f"runtime; expected one of {SESSION_EXCHANGES}")
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        self.mesh, self.axis = mesh, axis
        n_dev = int(np.prod([mesh.shape[a] for a in axes]))
        n = hg.n
        n_loc = -(-n // n_dev)
        n_pad = n_loc * n_dev
        self.n, self.n_dev, self.n_loc, self.n_pad = n, n_dev, n_loc, n_pad
        self.dtype = jnp.dtype(dtype)
        self.exchange = exchange
        self.delta_capacity = delta_capacity
        self._alpha = float(alpha)
        self._tau = float(tau)
        self._tau_f = (float(tau_f) if tau_f is not None else tau / 1000.0)
        self._marks_dtype = marks_dtype
        self._sweeps: dict = {}

        e = hg.edges
        loops = np.arange(n, dtype=np.int64)
        src = np.concatenate([e[:, 0], loops])
        dst = np.concatenate([e[:, 1], loops])
        out_deg = np.bincount(src, minlength=n_pad)
        vv = np.zeros(n_pad, bool)
        vv[:n] = True
        self._in = _SlabSet(by="dst", n=n, n_loc=n_loc, sentinel=n_pad)
        self._out = _SlabSet(by="src", n=n, n_loc=n_loc, sentinel=n_pad)
        A_in, B_in = self._in.build(src, dst, n_dev)
        A_out, B_out = self._out.build(src, dst, n_dev)
        sh_vec, sh_slab = self._shardings()
        self._out_deg = jax.device_put(jnp.asarray(out_deg, jnp.int32),
                                       sh_vec)
        inv = np.where(vv, 1.0 / np.maximum(out_deg, 1), 0.0)
        self.dg = DistGraph(
            n=n, n_pad=n_pad, n_dev=n_dev,
            src_in=jax.device_put(jnp.asarray(A_in), sh_slab),
            dst_in=jax.device_put(jnp.asarray(B_in), sh_slab),
            src_out=jax.device_put(jnp.asarray(A_out), sh_slab),
            dst_out=jax.device_put(jnp.asarray(B_out), sh_slab),
            inv_deg=jax.device_put(jnp.asarray(inv, self.dtype), sh_vec),
            vertex_valid=jax.device_put(jnp.asarray(vv), sh_vec))
        # the delta-exchange contribution cache persists across drives:
        # every device holds a consistent view of the last-exchanged
        # contributions (zeros before the first sweep), so a new drive
        # starts from a warm cache — and the array keeps the sweep's own
        # canonical sharding, avoiding a one-off re-layout retrace
        cache_w = n_pad if exchange == "delta" else 1
        self._cache = jax.device_put(
            jnp.zeros((n_dev, cache_w), self.dtype), sh_slab)

    def _shardings(self):
        """(vector, slab) NamedShardings matching the sweep's out_specs —
        every array entering the compiled sweep is committed to these, so
        the sweep only ever sees **one** input-layout signature (uncommitted
        inputs would retrace it once per distinct layout)."""
        from jax.sharding import NamedSharding
        axes = ((self.axis,) if isinstance(self.axis, str)
                else tuple(self.axis))
        ax = axes if len(axes) > 1 else axes[0]
        return (NamedSharding(self.mesh, P(ax)),
                NamedSharding(self.mesh, P(ax, None)))

    @property
    def valid(self) -> jnp.ndarray:
        return self.dg.vertex_valid

    # -- O(batch) delta application -----------------------------------------
    def apply_batch(self, dels: np.ndarray, ins: np.ndarray) -> None:
        """Route one *effective* (deletions, insertions) batch to its
        owning shards: stage the per-slab writes on host (dict lookups,
        O(batch)), then patch each device slab pair with one bucketed
        scatter.  A capacity overflow rebuilds the overflowing slab at the
        next bucket instead (rare; one retrace)."""
        dels = np.asarray(dels, np.int64).reshape(-1, 2)
        ins = np.asarray(ins, np.int64).reshape(-1, 2)
        dg = self.dg
        new_slabs = {}
        for name_a, name_b, slabset in (
                ("src_in", "dst_in", self._in),
                ("src_out", "dst_out", self._out)):
            staged = slabset.stage(dels, ins)
            if staged is None:
                A, B = slabset.rebuild(self.n_dev)
                _, sh_slab = self._shardings()
                new_slabs[name_a] = jax.device_put(jnp.asarray(A), sh_slab)
                new_slabs[name_b] = jax.device_put(jnp.asarray(B), sh_slab)
                continue
            dev, slot, a, b = staged
            pad = _bucket(max(len(dev), 1)) - len(dev)
            dev = np.asarray(dev + [0] * pad, np.int32)
            # padded writes land at slot == cap → dropped by the scatter
            slot = np.asarray(slot + [slabset.cap] * pad, np.int32)
            a = np.asarray(a + [slabset.sentinel] * pad, np.int32)
            b = np.asarray(b + [slabset.sentinel] * pad, np.int32)
            A, B = _patch_slab(getattr(dg, name_a), getattr(dg, name_b),
                               jnp.asarray(dev), jnp.asarray(slot),
                               jnp.asarray(a), jnp.asarray(b))
            new_slabs[name_a] = A
            new_slabs[name_b] = B

        srcs = np.concatenate([dels[:, 0], ins[:, 0]])
        dval = np.concatenate([-np.ones(len(dels), np.int32),
                               np.ones(len(ins), np.int32)])
        pad = _bucket(max(len(srcs), 1)) - len(srcs)
        idx = np.concatenate([srcs, np.full(pad, self.n_pad)]).astype(
            np.int32)
        dval = np.concatenate([dval, np.zeros(pad, np.int32)])
        self._out_deg, inv_deg = _patch_degrees(
            self._out_deg, dg.inv_deg, dg.vertex_valid,
            jnp.asarray(idx), jnp.asarray(dval))
        self.dg = dataclasses.replace(dg, inv_deg=inv_deg, **new_slabs)

    def mask_from_indices(self, idx: np.ndarray) -> jnp.ndarray:
        """Bucketed device scatter of a vertex-index list into a [n_pad]
        indicator (the affected-seed upload path: O(frontier) host→device,
        never the graph-sized vector)."""
        idx = np.asarray(idx, np.int64).reshape(-1)
        pad = _bucket(max(len(idx), 1), _SEED_BUCKET) - len(idx)
        idx = np.concatenate([idx, np.full(pad, self.n_pad)]).astype(
            np.int32)
        return _scatter_mask(self.dg.vertex_valid, jnp.asarray(idx))

    # -- the reused compiled sweep ------------------------------------------
    def _sweep_for(self, expand: bool):
        key = bool(expand)
        if key not in self._sweeps:
            self._sweeps[key] = make_sweep(
                self.dg, self.mesh, self.axis, alpha=self._alpha,
                tau=self._tau,
                tau_f=(self._tau_f if expand else float("inf")),
                expand=expand, exchange=self.exchange,
                delta_capacity=self.delta_capacity,
                marks_dtype=self._marks_dtype)
        return self._sweeps[key]

    def drive(self, R, affected, *, expand: bool, max_sweeps: int = 500,
              rc0=None, collect_state: bool = False):
        """Converge one (R, affected) problem through the cached compiled
        sweep.  Ranks stay device-resident throughout; the per-sweep host
        sync is the scalar convergence counter.

        ``rc0`` seeds the per-vertex still-unconverged flags (defaults to
        the affected set); ``collect_state=True`` additionally returns the
        final ``(affected, rc)`` vectors so a caller can *suspend* a drive
        (e.g. at a shard-fault injection point) and resume it later —
        possibly on a different mesh — from exactly the un-converged
        row set.  Returns ``(R, stats)`` or ``(R, stats, (aff, rc))``."""
        sweep = self._sweep_for(expand)
        dg = self.dg
        sh_vec, _ = self._shardings()
        R = jnp.asarray(R, self.dtype)
        R = jax.device_put(jnp.where(dg.vertex_valid, R[:self.n_pad], 0),
                           sh_vec)
        aff = jax.device_put(affected & dg.vertex_valid, sh_vec)
        rc = (aff if rc0 is None
              else jax.device_put(rc0 & dg.vertex_valid, sh_vec))
        cache = self._cache
        stats = DistStats()
        for _ in range(max_sweeps):
            (R, aff, rc, cache, outstanding, max_dr, overflow,
             edges) = sweep(R, aff, rc, cache, dg.src_in, dg.dst_in,
                            dg.src_out, dg.dst_out, dg.inv_deg,
                            dg.vertex_valid)
            outstanding, max_dr, edges, overflow = jax.device_get(
                (outstanding, max_dr, edges, overflow))
            stats.sweeps += 1
            stats.edges_processed += int(edges)
            if self.exchange == "delta":
                if bool(overflow):
                    stats.full_exchanges += 1
                else:
                    stats.delta_exchanges += 1
            else:
                stats.full_exchanges += 1
            # RC-empty is the paper's criterion; max_dr <= tau is the fused
            # pull driver's escape from the float limit cycle in which
            # sub-ulp moves above tau_f re-mark the frontier forever
            if int(outstanding) == 0 or float(max_dr) <= self._tau:
                stats.converged = True
                break
        self._cache = cache
        if collect_state:
            return R, stats, (aff, rc)
        return R, stats

    # -- shard fault domain ---------------------------------------------------
    def owned_range(self, shard: int) -> Tuple[int, int]:
        """[lo, hi) of real vertex ids (runtime-relabeled space) owned by
        ``shard`` under the contiguous layout."""
        lo = shard * self.n_loc
        return lo, min((shard + 1) * self.n_loc, self.n)

    def registered_edges(self) -> np.ndarray:
        """The authoritative edge set (self-loops excluded) recovered from
        the in-slab slot table — the survivors' view of the graph, used to
        rebuild slabs after a permanent shard loss."""
        src, dst = self._in.edges()
        keep = src != dst
        return np.stack([src[keep], dst[keep]], axis=1)

    def shrink(self, dead: int) -> "DistRuntime":
        """Elastic re-partition after a *permanent* shard loss: rebuild the
        runtime on the surviving ``n_dev - 1`` devices, with the edge slabs
        reconstructed from the (host-side) slot tables — no device in the
        old mesh needs to be alive for this, which is the point.  Vertex
        relabeling is untouched; only the contiguous ownership split
        changes.  The recovery event costs one slab rebuild + sweep
        compile; steady-state streaming resumes recompile-free after."""
        axes = ((self.axis,) if isinstance(self.axis, str)
                else tuple(self.axis))
        if len(axes) != 1:
            raise ValueError("shrink() supports single-axis meshes "
                             f"(got axes {axes})")
        if self.n_dev <= 1:
            raise ValueError("cannot shrink a 1-shard runtime")
        if not (0 <= dead < self.n_dev):
            raise ValueError(f"dead shard {dead} out of range "
                             f"(n_dev={self.n_dev})")
        survivors = [d for i, d in enumerate(self.mesh.devices.flat)
                     if i != dead]
        mesh = Mesh(np.asarray(survivors), axes)
        hg = HostGraph(self.n, self.registered_edges())
        return DistRuntime(
            hg, mesh, axis=self.axis, alpha=self._alpha, tau=self._tau,
            tau_f=self._tau_f, exchange=self.exchange,
            delta_capacity=self.delta_capacity, dtype=self.dtype,
            marks_dtype=self._marks_dtype)

    def warmup(self, R) -> None:
        """Trace the per-batch pipeline (slab/degree patch at the base
        batch bucket, seed scatter at the base frontier bucket, the
        expand sweep) without perturbing graph or rank state.  Two
        one-sweep drives: the second runs against the first's
        canonically-laid-out cache, covering both sweep signatures."""
        empty = np.zeros((0, 2), np.int64)
        self.apply_batch(empty, empty)
        aff = self.mask_from_indices(np.zeros(0, np.int64))
        self.drive(R, aff, expand=True, max_sweeps=1)
        self.drive(R, aff, expand=True, max_sweeps=1)

    def cache_size(self) -> int:
        """Total jit-cache entries of the sweep(s) + patch functions (the
        sharded analogue of the fused driver's cache size)."""
        fns = list(self._sweeps.values()) + [_patch_slab, _patch_degrees,
                                             _scatter_mask]
        return sum(int(fn._cache_size()) for fn in fns)

    def fork(self) -> "DistRuntime":
        """Twin sharing every device array (immutable; patches are
        functional) with independent host bookkeeping.  Already-compiled
        sweeps are shared."""
        new = object.__new__(DistRuntime)
        new.__dict__.update(self.__dict__)
        new._in = self._in.fork()
        new._out = self._out.fork()
        new._sweeps = dict(self._sweeps)
        return new


def df_seed_indices(hg_prev: HostGraph, hg_cur: HostGraph,
                    sources: np.ndarray) -> np.ndarray:
    """Paper Alg. 1 lines 4-6, host-side in O(batch · deg): the
    out-neighbors of every update source in G^{t-1} **and** G^t, plus the
    sources themselves (the per-vertex self-loops every device graph
    carries make a source its own out-neighbor, matching
    :func:`repro.core.frontier.initial_affected` on snapshots)."""
    sources = np.unique(np.asarray(sources, np.int64).reshape(-1))
    sources = sources[(sources >= 0) & (sources < hg_cur.n)]
    out = [sources]
    for hg in (hg_prev, hg_cur):
        keys = hg._keys
        n = np.int64(hg.n)
        lo = np.searchsorted(keys, sources * n)
        hi = np.searchsorted(keys, (sources + 1) * n)
        for k0, k1 in zip(lo.tolist(), hi.tolist()):
            if k1 > k0:
                out.append(keys[k0:k1] % n)
    return np.unique(np.concatenate(out)) if out else sources


def collective_bytes_per_sweep(*, n_pad: int, n_dev: int, exchange: str,
                               rank_bytes: int, marks_bytes: int = 4,
                               delta_capacity: int = 1024,
                               expand: bool = True,
                               frac_full: float = 1.0) -> float:
    """Analytic wire-traffic model for one sweep, summed over devices
    (host-CPU "devices" have no physical wire — this is the number the
    partitioner/exchange choice controls on a real mesh).

    Contribution exchange: every device ships its n_loc chunk to the other
    n_dev−1 devices (`full`: rank_bytes/entry; `bf16`: 2 bytes; `delta`:
    (4-byte idx + value) × delta_capacity, with `frac_full` of sweeps
    falling back to the full gather on overflow).  Frontier expansion adds
    one all-reduce of the [n_pad] mark vector.  Scalar reductions (RC
    count, max |Δr|) are negligible and omitted."""
    n_loc = n_pad // max(n_dev, 1)
    pairs = n_dev * (n_dev - 1)
    gather_full = pairs * n_loc * rank_bytes
    if exchange == "full":
        g = gather_full
    elif exchange == "bf16":
        g = pairs * n_loc * 2
    elif exchange == "delta":
        g_delta = pairs * delta_capacity * (4 + rank_bytes)
        g = frac_full * gather_full + (1.0 - frac_full) * g_delta
    else:
        raise ValueError(f"exchange={exchange!r}; "
                         f"expected one of {SESSION_EXCHANGES}")
    marks = pairs * n_pad * marks_bytes if expand else 0
    return float(g + marks)


# ---------------------------------------------------------------------------
# repro.api engine adapter (Engine protocol; discovered lazily by
# repro.api.registry so this module never imports the api package)
# ---------------------------------------------------------------------------

class DistributedEngine:
    """Registry adapter for the sharded stale-synchronous engine: a
    one-shot solve that partitions the snapshot over the device mesh.
    Sessions with ``topology="sharded"`` bypass this adapter and drive
    :class:`DistRuntime` directly (the O(batch) incremental path); the
    adapter is the snapshot-level interop surface."""

    name = "distributed"
    fault_domains = ("shard", "process")

    def run(self, g, R0, affected0, *, mode, expand, alpha, tau, tau_f,
            max_iterations, faults, tile, active_policy,
            mat=None, aux=None, backend=None, interpret=None, shards=None):
        from repro.api.registry import reject_tile_operands
        from repro.graphs import partition as gpart
        reject_tile_operands(self.name, mat, aux, backend)
        del mode, tile, active_policy, interpret   # single-device knobs:
        # the sharded sweep is stale-synchronous block-Jacobi by design
        if faults is not None:
            raise ValueError(
                "fault simulation is not supported by engine='distributed' "
                "(stragglers are the model: stale contributions, no crash "
                "tables) — use engine='blocked'/'pallas' with a FaultPlan")
        spec = shards if shards is not None else ShardSpec(
            n_shards=len(jax.devices()))
        src, dst = g.in_edges_host()
        hg = HostGraph(g.n, np.stack([src, dst], 1))
        order, inv, _ = gpart.make_partition(hg, spec.n_shards,
                                             spec.partitioner)
        hg_rel, _ = gpart.relabel(hg, order)
        mesh = Mesh(np.asarray(jax.devices()[:spec.n_shards]), ("shards",))
        n_loc = -(-g.n // spec.n_shards)
        n_pad_rel = n_loc * spec.n_shards
        R0h = np.asarray(R0)
        r_rel = np.zeros(n_pad_rel, R0h.dtype)
        r_rel[:g.n] = R0h[order]
        affh = np.asarray(affected0)[:g.n_pad]
        a_rel = np.zeros(n_pad_rel, bool)
        a_rel[:g.n] = affh[order]
        R, st = run_distributed(
            hg_rel, mesh, axis="shards", r_prev=jnp.asarray(r_rel),
            affected0=jnp.asarray(a_rel), alpha=alpha, tau=tau, tau_f=tau_f,
            expand=expand, exchange=spec.exchange,
            delta_capacity=spec.delta_capacity,
            max_sweeps=max_iterations, dtype=R0h.dtype)
        from repro.core.blocked import SweepStats
        Rh = np.asarray(R)
        out = np.zeros(g.n_pad, Rh.dtype)
        out[order] = Rh[:g.n]
        stats = SweepStats(sweeps=st.sweeps, iterations=st.sweeps,
                           edges_processed=st.edges_processed,
                           converged=st.converged)
        return jax.block_until_ready(jnp.asarray(out)), stats


def as_engine() -> DistributedEngine:
    return DistributedEngine()
