"""`PageRankSession` — one stateful handle for snapshots, streams, serving.

The paper's DF_LF algorithm is stateful: ranks, the affected frontier and
the incremental pull matrix persist across update batches.  The session
owns all of that behind one object::

    from repro.api import PageRankSession, EngineConfig

    sess = PageRankSession.from_graph(hg, config=EngineConfig(tau=1e-10))
    sess.update(dels, ins)          # DF_LF step: recompile-free, O(batch)
    sess.query([3, 17, 42])         # device-resident partial read
    sess.top_k(10)                  # device-side top-k, k values transferred
    sess.recompute(variant="nd")    # re-solve the current graph
    twin = sess.fork()              # what-if branch sharing the tile pool
    sess.report()                   # latency / retrace / work statistics

Three operating modes, picked at construction:

* **stream mode** (``from_graph`` + the pallas engine): the PR-2 streaming
  machinery lives here — the graph is snapshotted **once**, the
  capacity-padded pull matrix and the per-vertex/per-block engine operands
  are maintained as device-resident mirrors patched in O(batch), and
  ``update`` re-enters the fused driver with zero post-warmup retraces
  (asserted in ``tests/test_api_surface.py``).

* **snapshot mode** (``from_snapshot``, or any non-pallas engine): the
  session holds a :class:`~repro.core.graph.GraphSnapshot` and converges
  through the engine adapter resolved from :mod:`repro.api.registry`.
  The legacy ``static/nd/dt/df_pagerank`` functions are deprecated shims
  over exactly this path (bit-for-bit parity,
  ``tests/test_api_session.py``).

* **sharded mode** (``EngineConfig(topology="sharded")``): the vertex set
  is partitioned over an ``n_shards`` device mesh
  (:mod:`repro.graphs.partition`) and updates route each delta batch to
  its owning shards through the incremental
  :class:`~repro.core.distributed.DistRuntime` — same O(batch),
  recompile-free contract as stream mode, with ranks sharded across
  devices.  The topology is invisible through the public surface:
  ``update``/``query``/``top_k``/``fork``/``report`` behave identically
  (``report`` additionally exposes ``edge_cut`` and the per-sweep
  collective-bytes model).

* **walk mode** (``EngineConfig(engine="walk")``): the sweep-free Monte
  Carlo engine (:mod:`repro.core.walk_engine`) — R walk segments per
  vertex in device-resident capacity-padded buffers, regenerated
  delta-locally per ``update`` (only walks through touched vertices) and
  serving global estimates **plus** :meth:`ppr_query` (seed-set
  personalized top-k), the capability no sweep engine declares.

Faults in any domain (docs/FAULTS.md) recover behind the same surface:
thread-domain plans ride on ``EngineConfig(faults=…)``/``fault_domain=``,
sharded sessions survive shard crashes via helping + elastic re-partition
(:meth:`inject_shard_fault` schedules one deterministically), and
``durability="wal"`` + ``store_dir=`` makes the session crash-stop-proof
— :meth:`save` / :meth:`restore` round-trip through an atomic checkpoint
plus a write-ahead log replayed on the zero-retrace hot path, with every
recovery's cost visible in :meth:`report`.

The vertex set (and hence the block grid) is fixed for the lifetime of a
session; growing past it requires a new session.  ``close()`` (or the
context-manager form) releases device buffers and unregisters from any
service.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
import warnings
import zlib
from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.api import registry
from repro.api.config import EngineConfig
from repro.ckpt.checkpoint import SessionStore
from repro.core import distributed as dist
from repro.core import fault_domain as fd
from repro.core import faults as flt
from repro.core import frontier as fr
from repro.core import integrity as ig
from repro.core import pallas_engine as pe
from repro.core import push_engine as pshe
from repro.core.blocked import SweepStats
from repro.core.delta import signed_edge_delta, validate_edge_batch
from repro.core.graph import (GraphSnapshot, HostGraph, initial_ranks,
                              pad_ranks)
from repro.core.incremental import (IncrementalPullMatrix, MatrixAux,
                                    effective_batch)
from repro.core.pagerank import PagerankResult
from repro.core import tiering
from repro.core import walk_engine as we
from repro.graphs import partition as gpart
from repro.kernels.block_spmv import ops

VARIANTS = ("static", "nd", "dt", "df")


class SweepCapWarning(RuntimeWarning):
    """An update batch hit ``max_iterations`` without converging — the
    served ranks are the best iterate, not a ``tau``-converged solution.
    Raised as a warning (not an error) because bounded-staleness serving
    legitimately runs with tight sweep budgets; ``report()`` counts every
    occurrence in ``sweep_cap_hits``."""


# ---------------------------------------------------------------------------
# streaming machinery (moved here from repro.core.stream in PR 3; the
# per-batch hot path is session state now — core.stream re-exports these
# for compatibility)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_size", "interpret", "backend"))
@jax.named_scope("df.seed")
def _seed_affected(mat_prev: ops.BlockSparse, mat_new: ops.BlockSparse,
                   bmat, batch, valid, *, block_size: int, interpret: bool,
                   backend: str) -> jnp.ndarray:
    """Initial DF frontier for one batch (paper Alg. 1 lines 4-6): mark the
    out-neighbors of every update source in G^{t-1} *and* G^t.

    Both graphs are queried through their pull matrices (A[v,u] ≥ 1 iff
    edge u→v, self-loops included — the same edge set a snapshot's
    ``out_neighbor_or`` walks), so the stream needs no snapshot edge
    arrays.  Launches are restricted to the candidate row-blocks that own a
    tile in a source's column-block; ``mat_new``'s structure is a superset
    of ``mat_prev``'s (growth is monotone), so one candidate set covers
    both passes."""
    n_pad = valid.shape[0]
    n_rb = n_pad // block_size
    ind = jnp.zeros((n_pad + 1,), bool)
    ind = ind.at[jnp.minimum(batch[:, 0], n_pad)].set(True)
    f = ind[:n_pad] & valid
    sb = fr.block_any(f, n_rb, block_size)
    cand = (bmat & sb[None, :]).any(axis=1)
    n_cand = cand.sum(dtype=jnp.int32)
    cids = fr.compact_block_ids(cand, n_rb)
    fx = f.astype(mat_new.tiles.dtype)
    h_prev = ops.block_spmv_active_bucketed(
        mat_prev, fx, cids, n_cand, semiring="or", interpret=interpret,
        backend=backend)
    h_new = ops.block_spmv_active_bucketed(
        mat_new, fx, cids, n_cand, semiring="or", interpret=interpret,
        backend=backend)
    return (((h_prev > 0) | (h_new > 0))
            & jnp.repeat(cand, block_size) & valid)


@partial(jax.jit, static_argnames=("block",))
@jax.named_scope("delta.scatter")
def _apply_operand_delta(out_deg, rb_in, rb_out, bmat,
                         rows, cols, vals, *, block: int):
    """O(batch) device-side update of the engine-operand mirrors from the
    signed pull-layout delta (rows = dst, cols = src, vals = ±1; padded
    entries carry val 0 and are inert).  Mirrors
    :meth:`repro.core.incremental.MatrixAux.apply_delta` plus the
    out-degree update, so a stream never re-uploads the graph-sized
    operand vectors — only the bucketed batch crosses to the device."""
    n_pad = out_deg.shape[0]
    n_rb = rb_in.shape[0]
    real = vals != 0
    v = jnp.where(real, vals, 0).astype(rb_in.dtype)
    rb = jnp.minimum(rows // block, n_rb - 1)
    cb = jnp.minimum(cols // block, n_rb - 1)
    out_deg = out_deg.at[jnp.minimum(cols, n_pad - 1)].add(
        v.astype(out_deg.dtype))
    rb_in = rb_in.at[rb].add(v)
    rb_out = rb_out.at[cb].add(v)
    # OR-scatter: padded entries contribute max(existing, False) == existing
    bmat = bmat.at[rb, cb].max(real)
    return out_deg, rb_in, rb_out, bmat


def _driver_cache_size() -> int:
    return int(pe._driver._cache_size())


# Cross-session retrace attribution.  The fused driver's jit cache is
# process-wide, so a step's cache-size delta can observe ANOTHER session's
# legitimate first-visit bucket compile (concurrent service dispatch) and
# misreport it as an unexpected retrace.  Every stream-mode drive entered
# at a first-visit operand bucket registers here for its duration; a step
# whose measurement window overlaps any registered drive (its own or a
# concurrent session's) attributes the window's cache growth to the
# capacity ladder, keeping ``driver_retraces`` an assertable
# zero-invariant under concurrency.  Sequential callers are unaffected:
# with no overlap, only the step's own first-visit can explain growth —
# exactly the previous behavior.
_RETRACE_LOCK = threading.Lock()
_NEW_BUCKET_STARTED = 0         # monotone count of first-visit drives begun
_NEW_BUCKET_ACTIVE = 0          # of those, currently mid-drive


@dataclasses.dataclass
class StreamBatchResult:
    """Outcome of one update step."""
    ranks: Optional[jnp.ndarray]  # [n_pad] post-batch converged ranks
    #                               (None in the session's kept history)
    stats: SweepStats
    wall_time_s: float            # full step: delta + seed + converge
    batch_edges: int              # raw batch size (before no-op filtering)
    driver_cache_size: int        # jit cache entries of the fused driver
    driver_retraces: int = 0      # cache growth DURING this step (-1 n/a) —
    #                               unlike the global cache size, immune to
    #                               other sessions/forks compiling variants
    bucket_retraces: int = 0      # cache growth explained by a FIRST visit
    #                               to a (tile capacity, max_tiles, expand)
    #                               operand bucket — the expected once-per-
    #                               bucket compile of the doubling ladder,
    #                               split out so driver_retraces stays an
    #                               assertable zero-invariant
    # -- walk-mode localization accounting (None on sweep engines) ----------
    regenerated_walks: Optional[int] = None   # walks rebuilt this batch
    touched_walks: Optional[int] = None       # touched-walk mass (bound)
    total_walks: Optional[int] = None         # n * R (the "global" yardstick)
    # -- push-driver accounting (None on the pull driver) --------------------
    residual_mass: Optional[float] = None     # ‖r‖₁ at drive exit
    pushed_blocks: Optional[int] = None       # source blocks pushed (summed
    #                                           over sweeps/refill rounds)

    @property
    def converged(self) -> bool:
        """Whether this batch reached ``tau`` within the sweep budget
        (``False`` = the sweep cap was hit; see :class:`SweepCapWarning`)."""
        return bool(self.stats.converged)


@dataclasses.dataclass
class SessionReport:
    """Aggregate latency / retrace / work statistics of a session."""
    engine: str
    backend: Optional[str]        # tile backend (pallas engine), else None
    mode: str
    n_updates: int
    p50_s: float
    p95_s: float
    retraces_post_warmup: int     # driver cache growth after warmup (-1 n/a)
    total_sweeps: int
    total_edges_processed: int
    queries_served: int
    wall_times_s: List[float]
    # -- convergence accounting (no silent sweep-capping) --------------------
    batches_converged: int = 0    # updates that reached tau in budget
    sweep_cap_hits: int = 0       # updates that hit max_iterations instead
    # -- topology (sharded sessions; None/"single" otherwise) ---------------
    topology: str = "single"
    n_shards: Optional[int] = None
    partitioner: Optional[str] = None
    edge_cut: Optional[float] = None          # realized cross-shard edges
    collective_bytes_per_sweep: Optional[float] = None  # analytic wire model
    # -- retrace decomposition (stream mode) ---------------------------------
    bucket_retraces_post_warmup: int = 0      # first-visit bucket compiles
    # -- fault domains / durability (docs/FAULTS.md) -------------------------
    durability: str = "none"
    recoveries: int = 0                       # completed, any domain
    recovery_time_s: float = 0.0              # summed detection→recovered
    replayed_batches: int = 0                 # WAL batches replayed (process)
    recovery_events: List[dict] = dataclasses.field(default_factory=list)
    # -- corruption domain (core/integrity.py; None = integrity disabled) ----
    integrity: Optional[dict] = None
    # -- tiered storage / memory audit (docs/SCALE.md) -----------------------
    tiering: Optional[dict] = None            # HotSetManager counters
    device_bytes: Optional[dict] = None       # per-component device bytes
    bytes_per_vertex: Optional[float] = None  # sum(device_bytes) / n
    # -- work accounting (per-batch history; pull-vs-push comparable) --------
    driver: str = "pull"                      # EngineConfig.driver
    sweeps_history: List[int] = dataclasses.field(default_factory=list)
    edges_processed_history: List[int] = dataclasses.field(
        default_factory=list)
    residual_mass_last: Optional[float] = None  # push: ‖r‖₁ at last exit
    pushed_blocks: Optional[int] = None         # push: total source blocks


class PageRankSession:
    """Stateful PageRank handle owning graph state, the resolved engine and
    the incremental operands.  Construct via :meth:`from_graph` (dynamic
    streams + serving) or :meth:`from_snapshot` (one-shot solves over an
    existing device snapshot)."""

    def __init__(self, *, hg: Optional[HostGraph] = None,
                 g: Optional[GraphSnapshot] = None,
                 config: Optional[EngineConfig] = None,
                 r0=None, interpret: Optional[bool] = None,
                 store_dir: Optional[str] = None,
                 _restore_attach: bool = False):
        if config is None:
            config = EngineConfig()
        if not isinstance(config, EngineConfig):
            raise TypeError(
                f"config must be an EngineConfig, got {type(config).__name__}"
                " — build one with repro.api.EngineConfig(...)")
        if hg is None and g is None:
            raise ValueError("need a HostGraph (from_graph) or a "
                             "GraphSnapshot (from_snapshot)")
        self.config = config
        self._sharded = config.topology == "sharded"
        self.engine = registry.resolve(config._engine_for_resolution())
        self.engine_name = self.engine.name
        self.hg = hg
        self._dtype = config.resolved_dtype()
        self.interpret = (ops.default_interpret() if interpret is None
                          else interpret)
        self.backend = (config.resolved_backend
                        if self.engine_name == "pallas" else config.backend)
        if (self.engine_name == "pallas" and self.backend == "pallas"
                and not self.interpret and self._dtype == jnp.float64):
            # Mosaic has no f64: fail here, not deep inside the first compile
            raise ValueError(
                "the compiled Pallas tile kernels run float32 ranks only "
                "(the TPU has no float64 path); the resolved rank dtype is "
                "float64 — pass EngineConfig(dtype=jnp.float32) or run "
                "without jax_enable_x64")
        self._stream = (self.engine_name == "pallas" and hg is not None
                        and g is None)
        self._walk = "ppr" in registry.supports_of(self.engine)
        # tiered storage (docs/SCALE.md): host-truth tile pool + bounded
        # device hot set; stream mode only — everything else keeps its
        # state fully device-resident
        self._tiered = config.device_budget_bytes is not None
        if self._tiered and not self._stream:
            raise ValueError(
                "device_budget_bytes tiers the streaming tile pool — open "
                "the session with from_graph and the pallas engine")
        self.pool: Optional[tiering.HostTilePool] = None
        self.hot: Optional[tiering.HotSetManager] = None
        self._deferred_rb: Optional[np.ndarray] = None
        # residual forward-push driver (docs/ENGINES.md): the session keeps
        # a device-resident residual vector next to the ranks, seeded in
        # O(batch) per update; config validation already pinned the engine
        # to pallas — here we additionally need the *stream* machinery
        self._push = config.driver == "push"
        if self._push and not self._stream:
            raise ValueError(
                "driver='push' runs the residual forward-push stream — "
                "open the session with from_graph and the pallas engine "
                "(from_snapshot has no operand mirrors to seed)")
        self._residual = None
        self._closed = False
        self._service = None          # backref set by PageRankService
        self._shard_spec: Optional[dist.ShardSpec] = None
        self._history: List[StreamBatchResult] = []
        self._warm_idx: Optional[int] = None
        self._queries = 0
        # replay state for recompute("dt"/"df"): the last applied batch,
        # the pre-batch host graph / snapshot, and the pre-batch ranks
        self._last_batch: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._hg_prev: Optional[HostGraph] = None
        self._g_prev: Optional[GraphSnapshot] = None
        self._r_prev = None

        # -- fault domains / durability (docs/FAULTS.md) ---------------------
        self._fault_plan = fd.resolve_thread_plan(config.faults,
                                                  config.fault_domain)
        self._shard_faults: Optional[fd.ShardFaultDomain] = None
        if self._sharded:
            # each session consumes its OWN schedule: the domain object
            # lives on a frozen, shareable config, so adopt a clone
            self._shard_faults = (
                config.fault_domain.clone()
                if isinstance(config.fault_domain, fd.ShardFaultDomain)
                else fd.ShardFaultDomain())
        self._recoveries: List[fd.RecoveryRecord] = []
        # -- corruption domain (core/integrity.py) ---------------------------
        self._corruption_faults: Optional[fd.CorruptionFaultDomain] = None
        if isinstance(config.fault_domain, fd.CorruptionFaultDomain):
            config.fault_domain.validate_for(topology=config.topology)
            # same contract as the shard domain: consume a private clone of
            # the schedule riding the (shareable) frozen config
            self._corruption_faults = config.fault_domain.clone()
        self._integrity_checks = 0      # invariant/digest checks evaluated
        self._corruption_detected = 0   # verify() passes that found damage
        self._integrity_alert: Optional[dict] = None  # fused-drive detection
        self._scatter_fault: Optional[str] = None     # pending torn scatter
        self._r_verified = None         # last integrity-clean iterate
        self._hg_digest: Optional[int] = None
        self._driver_keys: set = set()  # operand buckets already compiled
        self._batch_index = 0       # total update batches applied (WAL key)
        self._replaying = False     # True while restore() replays the WAL
        self.store_dir = store_dir
        self.store: Optional[SessionStore] = None
        self._process_domain: Optional[fd.ProcessFaultDomain] = None
        if config.durability == "wal":
            if hg is None:
                raise ValueError(
                    "durability='wal' needs a host graph (from_graph, or "
                    "from_snapshot with hg=) — the WAL replays edge "
                    "batches against it")
            if store_dir is None:
                raise ValueError(
                    "durability='wal' needs a store_dir= (the directory "
                    "holding the checkpoint + WAL)")
            self.store = SessionStore(store_dir)
            if not _restore_attach and (
                    self.store.read_meta() is not None
                    or self.store.latest_checkpoint_index is not None):
                raise ValueError(
                    f"store_dir {store_dir!r} already holds a session — "
                    "reopen it with PageRankSession.restore(dir) (replays "
                    "its WAL), or give a new session a fresh directory; "
                    "mixing two sessions' logs would corrupt both")
            self._process_domain = fd.ProcessFaultDomain(
                self.store, checkpoint_interval=config.checkpoint_interval)

        if self._sharded:
            self._init_sharded(g, r0)
        elif self._walk:
            self._init_walk(g, r0)
        elif self._stream:
            self._init_stream(r0)
        else:
            self._init_snapshot(g, r0)

        # a config-carried fault schedule is validated against the REAL
        # mesh now that it exists — never mid-update (see
        # inject_shard_fault)
        if self._shard_faults is not None:
            bad = [f.shard for f in self._shard_faults.pending_faults
                   if not 0 <= f.shard < self.runtime.n_dev]
            if bad:
                raise ValueError(
                    f"ShardFaultDomain schedules shard(s) {bad} outside "
                    f"the {self.runtime.n_dev}-shard mesh")

        # durable bootstrap: a FRESH store gets the session meta + one
        # atomic checkpoint of the born state (batch index 0), so a crash
        # before the first update already restores; restore() re-attaches
        # to a populated store and must not clobber it
        if (self.store is not None
                and self.store.latest_checkpoint_index is None):
            self._checkpoint_now()          # writes meta on a fresh store

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_graph(cls, hg: HostGraph, *,
                   config: Optional[EngineConfig] = None, r0=None,
                   interpret: Optional[bool] = None,
                   store_dir: Optional[str] = None) -> "PageRankSession":
        """Open a session over a host graph.  With the pallas engine this is
        **stream mode**: the graph is snapshotted once and every engine
        operand is maintained incrementally (O(batch) per update, zero
        post-warmup driver retraces).  ``r0=None`` runs one initial solve
        (``variant="static"`` semantics) so the session is born serving.
        ``store_dir`` attaches the durable store a
        ``config.durability="wal"`` session checkpoints and logs through."""
        return cls(hg=hg, config=config, r0=r0, interpret=interpret,
                   store_dir=store_dir)

    @classmethod
    def from_snapshot(cls, g: GraphSnapshot, *,
                      config: Optional[EngineConfig] = None, r0=None,
                      hg: Optional[HostGraph] = None,
                      interpret: Optional[bool] = None,
                      store_dir: Optional[str] = None) -> "PageRankSession":
        """Wrap an existing device snapshot (snapshot mode; the block grid
        comes from the snapshot, not ``config.block_size``).  Pass ``hg``
        as well to enable ``update``."""
        return cls(hg=hg, g=g, config=config, r0=r0, interpret=interpret,
                   store_dir=store_dir)

    # -- init paths ----------------------------------------------------------
    def _init_stream(self, r0) -> None:
        cfg = self.config
        # the ONLY snapshot stream mode ever builds; not retained — the
        # scalars + operand mirrors below carry everything the hot path needs
        g0 = self.hg.snapshot(block_size=cfg.block_size)
        self.g = None
        self.n, self.n_pad = g0.n, g0.n_pad
        self.block_size, self.n_rb = g0.block_size, g0.n_blocks
        dt = self._dtype
        # traced hyperparameter operands, created once so dtypes (and the
        # jit cache key) are identical across every step
        self._alpha = jnp.asarray(cfg.alpha, dt)
        self._tau = jnp.asarray(cfg.tau, dt)
        self._tau_f = jnp.asarray(cfg.resolved_tau_f(expand=True), dt)
        plan = self._fault_plan or flt.NO_FAULTS
        t = plan.device_tables(cfg.max_iterations)
        self._fault_tables = tuple(jnp.asarray(a) for a in t)

        if self._tiered:
            # host tier: the full tile pool + slot tables never land on the
            # device — only the HotSetManager's budget-bounded slab does.
            # The device "matrix" is the slab VIEW (same BlockSparse slot-
            # table indirection), rebound after every admission.
            src, dst = g0.in_edges_host()
            self.pool = tiering.HostTilePool.from_edges(
                dst, src, g0.n_pad, g0.n_pad, block=g0.block_size,
                dtype=np.dtype(dt))
            self.hot = tiering.HotSetManager(self.pool,
                                             cfg.device_budget_bytes)
            aux = MatrixAux(
                bmat=tiering.host_block_adjacency(self.pool.tile_cols,
                                                  self.pool.mat.n_cb),
                rb_in=np.asarray(g0.block_in_edges()).copy(),
                rb_out=np.asarray(g0.block_out_edges()).copy())
            self.inc = IncrementalPullMatrix(self.hot.view(), aux)
        else:
            self.inc = IncrementalPullMatrix.from_snapshot(
                g0, dtype=np.dtype(dt), padded=True)
        self._rb_res_full = jnp.ones((self.n_rb,), bool)
        self.valid = g0.vertex_valid
        # device-resident engine operands, patched in place per batch by
        # _apply_operand_delta (the host-side numpy twins live in inc.aux
        # for non-stream callers; bmat is uploaded as a copy because its
        # twin is patched in place)
        self._out_deg = jnp.asarray(g0.out_deg)
        self._rb_in = jnp.asarray(self.inc.aux.rb_in)
        self._rb_out = jnp.asarray(self.inc.aux.rb_out)
        self._bmat = jnp.array(self.inc.aux.bmat, copy=True)
        # host-truth twin of the out-degree mirror (rb_in/rb_out/bmat have
        # theirs in inc.aux), maintained in O(batch) — what the integrity
        # scrubber digests the device mirror against
        self._out_deg_host = np.asarray(g0.out_deg).copy()
        self._hg_digest = self._graph_digest()
        if r0 is None:
            if self._push:
                # cold push solve: p = 0, r = b — the invariant
                # r = b + M·p − p holds trivially, and the drive pushes the
                # whole teleport mass to the fixed point (tiered sessions
                # refill through the same admit → re-drive loop as pull)
                self._residual = jnp.where(
                    self.valid, (1.0 - cfg.alpha) / self.n, 0).astype(dt)
                r0, _, _ = self._drive_push_refill(
                    jnp.zeros((self.n_pad,), dt),
                    want_rb=(np.arange(self.n_rb) if self._tiered
                             else None))
                m = self.inc.mat
                self._driver_keys.add((int(m.tiles.shape[0]),
                                       int(m.tile_cols.shape[1]), "push"))
            elif self._tiered:
                # cold solve through the refill loop: admit what fits,
                # converge resident blocks, defer the rest — block-Jacobi
                # over residency partitions (expand=True propagates
                # corrections across rounds; docs/SCALE.md §Miss semantics)
                r0, _ = self._drive_refill(
                    jnp.asarray(initial_ranks(g0, dt)), g0.vertex_valid,
                    expand=True, want_rb=np.arange(self.n_rb))
                m = self.inc.mat
                self._driver_keys.add((int(m.tiles.shape[0]),
                                       int(m.tile_cols.shape[1]), True))
            else:
                r0, _ = pe.run_pallas(
                    g0, initial_ranks(g0, dt), g0.vertex_valid,
                    mode=cfg.mode,
                    expand=False, alpha=cfg.alpha, tau=cfg.tau,
                    max_iterations=cfg.max_iterations,
                    active_policy=cfg.active_policy,
                    mat=self.inc.mat, aux=self.inc.aux,
                    interpret=self.interpret, backend=self.backend)
        r0 = jnp.asarray(r0, dt)
        if r0.shape[0] < self.n_pad:       # e.g. length-n restore state
            r0 = jnp.zeros((self.n_pad,), dt).at[:r0.shape[0]].set(r0)
        self.R = r0[:self.n_pad]
        self._r_verified = self.R       # drift baseline for integrity checks
        if self._push and self._residual is None:
            # restored / caller-provided ranks: rebuild the exact residual
            # invariant before the first update seeds against it
            self._residual = self._residual_recompute(self.R)

    def _init_snapshot(self, g: Optional[GraphSnapshot], r0) -> None:
        cfg = self.config
        if g is None:
            g = self.hg.snapshot(block_size=cfg.block_size)
        self.g = g
        self.n, self.n_pad = g.n, g.n_pad
        self.block_size, self.n_rb = g.block_size, g.n_blocks
        self.valid = g.vertex_valid
        self.inc = None
        if r0 is None:
            res = self._converge(initial_ranks(g, self._dtype),
                                 g.vertex_valid, expand=False)
            self.R = res.ranks
        else:
            # keep the caller's dtype: engines key their compute dtype off
            # R0.dtype (an f32 rank vector must stay f32)
            self.R = pad_ranks(g, jnp.asarray(r0))

    def _init_sharded(self, g: Optional[GraphSnapshot], r0) -> None:
        """Sharded mode (``topology="sharded"``): partition the vertex set
        over an ``n_shards`` device mesh with the configured partitioner
        and hand the graph to the incremental
        :class:`repro.core.distributed.DistRuntime`.  Ranks live
        device-resident in the partitioner-relabeled vertex space; every
        public read (``query``/``top_k``/``ranks``) translates back, so the
        topology is invisible to callers."""
        cfg = self.config
        if self.hg is None:
            # from_snapshot without hg: recover the host edge set (the
            # sharded runtime is host-graph-based; self-loops re-added by it)
            src, dst = g.in_edges_host()
            self.hg = HostGraph(g.n, np.stack([src, dst], 1))
        self.g = None
        self.inc = None
        n_shards = cfg.resolved_n_shards
        self._shard_spec = dist.ShardSpec(
            n_shards=n_shards, partitioner=cfg.partitioner,
            exchange=cfg.exchange)
        order, inv, _ = gpart.make_partition(self.hg, n_shards,
                                             cfg.partitioner)
        self._order, self._inv = order, inv
        self._hg_rel, _ = gpart.relabel(self.hg, order)
        self._hg_rel_prev: Optional[HostGraph] = None
        self._last_batch_rel = None
        self._x_full = self._x_delta = self._x_sweeps = 0
        devices = np.asarray(jax.devices()[:n_shards])
        self._mesh = dist.Mesh(devices, ("shards",))
        self.runtime = dist.DistRuntime(
            self._hg_rel, self._mesh, axis="shards", alpha=cfg.alpha,
            tau=cfg.tau, tau_f=cfg.resolved_tau_f(expand=True),
            exchange=cfg.exchange, dtype=self._dtype)
        self.n, self.n_pad = self.hg.n, self.runtime.n_pad
        self.block_size, self.n_rb = cfg.block_size, 0
        self.valid = self.runtime.valid
        # realized shard of vertex v is its relabeled position's contiguous
        # share — the edge-cut this layout actually pays.  Counted once
        # here (O(m)), then maintained in O(batch) per update.
        self._cut_edges = int(self._crossing(self._hg_rel.edges))
        if r0 is None:
            R0 = jnp.where(self.valid, 1.0 / self.n, 0).astype(self._dtype)
            R, _ = self.runtime.drive(R0, self.valid, expand=False,
                                      max_sweeps=cfg.max_iterations)
            self.R = R
        else:
            r0h = np.asarray(r0)
            r_rel = np.zeros(self.n_pad, r0h.dtype)
            r_rel[:self.n] = r0h[order]
            # born sharded over the mesh, like every drive's output
            self.R = jax.device_put(r_rel.astype(self._dtype),
                                    self.runtime._shardings()[0])

    def _init_walk(self, g: Optional[GraphSnapshot], r0) -> None:
        """Walk mode (``engine="walk"``): no sweeps, no pull operands — the
        session owns a :class:`repro.core.walk_engine.WalkState` (R walk
        segments per vertex, device-resident) and every rank read derives
        from its visit counters.  ``r0`` is accepted for constructor parity
        (and WAL restore) but ignored: regeneration is deterministic in
        (graph, seed), so replaying the WAL reproduces the counters exactly
        — there is no separate rank state to seed."""
        cfg = self.config
        if self.hg is None:
            # from_snapshot without hg: recover the host edge set (walks run
            # over the host-graph adjacency; the snapshot's implicit
            # self-loops are re-added by the walk kernel's sampling)
            src, dst = g.in_edges_host()
            keep = src != dst
            self.hg = HostGraph(g.n, np.stack([src[keep], dst[keep]], 1))
        self.g = None
        self.inc = None
        self.n = self.n_pad = self.hg.n
        self.block_size, self.n_rb = cfg.block_size, 0
        self.valid = jnp.ones((self.n,), bool)
        self.walks = we.WalkState(
            self.hg, R=cfg.resolved_walks_per_vertex,
            L=cfg.resolved_walk_length, seed=cfg.resolved_walk_seed,
            alpha=cfg.alpha, dtype=self._dtype)
        self._hg_digest = self._graph_digest()
        self.R = self.walks.pagerank()
        self._r_verified = self.R

    # -- the snapshot-level solve (registry-dispatched) ----------------------
    def _converge(self, R0, affected0, *, expand: bool,
                  mode: Optional[str] = None, mat=None, aux=None,
                  g: Optional[GraphSnapshot] = None) -> PagerankResult:
        """Converge one (R0, affected0) problem through the resolved engine
        adapter and adopt the result as the session's ranks.  This is the
        exact path the deprecated ``*_pagerank`` functions shim onto."""
        cfg = self.config
        g = g if g is not None else self.g
        if g is None:
            raise ValueError("snapshot-level solve needs a GraphSnapshot "
                             "(stream-mode sessions use update/recompute)")
        t0 = time.perf_counter()
        R, stats = self.engine.run(
            g, R0, affected0, mode=mode or cfg.mode, expand=expand,
            alpha=cfg.alpha, tau=cfg.tau, tau_f=cfg.tau_f,
            max_iterations=cfg.max_iterations, faults=self._fault_plan,
            tile=cfg.tile, active_policy=cfg.active_policy,
            mat=mat, aux=aux, backend=cfg.backend,
            interpret=self.interpret)
        self.R = R
        return PagerankResult(ranks=R, stats=stats,
                              wall_time_s=time.perf_counter() - t0)

    # -- the stream-mode fused solve ----------------------------------------
    def _drive(self, R0, affected, *, expand: bool
               ) -> Tuple[jnp.ndarray, SweepStats]:
        """Run the fused driver over the device-resident operand mirrors
        (stream mode; one host sync for the stats vector).

        With ``EngineConfig(integrity=…)`` the corruption-domain invariant
        vector (mass error / negativity / finiteness / drift,
        :func:`repro.core.integrity.invariant_vec`) is concatenated onto
        the stats vector and fetched in the SAME ``block_until_ready`` —
        the per-drive checks cost device FLOPs, never an extra host sync.
        A violated invariant raises no error here (the batch is already
        applied); it posts ``_integrity_alert`` for :meth:`update` /
        :meth:`verify` to repair."""
        cfg = self.config
        part, alive, delay, crashed = self._fault_tables
        tiered = self._tiered
        rb_res = self.hot.rb_res if tiered else self._rb_res_full
        icfg = cfg.integrity
        fused = (icfg is not None and icfg.fused
                 and self._r_verified is not None)
        with TraceAnnotation("session.drive"):
            R, stats_vec, deferred = pe._driver(
                self.inc.mat, R0, affected, self.valid, self._out_deg,
                self._rb_in, self._rb_out, self._bmat, rb_res,
                self._alpha, self._tau, self._tau_f,
                part, alive, delay, crashed,
                n=self.n, block_size=self.block_size, mode=cfg.mode,
                expand=expand, active_policy=cfg.active_policy,
                max_iterations=cfg.max_iterations, interpret=self.interpret,
                backend=self.backend, tiered=tiered)
            # everything riding the drive — invariants AND the tiered
            # deferral indicator — is fetched in the SAME
            # block_until_ready: one sync
            tail = []
            if fused:
                inv = ig.invariant_vec(R, self._r_verified, self.valid)
                tail.append(inv.astype(stats_vec.dtype))
            if tiered:
                tail.append(deferred.astype(stats_vec.dtype))
            sv = np.asarray(jax.block_until_ready(       # the single sync
                jnp.concatenate([stats_vec] + tail) if tail else stats_vec))
        def_pending = False
        if tiered:
            self._deferred_rb = sv[-self.n_rb:] != 0
            def_pending = bool(self._deferred_rb.any())
            sv = sv[:-self.n_rb]
        else:
            self._deferred_rb = None
        if fused:
            stats = pe._stats_from_vec(sv[:-ig.N_INVARIANTS])
            mass_err, neg, nonfinite, _drift = (
                float(x) for x in sv[-ig.N_INVARIANTS:])
            # the drift term is informational here: a drive legitimately
            # moves ranks arbitrarily far from the pre-batch baseline, so
            # only verify() (between drives, where drift must be 0) gates
            # on it.  Mass is gated on converged iterates only — a sweep-
            # capped iterate's residual legitimately carries ≤ n·tau —
            # and only once no deferred (non-resident) blocks are pending:
            # mid-refill iterates carry those blocks' stale mass.
            self._integrity_checks += 3
            alert = None
            if nonfinite > 0:
                alert = {"check": "rank_finite", "count": int(nonfinite)}
            elif neg > 0:
                alert = {"check": "rank_negativity", "count": int(neg)}
            elif (stats.converged and not def_pending
                    and mass_err > icfg.mass_tol):
                alert = {"check": "rank_mass", "mass_error": mass_err}
            if alert is None:
                self._r_verified = R
            else:
                self._integrity_alert = alert
            return R, stats
        self._r_verified = R
        return R, pe._stats_from_vec(sv)

    def _admit(self, want_rb) -> None:
        """Admit row-blocks into the hot slab and rebind the device view
        (tiered streams only)."""
        with TraceAnnotation("session.admit"):
            self.hot.admit(want_rb)
            self.inc.mat = self.hot.view()

    def _mask_from_indices(self, idx: np.ndarray) -> jnp.ndarray:
        """Device indicator from a host index list: only the bucket-padded
        list crosses host→device (pad slots target the guard row), so the
        per-step transfer is O(batch·deg), never O(n)."""
        idx = np.asarray(idx, np.int64).reshape(-1)
        k_pad = ops.capacity_bucket(max(len(idx), 1), 1024)
        buf = np.full(k_pad, self.n_pad, np.int64)
        buf[:len(idx)] = np.minimum(idx, self.n_pad)
        ind = jnp.zeros((self.n_pad + 1,), bool).at[jnp.asarray(buf)].set(
            True)
        return ind[:self.n_pad] & self.valid

    def _drive_refill(self, R0, affected, *, expand: bool,
                      want_rb=None) -> Tuple[jnp.ndarray, SweepStats]:
        """Admission + fused drive + deferred-refill loop.

        Untiered sessions fall through to one plain :meth:`_drive`.
        Tiered: admit the frontier-biased want set in one batched gather,
        drive, and while the driver deferred non-resident blocks, admit
        those and re-drive with exactly the deferred blocks re-marked
        affected — the paper's helping mechanism applied to residency
        misses (a miss inside a sweep never syncs; the block is helped on
        the next drive).  Each round makes the previous rounds' blocks
        evictable, so the loop progresses whenever one row-block fits the
        slab; ``max_iterations`` rounds is the safety cap.

        Drain criterion: the loop stops once every currently-deferred
        block has been re-driven during an unbroken run of *quiet* rounds
        — rounds whose max rank movement stayed at or below ``tau`` (or
        the float ulp floor when ``tau`` sits under machine precision, a
        limit-cycle regime counted in ``refill_stalls``).  Abandoning the
        expansion marks of a quiet round is exactly what the untiered
        driver does when a sweep's max change falls to ``tau``, so tiered
        and untiered share one convergence semantics; the quiet *window*
        (rather than a single round) is what makes the criterion reachable
        when the deferred set is larger than the slab and each round can
        only re-drive a slice of it."""
        if not self._tiered:
            return self._drive(R0, affected, expand=expand)
        if want_rb is not None:
            self._admit(want_rb)
        R, agg = self._drive(R0, affected, expand=expand)
        rounds = 0
        eps = float(np.finfo(np.dtype(R.dtype)).eps)
        quiet_driven = np.zeros(self.n_rb, bool)
        B = self.block_size
        while self._deferred_rb is not None and self._deferred_rb.any():
            if rounds >= int(self.config.max_iterations):
                warnings.warn(
                    f"tiered refill loop did not drain in {rounds} rounds "
                    "— serving the best iterate (raise "
                    "device_budget_bytes)", SweepCapWarning, stacklevel=3)
                agg = SweepStats(
                    sweeps=agg.sweeps, iterations=agg.iterations,
                    blocks_processed=agg.blocks_processed,
                    edges_processed=agg.edges_processed,
                    sim_time_ms=agg.sim_time_ms, converged=False,
                    dnf=agg.dnf)
                break
            rounds += 1
            deferred = self._deferred_rb
            pending = np.nonzero(deferred)[0]
            self._admit(pending)
            aff = jnp.repeat(jnp.asarray(deferred), B) & self.valid
            R_prev = R
            R, st = self._drive(R, aff, expand=expand)
            agg = SweepStats(
                sweeps=agg.sweeps + st.sweeps,
                iterations=agg.iterations + st.iterations,
                blocks_processed=agg.blocks_processed + st.blocks_processed,
                edges_processed=agg.edges_processed + st.edges_processed,
                sim_time_ms=agg.sim_time_ms + st.sim_time_ms,
                converged=bool(st.converged), dnf=bool(agg.dnf or st.dnf))
            # drain check: a quiet round extends the window with the
            # blocks it actually re-drove; a loud round (or an unconverged
            # drive) resets it
            driven = pending[self.hot.resident[pending]]
            quiet = False
            at_floor = False
            if st.converged and len(driven):
                delta = float(jnp.max(jnp.abs(R - R_prev)))
                if delta <= float(self.config.tau):
                    quiet = True
                else:
                    rmax = float(jnp.max(jnp.abs(R)))
                    at_floor = delta <= 16.0 * eps * max(rmax, eps)
                    quiet = at_floor
            if quiet:
                quiet_driven[driven] = True
                cur = np.nonzero(self._deferred_rb)[0]
                if quiet_driven[cur].all():
                    if at_floor:
                        self.hot.counters["refill_stalls"] += 1
                    self._deferred_rb = np.zeros_like(deferred)
                    break
            else:
                quiet_driven[:] = False
        self.hot.counters["refill_drives"] += rounds
        return R, agg

    # -- the stream-mode residual forward-push solve -------------------------
    def _drv_cache_size(self) -> int:
        """Jit-cache size of THIS session's fused driver (push sessions
        measure the push driver's cache, pull sessions the pull driver's —
        the retrace yardsticks are per-driver)."""
        return (pshe.push_cache_size() if getattr(self, "_push", False)
                else _driver_cache_size())

    def _drive_push(self, P0) -> Tuple[jnp.ndarray, SweepStats, dict]:
        """One fused push drive over the device-resident operand mirrors:
        ranks + carried residual in, ranks + shrunk residual out, one host
        sync for the stats vector (the tiered deferral indicator rides the
        same ``block_until_ready``, exactly like :meth:`_drive`)."""
        cfg = self.config
        tiered = self._tiered
        rb_res = self.hot.rb_res if tiered else self._rb_res_full
        with TraceAnnotation("session.drive"):
            P, Rr, stats_vec, deferred = pshe._push_driver(
                self.inc.mat, P0, self._residual, self.valid, self._out_deg,
                self._rb_out, self._bmat, rb_res, self._alpha, self._tau,
                n=self.n, block_size=self.block_size,
                max_iterations=cfg.max_iterations, interpret=self.interpret,
                backend=self.backend, tiered=tiered)
            tail = [deferred.astype(stats_vec.dtype)] if tiered else []
            sv = np.asarray(jax.block_until_ready(       # the single sync
                jnp.concatenate([stats_vec] + tail) if tail else stats_vec))
        if tiered:
            self._deferred_rb = sv[-self.n_rb:] != 0
            sv = sv[:-self.n_rb]
        else:
            self._deferred_rb = None
        self._residual = Rr
        self._r_verified = P
        stats, extras = pshe.push_stats_from_vec(sv)
        return P, stats, extras

    def _drive_push_refill(self, P0, *, want_rb=None
                           ) -> Tuple[jnp.ndarray, SweepStats, dict]:
        """Admission + push drive + stale-refresh refill loop (the push
        twin of :meth:`_drive_refill`).  A drive delivers pushes to
        resident destination rows only; rows it pushed to while
        non-resident are *stale* and sit in the deferred bitmap.  Each
        round admits the pending blocks, rebuilds the admitted ones'
        residuals exactly from the invariant (``r = b + M·p − p`` needs
        only the row's own — now resident — tiles;
        :func:`repro.core.push_engine.residual_refresh_blocks`) and
        re-drives, until the bitmap drains or the rounds cap trips.  No
        quiet-window drain is needed: ``p`` is globally exact at all
        times, so draining the bitmap IS convergence."""
        if not self._tiered:
            return self._drive_push(P0)
        if want_rb is not None:
            self._admit(want_rb)
        P, agg, extras = self._drive_push(P0)
        pushed = extras["pushed_blocks"]
        rounds = 0
        while self._deferred_rb is not None and self._deferred_rb.any():
            if rounds >= int(self.config.max_iterations):
                warnings.warn(
                    f"tiered push refill loop did not drain in {rounds} "
                    "rounds — serving the best iterate (raise "
                    "device_budget_bytes)", SweepCapWarning, stacklevel=3)
                agg = SweepStats(
                    sweeps=agg.sweeps, iterations=agg.iterations,
                    blocks_processed=agg.blocks_processed,
                    edges_processed=agg.edges_processed,
                    sim_time_ms=agg.sim_time_ms, converged=False,
                    dnf=agg.dnf)
                break
            rounds += 1
            pending = np.nonzero(self._deferred_rb)[0]
            self._admit(pending)
            got = pending[self.hot.resident[pending]]
            if len(got):
                ids = np.full(self.n_rb, -1, np.int32)
                ids[:len(got)] = got
                self._residual = pshe.residual_refresh_blocks(
                    self.inc.mat, P, self._residual, self.valid,
                    self._out_deg, self._alpha, jnp.asarray(ids),
                    jnp.asarray(np.int32(len(got))),
                    n=self.n, block_size=self.block_size,
                    interpret=self.interpret, backend=self.backend)
            # blocks the slab could not take this round stay deferred
            leftover = np.zeros(self.n_rb, bool)
            leftover[pending] = ~self.hot.resident[pending]
            P, st, extras = self._drive_push(P)
            pushed += extras["pushed_blocks"]
            agg = SweepStats(
                sweeps=agg.sweeps + st.sweeps,
                iterations=agg.iterations + st.iterations,
                blocks_processed=agg.blocks_processed + st.blocks_processed,
                edges_processed=agg.edges_processed + st.edges_processed,
                sim_time_ms=agg.sim_time_ms + st.sim_time_ms,
                converged=bool(st.converged), dnf=bool(agg.dnf or st.dnf))
            if leftover.any():
                self._deferred_rb = (leftover if self._deferred_rb is None
                                     else self._deferred_rb | leftover)
        self.hot.counters["refill_drives"] += rounds
        return P, agg, {**extras, "pushed_blocks": pushed}

    def _residual_recompute(self, P) -> jnp.ndarray:
        """Exact O(m) residual rebuild ``r = b + M·p − p`` for the current
        graph (nd / restore / static-repair path).  Tiered sessions hold
        only a partial device view, so they walk host truth instead."""
        if self._tiered:
            return jnp.asarray(pshe.residual_from_host(
                self.hg, self._out_deg_host, np.asarray(P),
                float(self.config.alpha)))
        return pshe.residual_full(
            self.inc.mat, P, self.valid, self._out_deg, self._alpha,
            n=self.n, interpret=self.interpret, backend=self.backend)

    def _seed_push(self, variant: str, dels_eff, ins_eff, deg_old_host
                   ) -> Tuple[jnp.ndarray, Optional[np.ndarray]]:
        """Set the session residual for one applied batch and return
        ``(P0, seed_idx)``.  ``df`` is the O(batch·deg) hot path: the batch
        changes the pull matrix only in its effective source columns, so
        ``Δr = (M' − M)·p`` is enumerated host-side
        (:func:`repro.core.push_engine.residual_seed_host`) and applied
        with one bucketed device scatter — the operand-mirror scatter
        discipline.  ``nd`` keeps ``p`` and rebuilds the exact residual
        (O(m)); ``static`` restarts cold (p = 0, r = b)."""
        cfg = self.config
        if variant == "df":
            dels_a = np.asarray(dels_eff, np.int64).reshape(-1, 2)
            ins_a = np.asarray(ins_eff, np.int64).reshape(-1, 2)
            sources = np.unique(np.concatenate([dels_a[:, 0],
                                                ins_a[:, 0]]))
            # the host work push adds to a batch: the read of p at the
            # sources (a host sync), the enumeration and the scatter
            with TraceAnnotation("session.push_seed"):
                if len(sources):
                    p_src = pshe.gather_host(self.R, sources)
                    sidx, svals = pshe.residual_seed_host(
                        self._hg_prev, self.hg, sources, p_src,
                        deg_old_host[sources], self._out_deg_host[sources],
                        float(cfg.alpha))
                else:
                    sidx = np.zeros(0, np.int64)
                    svals = np.zeros(0, self._dtype)
                # the scatter runs even for an empty batch so warmup()
                # traces it at the base bucket, like the operand scatter
                self._residual = pshe.scatter_residual(self._residual, sidx,
                                                       svals)
            return self.R, sidx
        if variant == "nd":
            self._residual = self._residual_recompute(self.R)
            return self.R, None
        # static: cold restart — invariant holds trivially at p=0, r=b
        self._residual = jnp.where(
            self.valid, (1.0 - cfg.alpha) / self.n, 0).astype(self._dtype)
        return jnp.zeros((self.n_pad,), self._dtype), None

    # -- updates -------------------------------------------------------------
    def update(self, deletions, insertions, *, variant: str = "df"
               ) -> StreamBatchResult:
        """Apply one edge batch and reconverge.

        ``variant`` selects the dynamic marking: ``"df"`` (Dynamic Frontier,
        the paper's algorithm — the default and the recompile-free hot
        path), ``"dt"`` (reachability marking), ``"nd"`` (warm start, all
        affected) or ``"static"`` (cold start, all affected).  In stream
        mode everything except the ``dt`` marking stays snapshot-free."""
        with TraceAnnotation("session.update"):
            self._ensure_open()
            if variant not in VARIANTS:
                raise ValueError(f"variant={variant!r} invalid; "
                                 f"expected one of {VARIANTS}")
            if self.hg is None:
                raise ValueError(
                    "this session wraps a bare snapshot (from_snapshot "
                    "without hg=); build it with PageRankSession.from_graph "
                    "to stream updates")
            # validate BEFORE the WAL append and before any device scatter: a
            # NaN-weighted, duplicate, out-of-range or ambiguous batch raises
            # here, is never durably logged, and never replays after a restore
            with TraceAnnotation("session.validate"):
                deletions, insertions = validate_edge_batch(
                    deletions, insertions, self.n)
            # a scheduled silent corruption lands on live state BEFORE the
            # batch, so this drive's fused invariants (or the next scrub) must
            # be what detects it — the domain's whole point
            if self._corruption_faults is not None and not self._replaying:
                cfault = self._corruption_faults.pop_pending()
                if cfault is not None:
                    self._apply_corruption(cfault)
            bidx = self._batch_index + 1
            wal_undo = None
            if self.store is not None and not self._replaying:
                wal_undo = self.store.wal_size()
            try:
                if wal_undo is not None:
                    # write-ahead: the batch is durable BEFORE any device
                    # scatter, so a crash-stop at any instant restores to
                    # either fully-before or (via replay) fully-after this
                    # batch.  Inside the try: a failed append (torn frame on
                    # ENOSPC) must also roll back, or the broken tail would
                    # hide every later acknowledged record from read_wal
                    with TraceAnnotation("session.wal"):
                        self.store.append_wal(
                            batch_index=bidx, variant=variant,
                            deletions=np.asarray(deletions,
                                                 np.int64).reshape(-1, 2),
                            insertions=np.asarray(insertions,
                                                  np.int64).reshape(-1, 2))
                if self._sharded:
                    res = self._update_sharded(deletions, insertions, variant)
                elif self._walk:
                    res = self._update_walk(deletions, insertions, variant)
                elif self._stream:
                    res = self._update_stream(deletions, insertions, variant)
                else:
                    res = self._update_snapshot(deletions, insertions, variant)
            except BaseException:
                # the batch was REJECTED in-process (it never became session
                # state): revoke its record so a later restore does not replay
                # a batch the live session refused
                if wal_undo is not None:
                    self.store.truncate_wal(wal_undo)
                raise
            self._batch_index = bidx
            # the kept record drops the [n_pad] ranks: a long-running
            # session would otherwise hold one device array per batch
            self._history.append(dataclasses.replace(res, ranks=None))
            if not res.stats.converged:
                warnings.warn(
                    f"update batch {bidx} hit the sweep cap "
                    f"(max_iterations={self.config.max_iterations}) without "
                    f"reaching tau={self.config.tau} — serving the best "
                    "iterate; raise max_iterations or loosen tau "
                    "(report().sweep_cap_hits counts these)",
                    SweepCapWarning, stacklevel=2)
            if (self._process_domain is not None and not self._replaying
                    and bidx % self._process_domain.checkpoint_interval == 0):
                self._checkpoint_now()
            # fused detection → repair ladder, inside the same update call (the
            # batch itself was applied; only the iterate needs repairing)
            if self._integrity_alert is not None and not self._replaying:
                icfg = self.config.integrity
                if icfg is not None and icfg.auto_repair:
                    self.verify(repair=True, deep=False)
                # else: leave the alert posted; the next verify() handles it
            return res

    def _crossing(self, edges_rel: np.ndarray) -> int:
        """Count edges (in relabeled coordinates) whose endpoints land on
        different shards under the contiguous 1-D layout."""
        if len(edges_rel) == 0:
            return 0
        n_loc = self.runtime.n_loc
        return int((edges_rel[:, 0] // n_loc
                    != edges_rel[:, 1] // n_loc).sum())

    def _sharded_affected(self, variant: str, hg_rel_prev: HostGraph,
                          dels_rel: np.ndarray, ins_rel: np.ndarray
                          ) -> jnp.ndarray:
        """Initial affected marking for one sharded batch, in relabeled
        space.  ``df`` seeds from the host adjacency in O(batch · deg) and
        uploads only the bucketed index list; ``dt`` walks reachability on
        throwaway snapshots (the what-if path, O(m))."""
        if variant == "df":
            sources = np.concatenate([dels_rel[:, 0], ins_rel[:, 0]])
            idx = dist.df_seed_indices(hg_rel_prev, self._hg_rel, sources)
            return self.runtime.mask_from_indices(idx)
        if variant == "dt":
            bs = self.config.block_size
            g_prev = hg_rel_prev.snapshot(block_size=bs)
            g_new = self._hg_rel.snapshot(block_size=bs)
            batch_dev = fr.batch_to_device(g_new, dels_rel, ins_rel)
            aff = np.asarray(fr.dt_affected(g_prev, g_new, batch_dev))
            return self.runtime.mask_from_indices(np.nonzero(
                aff[:self.n])[0])
        return self.valid        # nd / static

    def _update_sharded(self, deletions, insertions, variant: str = "df"
                        ) -> StreamBatchResult:
        """Sharded step: translate the batch into the partitioner-relabeled
        space, route it to its owning shards (O(batch) slab/degree
        scatters), seed the frontier, and re-enter the cached compiled
        sweep.  Ranks never leave the devices."""
        t0 = time.perf_counter()
        cfg = self.config
        cache0 = self.runtime.cache_size()
        dels = np.asarray(deletions, np.int64).reshape(-1, 2)
        ins = np.asarray(insertions, np.int64).reshape(-1, 2)
        dels_rel = (self._inv[dels] if len(dels)
                    else np.zeros((0, 2), np.int64))
        ins_rel = (self._inv[ins] if len(ins)
                   else np.zeros((0, 2), np.int64))
        hg_rel_prev = self._hg_rel
        dels_eff, ins_eff = effective_batch(hg_rel_prev, dels_rel, ins_rel)
        self._hg_prev, self._g_prev = self.hg, None
        self._hg_rel_prev = hg_rel_prev
        self._last_batch = (dels, ins)
        self._last_batch_rel = (dels_rel, ins_rel)
        self._r_prev = self.R
        self.hg = self.hg.apply_batch(dels, ins)
        self._hg_rel = hg_rel_prev.apply_batch(dels_rel, ins_rel)
        self.runtime.apply_batch(dels_eff, ins_eff)
        self._cut_edges += int(self._crossing(ins_eff)
                               - self._crossing(dels_eff))

        affected = self._sharded_affected(variant, hg_rel_prev,
                                          dels_rel, ins_rel)
        if variant == "static":
            R0 = jnp.where(self.valid, 1.0 / self.n, 0).astype(self._dtype)
        else:
            R0 = self.R
        fault = (self._shard_faults.pop_pending()
                 if self._shard_faults is not None else None)
        if fault is None:
            R, dstats = self.runtime.drive(
                R0, affected, expand=(variant == "df"),
                max_sweeps=cfg.max_iterations)
        else:
            R, dstats = self._drive_with_shard_fault(
                R0, affected, expand=(variant == "df"), fault=fault)
        self.R = R
        self._x_full += dstats.full_exchanges
        self._x_delta += dstats.delta_exchanges
        self._x_sweeps += dstats.sweeps
        stats = SweepStats(sweeps=dstats.sweeps, iterations=dstats.sweeps,
                           edges_processed=dstats.edges_processed,
                           converged=dstats.converged)
        cache1 = self.runtime.cache_size()
        retraces = (cache1 - cache0 if cache0 >= 0 and cache1 >= 0 else -1)
        if fault is not None:
            # a consumed shard fault legitimately (re)compiles — on a new
            # mesh after a permanent loss — accounted through
            # report().recovery_events, not the streaming retrace counter
            retraces = 0
        return StreamBatchResult(
            ranks=R, stats=stats,
            wall_time_s=time.perf_counter() - t0,
            batch_edges=len(dels) + len(ins),
            driver_cache_size=cache1,
            driver_retraces=retraces)

    # -- shard fault domain (docs/FAULTS.md) ---------------------------------
    def inject_shard_fault(self, shard: int, *, at_sweep: int = 1,
                           permanent: bool = True) -> None:
        """Schedule one shard failure, consumed by the next :meth:`update`:
        the drive runs normally for ``at_sweep`` sweeps, then shard
        ``shard`` crash-stops (``permanent=True``, the mesh shrinks around
        it) or stalls and later rejoins (``permanent=False``).  Recovery —
        the paper's helping mechanism generalized to shards — happens
        inside the same update call; :meth:`report` records it."""
        self._ensure_open()
        if not self._sharded:
            raise ValueError(
                "shard faults require topology='sharded' (single-device "
                "sessions take a thread-domain FaultPlan instead)")
        # validate HERE, not mid-update: a fault consumed after the batch
        # has already mutated graph state must never be the thing that
        # raises (the update would be half-applied)
        if not (0 <= int(shard) < self.runtime.n_dev):
            raise ValueError(f"shard {shard} out of range (mesh has "
                             f"{self.runtime.n_dev} shards)")
        self._shard_faults.inject(shard, at_sweep=at_sweep,
                                  permanent=permanent)

    # -- corruption fault domain (core/integrity.py, docs/FAULTS.md) ---------
    def _graph_digest(self) -> int:
        """CRC32 of the host edge set — the host-truth identity the deep
        scrub's ``graph_digest`` check compares against."""
        return zlib.crc32(
            np.ascontiguousarray(self.hg.edges).tobytes()) & 0xFFFFFFFF

    def _integrity_cfg(self) -> ig.IntegrityConfig:
        icfg = self.config.integrity
        return icfg if icfg is not None else ig.IntegrityConfig()

    def _integrity_check(self, icfg: ig.IntegrityConfig, *, deep: bool
                         ) -> Tuple[List[dict], int, float, float]:
        """One detection pass, NO repair: ``(failures, checks_run,
        mass_error, drift)``.  Rank invariants always run; stream mode adds
        the mirror digests, the tile-pool sum check and the slot-table
        structural check; ``deep`` adds the host-graph digest."""
        failures: List[dict] = []
        checks = 0
        ref = self._r_verified if self._r_verified is not None else self.R
        inv = np.asarray(ig.invariant_vec(self.R, ref, self.valid))
        mass_err, neg, nonfinite, drift = (float(x) for x in inv)
        checks += 4
        if nonfinite > 0:
            failures.append({"check": "rank_finite",
                             "count": int(nonfinite)})
        if neg > 0:
            failures.append({"check": "rank_negativity", "count": int(neg)})
        # a sweep-capped iterate legitimately carries residual mass ≤ n·tau,
        # so the mass gate applies to converged iterates only
        converged = (not self._history
                     or bool(self._history[-1].stats.converged))
        if converged and mass_err > icfg.mass_tol:
            failures.append({"check": "rank_mass", "mass_error": mass_err})
        # between drives the ranks are bit-identical to the last verified
        # iterate (queries never write), so ANY drift is corruption
        if drift > icfg.drift_tol:
            failures.append({"check": "rank_drift", "drift": drift})
        if self._stream:
            aux = self.inc.aux
            mirrors = (("out_deg", self._out_deg, self._out_deg_host),
                       ("rb_in", self._rb_in, aux.rb_in),
                       ("rb_out", self._rb_out, aux.rb_out),
                       ("bmat", self._bmat, aux.bmat))
            for name, dev, host in mirrors:
                checks += 1
                bad = ig.compare_digests(
                    dev, host, chunk_bytes=icfg.scrub_chunk_bytes)
                if bad:
                    failures.append({"check": "mirror_digest",
                                     "mirror": name, "chunks": bad[:8]})
            # aggregate tile-pool checksum: every stored pull-matrix entry
            # is 1.0 (one per in-edge incl. self-loop), so the live tiles
            # of row-block i must sum to exactly rb_in[i]; 0.25 tolerates
            # nothing but float noise on integer counts
            checks += 1
            # tiered: host truth is the twin everything checks against —
            # the pool's live tiles carry the sums, its slot tables the
            # structure, and the slab scrub CRCs every resident device
            # tile against its host original
            sums = (self.pool.row_sums() if self._tiered
                    else ig.tile_row_sums(self.inc.mat))
            bad_rb = np.nonzero(np.abs(sums - aux.rb_in) > 0.25)[0]
            if len(bad_rb):
                failures.append({"check": "tile_sums",
                                 "row_blocks": bad_rb[:8].tolist()})
            checks += 1
            if self._tiered:
                failures.extend(ig.check_slot_tables(
                    self.pool.tile_cols, self.pool.mat.tile_idx,
                    aux.bmat, int(self.pool.mat.tiles.shape[0])))
                checks += 1
                failures.extend(self.hot.scrub(
                    np.asarray(self.inc.mat.tiles)))
            else:
                failures.extend(ig.check_slot_tables(
                    np.asarray(self.inc.mat.tile_cols),
                    np.asarray(self.inc.mat.tile_idx),
                    aux.bmat, int(self.inc.mat.tiles.shape[0])))
            if deep and self._hg_digest is not None:
                checks += 1
                if self._graph_digest() != self._hg_digest:
                    failures.append({"check": "graph_digest"})
        return failures, checks, mass_err, drift

    def verify(self, *, repair: Optional[bool] = None,
               deep: bool = True) -> ig.IntegrityReport:
        """Run the corruption-domain integrity checks on the live state
        and (by default, per ``IntegrityConfig.auto_repair``) climb the
        repair ladder on any failure.

        Checks: the rank invariants (mass conservation, non-negativity,
        finiteness, exact inter-drive drift vs the last verified iterate),
        and in stream mode the chunked digests of the operand mirrors
        against their host-truth twins, the tile-pool sum check and the
        slot-table structural check; ``deep=True`` adds the host-graph
        digest.  The ladder (``"frontier"`` → ``"rebuild"`` →
        ``"restore"``) re-marks corrupted rows into the DF frontier and
        helps them to convergence, rebuilds the device operands from host
        truth, or restores from the durable checkpoint+WAL store — each
        rung re-verifies and escalates on failure, emitting a
        ``RecoveryRecord(domain="corruption")`` visible in
        :meth:`report`.  This is also what the
        :class:`~repro.api.PageRankService` background scrubber calls on
        idle slots."""
        self._ensure_open()
        t0 = time.perf_counter()
        icfg = self._integrity_cfg()
        if repair is None:
            repair = icfg.auto_repair
        alert, self._integrity_alert = self._integrity_alert, None
        failures, checks, mass_err, drift = self._integrity_check(
            icfg, deep=deep)
        self._integrity_checks += checks
        if alert is not None and not any(f["check"] == alert["check"]
                                         for f in failures):
            # the fused drive flagged it even if the state has since moved
            failures = [dict(alert, fused=True)] + failures
        repairs: List[str] = []
        ok = not failures
        if failures:
            self._corruption_detected += 1
            if repair:
                ok, repairs, mass_err, drift = self._repair_corruption(
                    failures, icfg, deep=deep)
        if ok:
            self._r_verified = self.R
            # a repair rung's own drive may have re-posted a fused alert
            # against the pre-repair baseline; the clean re-check above
            # supersedes it
            self._integrity_alert = None
        return ig.IntegrityReport(
            ok=ok, checks_run=checks, failures=failures, repairs=repairs,
            mass_error=mass_err, drift=drift,
            wall_time_s=time.perf_counter() - t0)

    def _repair_corruption(self, failures: List[dict],
                           icfg: ig.IntegrityConfig, *, deep: bool
                           ) -> Tuple[bool, List[str], float, float]:
        """Climb the repair ladder from the cheapest rung the failure set
        allows, re-verifying after each rung and escalating while damage
        remains.  Returns ``(ok, rungs_applied, mass_error, drift)``."""
        checks = {f["check"] for f in failures}
        if "graph_digest" in checks:
            start = "restore"       # the host truth itself is damaged
        elif checks & {"mirror_digest", "tile_sums", "slot_tables",
                       "hot_slab"}:
            start = "rebuild"
        else:
            start = "frontier"
        detected = failures[0]["check"]
        repairs: List[str] = []
        mass_err = drift = float("nan")
        for rung in ig.REPAIR_RUNGS[ig.REPAIR_RUNGS.index(start):]:
            t0 = time.perf_counter()
            applied = self._apply_repair_rung(rung, icfg)
            if applied is None:     # rung unavailable (e.g. no store)
                continue
            desc, reconverged = applied
            left, checks_run, mass_err, drift = self._integrity_check(
                icfg, deep=deep or rung == "restore")
            self._integrity_checks += checks_run
            self._recoveries.append(fd.RecoveryRecord(
                domain="corruption", batch_index=self._batch_index,
                wall_time_s=time.perf_counter() - t0, rung=rung,
                check=detected, description=desc))
            repairs.append(rung)
            # a sweep-capped repair drive is NOT a repair even when the
            # checks pass (the mass gate is suspended on capped iterates):
            # escalate until a rung actually reconverges
            if not left and reconverged:
                return True, repairs, mass_err, drift
        return False, repairs, mass_err, drift

    def _apply_repair_rung(self, rung: str, icfg: ig.IntegrityConfig
                           ) -> Optional[Tuple[str, bool]]:
        """Execute one ladder rung; returns ``(description, reconverged)``
        or ``None`` when the rung does not apply to this session (skipped,
        not failed)."""
        if rung == "frontier":
            # the paper's helping mechanism aimed at corruption instead of
            # crashes: corrupted rows are reset to the last verified
            # iterate, re-marked affected, and the DF expansion propagates
            # any correction outward
            ref = (self._r_verified if self._r_verified is not None else
                   jnp.where(self.valid, 1.0 / self.n,
                             0.0).astype(self._dtype))
            bad = self.valid & (~jnp.isfinite(self.R) | (self.R < 0)
                                | (jnp.abs(self.R - ref) > icfg.drift_tol))
            n_bad = int(jnp.sum(bad))
            if n_bad:
                R0, affected = jnp.where(bad, ref, self.R), bad
            else:
                # aggregate-only symptom (mass off, nothing localizable):
                # fall back to the verified iterate wholesale
                R0 = jnp.where(self.valid, ref, jnp.zeros_like(ref))
                affected = self.valid
            if self._stream:
                R, st = self._drive_refill(R0, affected, expand=True)
                self.R, reconverged = R, bool(st.converged)
            else:
                self._converge(R0, affected, expand=True)
                reconverged = True
            return (f"{n_bad} corrupted rank(s) re-marked into the DF "
                    "frontier and helped back to convergence", reconverged)
        if rung == "rebuild":
            if not self._stream:
                return None         # nothing mirrored to rebuild
            g = self.hg.snapshot(block_size=self.block_size)
            if self._tiered:
                # both tiers rebuild from the host edge set: fresh pool,
                # fresh (empty) hot set — the re-converge below re-admits
                src, dst = g.in_edges_host()
                self.pool = tiering.HostTilePool.from_edges(
                    dst, src, g.n_pad, g.n_pad, block=self.block_size,
                    dtype=np.dtype(self._dtype))
                self.hot = tiering.HotSetManager(
                    self.pool, self.config.device_budget_bytes)
                aux = MatrixAux(
                    bmat=tiering.host_block_adjacency(
                        self.pool.tile_cols, self.pool.mat.n_cb),
                    rb_in=np.asarray(g.block_in_edges()).copy(),
                    rb_out=np.asarray(g.block_out_edges()).copy())
                self.inc = IncrementalPullMatrix(self.hot.view(), aux)
            else:
                self.inc = IncrementalPullMatrix.from_snapshot(
                    g, dtype=np.dtype(self._dtype), padded=True)
            self._out_deg = jnp.asarray(g.out_deg)
            self._out_deg_host = np.asarray(g.out_deg).copy()
            self._rb_in = jnp.asarray(self.inc.aux.rb_in)
            self._rb_out = jnp.asarray(self.inc.aux.rb_out)
            self._bmat = jnp.array(self.inc.aux.bmat, copy=True)
            self._scatter_fault = None
            # a rebuilt pool restarts the capacity ladder at its own
            # bucket; compiles it causes are recovery cost, not retraces
            cap = int(self.inc.mat.tiles.shape[0])
            mt = int(self.inc.mat.tile_cols.shape[1])
            self._driver_keys.update({(cap, mt, False), (cap, mt, True)})
            # cold uniform restart, NOT a warm start: both the current
            # iterate and the drift baseline may have converged (or sweep-
            # capped) against the torn operands, and a structured-garbage
            # warm start can need more sweeps than the cap — the cold
            # start's sweep count depends only on alpha/tau.  expand=True
            # so frontier expansion sweeps corrections through chunks that
            # look locally converged.
            R0 = jnp.where(self.valid, 1.0 / self.n, 0.0).astype(self._dtype)
            R, st = self._drive_refill(
                R0, self.valid, expand=True,
                want_rb=np.arange(self.n_rb) if self._tiered else None)
            self.R = R
            return ("operand mirrors + tile pool rebuilt from host truth; "
                    "full re-converge from the verified iterate",
                    bool(st.converged))
        if rung == "restore":
            if self.store is None:
                return None         # no durable store to fall back to
            svc, history = self._service, self._history
            warm, queries = self._warm_idx, self._queries
            recov = self._recoveries
            counters = (self._integrity_checks, self._corruption_detected)
            keys, store_dir = self._driver_keys, self.store.dir
            fresh = type(self).restore(store_dir, interpret=self.interpret)
            replayed = sum(r.replayed_batches for r in fresh._recoveries)
            # adopt the restored state in place, keeping this session's
            # identity (service registration, history, counters)
            self.__dict__.update(fresh.__dict__)
            self._service = svc
            self._history = history
            self._warm_idx = warm
            self._queries = queries
            self._recoveries = recov + fresh._recoveries
            self._integrity_checks, self._corruption_detected = counters
            self._driver_keys = keys | fresh._driver_keys
            return (f"checkpoint+WAL restore from {store_dir!r} "
                    f"({replayed} batch(es) replayed)", True)
        raise ValueError(f"unknown repair rung {rung!r}")

    def inject_corruption(self, kind: Union[str, "fd.CorruptionFault"], *,
                          index: Optional[int] = None, seed: int = 0,
                          defer: bool = False) -> "fd.CorruptionFault":
        """Silently corrupt live session state (chaos harness / tests —
        see ``fd.CORRUPTION_KINDS``).  Nothing is raised and nothing is
        recorded: detection is the integrity subsystem's job (the fused
        per-drive invariants, a scrub, or an explicit :meth:`verify`).
        ``defer=True`` queues the fault on the session's corruption domain
        instead, to be consumed by the NEXT :meth:`update` right before
        the batch applies."""
        self._ensure_open()
        if isinstance(kind, fd.CorruptionFault):
            fault = kind
        else:
            fault = fd.CorruptionFault(kind=str(kind), index=index,
                                       seed=int(seed))
        if defer:
            if self._corruption_faults is None:
                self._corruption_faults = fd.CorruptionFaultDomain()
            self._corruption_faults.inject(fault.kind, index=fault.index,
                                           seed=fault.seed)
        else:
            self._apply_corruption(fault)
        return fault

    def _apply_corruption(self, fault: "fd.CorruptionFault") -> None:
        kind = fault.kind
        rng = np.random.default_rng(fault.seed)
        if kind in ("scatter_drop", "scatter_dup"):
            # consumed by the next _update_stream: the device operand
            # scatter is dropped / double-applied while the host twins
            # record the truth — a torn scatter
            self._scatter_fault = kind
            return
        if kind == "rank":
            i = (int(fault.index) if fault.index is not None
                 else int(rng.integers(self.n)))
            bit = ig.exponent_bit(self._dtype, rng)
            val = np.asarray(self.R[i], self._dtype)
            self.R = self.R.at[i].set(ig.flipped_float(val, bit))
            return
        if not self._stream:
            raise ValueError(
                f"corruption kind {kind!r} instruments stream-mode state "
                "(tile pool / slot tables / operand mirrors); only 'rank' "
                "and the scatter kinds apply elsewhere")
        if kind == "graph":
            keys = self.hg._keys      # hg.edges is DERIVED from the key set
            if len(keys) == 0:
                raise ValueError("graph corruption needs at least one edge")
            i = (int(fault.index) if fault.index is not None
                 else int(rng.integers(len(keys))))
            keys[i] ^= 1              # in-place host-truth bit flip (dst±1)
            return
        if kind == "mirror":
            rb = (int(fault.index) if fault.index is not None
                  else int(rng.integers(self._rb_in.shape[0])))
            self._rb_in = self._rb_in.at[rb].add(
                jnp.asarray(3, self._rb_in.dtype))
            return
        mat = self.inc.mat
        tc = (self.pool.tile_cols.copy() if self._tiered
              else np.asarray(mat.tile_cols))
        occ = np.argwhere(tc >= 0)
        if kind == "slot":
            r, c = (occ[int(fault.index) % len(occ)]
                    if fault.index is not None
                    else occ[int(rng.integers(len(occ)))])
            n_cb = int(self.inc.aux.bmat.shape[1])
            if self._tiered:
                # the slot tables' truth is the HOST tier — corrupt it
                # there (the structural check scrubs host tables)
                self.pool.mat.tile_cols[int(r), int(c)] = np.int32(n_cb + 5)
            else:
                self.inc.mat = dataclasses.replace(
                    mat, tile_cols=mat.tile_cols.at[int(r), int(c)].set(
                        np.int32(n_cb + 5)))
            return
        # kind == "tile": flip an exponent bit of a LIVE (1.0) entry so the
        # perturbation clears the sum check's 0.25 count tolerance
        if self._tiered:
            # corrupt the DEVICE slab copy of a resident tile; host truth
            # stays clean — exactly the divergence hot.scrub() CRCs for
            tid_tbl = self.pool.tile_idx2d
            for rb in rng.permutation(sorted(self.hot._rb_slots)):
                rb = int(rb)
                slots = self.hot._rb_slots[rb]
                tids = tid_tbl[rb][self.pool.tile_cols[rb] >= 0]
                for tid, slot in zip(tids.tolist(), slots):
                    t = self.pool.mat.tiles[tid]
                    nz = np.argwhere(t != 0)
                    if len(nz):
                        bi, bj = (int(x) for x in
                                  nz[int(rng.integers(len(nz)))])
                        bit = ig.exponent_bit(t.dtype, rng)
                        new = ig.flipped_float(
                            np.asarray(t[bi, bj], t.dtype), bit)
                        self.inc.mat = dataclasses.replace(
                            mat, tiles=mat.tiles.at[slot, bi, bj].set(new))
                        self.hot.adopt_view(self.inc.mat)
                        return
            raise ValueError("no resident live tile entry to corrupt")
        tid_tbl = np.asarray(mat.tile_idx).reshape(tc.shape)
        for oi in rng.permutation(len(occ)):
            r, c = occ[oi]
            tid = int(tid_tbl[r, c])
            t = np.asarray(mat.tiles[tid])
            nz = np.argwhere(t != 0)
            if len(nz):
                bi, bj = (int(x) for x in nz[int(rng.integers(len(nz)))])
                bit = ig.exponent_bit(t.dtype, rng)
                new = ig.flipped_float(np.asarray(t[bi, bj], t.dtype), bit)
                self.inc.mat = dataclasses.replace(
                    mat, tiles=mat.tiles.at[tid, bi, bj].set(new))
                return
        raise ValueError("no live tile entry to corrupt")

    def _drive_with_shard_fault(self, R0, affected, *, expand: bool,
                                fault: "fd.ShardFault"
                                ) -> Tuple[jnp.ndarray, dist.DistStats]:
        """One sharded drive interrupted by a shard failure at
        ``fault.at_sweep`` sweeps, then recovered by **shard helping**:

        1. suspend the drive at the crash point, keeping the per-vertex
           (affected, still-unconverged) state;
        2. the dead shard's un-converged row-blocks — identified through
           the runtime's slot tables / ownership ranges — are re-marked as
           affected-and-unconverged (their last writes may be torn);
        3. permanent loss: elastically re-partition onto the surviving
           shards (:meth:`~repro.core.distributed.DistRuntime.shrink`),
           which re-homes every row-block the dead shard owned;
        4. resume the drive from the mid-crash ranks — the surviving
           shards pick up the re-marked rows and the DF expansion
           propagates their corrections, exactly the paper's recovery
           argument one level up."""
        cfg = self.config
        rt = self.runtime
        # a consumed fault must NEVER raise: the batch is already applied
        # to graph state when the drive runs.  A fault made stale by an
        # earlier shrink (its shard no longer exists) is dropped; a
        # permanent loss of the only remaining shard cannot re-partition
        # and degrades to a transient stall
        if not (0 <= fault.shard < rt.n_dev):
            return rt.drive(R0, affected, expand=expand,
                            max_sweeps=cfg.max_iterations)
        if fault.permanent and rt.n_dev == 1:
            fault = dataclasses.replace(fault, permanent=False)
        phase1 = max(1, min(int(fault.at_sweep), cfg.max_iterations))
        R_mid, st1, (aff_mid, rc_mid) = rt.drive(
            R0, affected, expand=expand, max_sweeps=phase1,
            collect_state=True)
        if st1.converged:           # crash scheduled past convergence
            return R_mid, st1
        t0 = time.perf_counter()
        n = self.n
        aff_h = np.asarray(aff_mid)[:n]
        rc_h = np.asarray(rc_mid)[:n]
        R_h = np.asarray(R_mid)
        lo, hi = rt.owned_range(fault.shard)
        dead_rows = np.zeros(n, bool)
        dead_rows[lo:min(hi, n)] = True
        # rows the survivors must help: everything still unconverged plus
        # every affected row the dead shard owned (its last sweep's writes
        # cannot be trusted)
        help_mask = rc_h | (dead_rows & aff_h)
        helped = int((help_mask & dead_rows).sum())
        if fault.permanent:
            rt2 = rt.shrink(fault.shard)
            self.runtime = rt2
            self._mesh = rt2.mesh
            self._shard_spec = dataclasses.replace(
                self._shard_spec, n_shards=rt2.n_dev)
            self.n_pad = rt2.n_pad
            self.valid = rt2.valid
            # ownership boundaries moved: recount the realized edge cut
            self._cut_edges = int(self._crossing(self._hg_rel.edges))
        else:
            rt2 = rt
        r2 = np.zeros(rt2.n_pad, R_h.dtype)
        r2[:n] = R_h[:n]
        aff2 = rt2.mask_from_indices(np.nonzero(aff_h | help_mask)[0])
        rc2 = rt2.mask_from_indices(np.nonzero(help_mask)[0])
        R, st2 = rt2.drive(jnp.asarray(r2), aff2, expand=True, rc0=rc2,
                           max_sweeps=cfg.max_iterations)
        wall = time.perf_counter() - t0
        self._recoveries.append(fd.RecoveryRecord(
            domain="shard", batch_index=self._batch_index + 1,
            wall_time_s=wall, shard=fault.shard, permanent=fault.permanent,
            helped_vertices=helped, recovery_sweeps=st2.sweeps,
            description=(
                f"shard {fault.shard} "
                f"{'lost — elastic re-partition to' if fault.permanent else 'stalled — rejoined,'} "
                f"{rt2.n_dev} shards; {helped} un-converged rows helped")))
        stats = dist.DistStats(
            sweeps=st1.sweeps + st2.sweeps, converged=st2.converged,
            full_exchanges=st1.full_exchanges + st2.full_exchanges,
            delta_exchanges=st1.delta_exchanges + st2.delta_exchanges,
            edges_processed=st1.edges_processed + st2.edges_processed)
        return R, stats

    def _update_stream(self, deletions, insertions, variant: str = "df"
                       ) -> StreamBatchResult:
        """Stream-mode step: delta scatter → frontier seed → fused
        convergence loop, all device-side after the O(batch) host
        bookkeeping."""
        global _NEW_BUCKET_STARTED, _NEW_BUCKET_ACTIVE
        if self._push and variant == "dt":
            raise ValueError(
                "driver='push' does not implement the dt reachability "
                "marking (it walks throwaway snapshots of the pull "
                "iterate); use variant='df' or 'nd', or a driver='pull' "
                "session")
        t0 = time.perf_counter()
        cache0 = self._drv_cache_size()
        with _RETRACE_LOCK:     # open the attribution window with cache0
            nb_started0 = _NEW_BUCKET_STARTED
            nb_active0 = _NEW_BUCKET_ACTIVE
        g_prev_snap = (self.hg.snapshot(block_size=self.block_size)
                       if variant == "dt" else None)
        with TraceAnnotation("session.plan"):
            dels_eff, ins_eff = effective_batch(self.hg, deletions, insertions)
            rows, cols, vals = signed_edge_delta(dels_eff, ins_eff)
            if self._tiered:
                # host tier first: patch host truth, drop residency of the
                # touched blocks (their slab copies are stale — the admission
                # below re-gathers them fresh), update the host aux twins.
                # mat_prev/mat_new stay None: tiered seeding is host-side.
                plan = self.pool.apply_delta(rows, cols, vals)
                self.inc.aux.apply_delta(self.block_size, rows, cols, vals)
                self.hot.invalidate(
                    plan.touched_rb,
                    structure_changed=(plan.tile_cols is not None
                                       or plan.n_new > plan.n_old))
                mat_prev = mat_new = None
            else:
                mat_prev = self.inc.mat
                mat_new = self.inc.advance(
                    self.hg, None, deletions, insertions,
                    effective=(dels_eff, ins_eff))
        self._hg_prev, self._g_prev = self.hg, None
        self._last_batch = (np.asarray(deletions, np.int64).reshape(-1, 2),
                            np.asarray(insertions, np.int64).reshape(-1, 2))
        self._r_prev = self.R
        with TraceAnnotation("session.host_graph"):
            self.hg = self.hg.apply_batch(deletions, insertions)
            if self.config.integrity is not None:
                # the host-truth digest tracks every legitimate rebinding of
                # the host graph; anything mutating hg.edges WITHOUT passing
                # here is what the deep scrub's graph_digest check catches
                self._hg_digest = self._graph_digest()

        # push seeding divides by the PRE-batch degrees: capture the host
        # twin before the mirror patch below rebinds it
        deg_old_host = self._out_deg_host if self._push else None
        # patch the device-resident operand mirrors in O(batch): only the
        # bucketed signed delta crosses host→device, never the graph-sized
        # vectors
        scatter_fault, self._scatter_fault = self._scatter_fault, None
        with TraceAnnotation("session.scatter"):
            if len(rows):
                b_pad = ops.capacity_bucket(len(rows), ops.DELTA_BATCH_BUCKET)
                z = np.zeros(b_pad - len(rows), np.int32)
                dev_args = (jnp.asarray(np.concatenate(
                                [rows.astype(np.int32), z])),
                            jnp.asarray(np.concatenate(
                                [cols.astype(np.int32), z])),
                            jnp.asarray(np.concatenate(
                                [vals.astype(np.int32), z])))
                # a pending torn-scatter corruption (scatter_drop/scatter_dup)
                # silently skips or double-applies the DEVICE patch only — the
                # host twins below stay truth, which is exactly how the scrub's
                # mirror digests detect the tear
                reps = {"scatter_drop": 0,
                        "scatter_dup": 2}.get(scatter_fault, 1)
                for _ in range(reps):
                    self._out_deg, self._rb_in, self._rb_out, self._bmat = \
                        _apply_operand_delta(
                            self._out_deg, self._rb_in, self._rb_out,
                            self._bmat, *dev_args, block=self.block_size)
                self._out_deg_host = self._out_deg_host + np.bincount(
                    cols, weights=vals, minlength=self.n_pad
                ).astype(self._out_deg_host.dtype)

        with TraceAnnotation("session.seed"):
            batch_dev = fr.pack_batch(self.n_pad, deletions, insertions)
            seed_idx = None
            pextras = None
            if self._push:
                # residual seeding replaces the frontier marking: the residual
                # IS the frontier (work ∝ its mass).  seed_idx feeds the same
                # tiered admission want-set as the pull df seed.
                R0, seed_idx = self._seed_push(variant, dels_eff, ins_eff,
                                               deg_old_host)
                affected, expand = None, True
            elif variant == "df":
                if self._tiered:
                    # host-side DF seed (paper Alg. 1 lines 4-6) through the
                    # sorted host key sets — needs no device pull matrices, and
                    # only the bucketed index list crosses to the device
                    dels_a = np.asarray(deletions, np.int64).reshape(-1, 2)
                    ins_a = np.asarray(insertions, np.int64).reshape(-1, 2)
                    sources = np.concatenate([dels_a[:, 0], ins_a[:, 0]])
                    seed_idx = dist.df_seed_indices(self._hg_prev, self.hg,
                                                    sources)
                    affected = self._mask_from_indices(seed_idx)
                else:
                    affected = _seed_affected(
                        mat_prev, mat_new, self._bmat, batch_dev, self.valid,
                        block_size=self.block_size, interpret=self.interpret,
                        backend=self.backend)
                R0, expand = self.R, True
            elif variant == "dt":
                g_new_snap = self.hg.snapshot(block_size=self.block_size)
                affected = fr.dt_affected(g_prev_snap, g_new_snap, batch_dev)
                R0, expand = self.R, False
            elif variant == "nd":
                affected, R0, expand = self.valid, self.R, False
            else:   # static
                affected = self.valid
                R0 = jnp.where(self.valid, 1.0 / self.n, 0).astype(self._dtype)
                expand = False
        if self._tiered:
            # tiered drives always expand: the refill loop is block-Jacobi
            # over residency partitions, and only frontier expansion
            # re-marks a resident block whose non-resident inputs moved in
            # a later round (docs/SCALE.md §Miss semantics)
            expand = True
            # frontier-biased admission BEFORE the drive: delta-touched
            # blocks ∪ seed blocks ∪ their tile-adjacent candidates (the
            # first expansion wave) — ONE batched gather per step
            want = [np.asarray(plan.touched_rb, np.int64)]
            if seed_idx is not None and len(seed_idx):
                srb = np.unique(np.asarray(seed_idx, np.int64)
                                // self.block_size)
                want += [srb, np.nonzero(
                    self.inc.aux.bmat[:, srb].any(axis=1))[0]]
            self._admit(np.concatenate(want))
            key_mat = self.inc.mat
        else:
            key_mat = mat_new

        # first visit to an operand bucket (tile capacity × slot width ×
        # expand flag) legitimately compiles once — the doubling ladder's
        # documented cost.  Record the visit BEFORE driving so the growth
        # observed below can be attributed to it.
        dkey = (int(key_mat.tiles.shape[0]),
                int(key_mat.tile_cols.shape[1]),
                "push" if self._push else bool(expand))
        new_bucket = dkey not in self._driver_keys
        self._driver_keys.add(dkey)

        if new_bucket:
            with _RETRACE_LOCK:
                _NEW_BUCKET_STARTED += 1
                _NEW_BUCKET_ACTIVE += 1
        try:
            if self._push:
                R, stats, pextras = self._drive_push_refill(R0)
            else:
                R, stats = self._drive_refill(R0, affected, expand=expand)
        finally:
            if new_bucket:
                with _RETRACE_LOCK:
                    _NEW_BUCKET_ACTIVE -= 1
        self.R = R
        raw = (np.asarray(deletions).reshape(-1, 2).shape[0]
               + np.asarray(insertions).reshape(-1, 2).shape[0])
        cache1 = self._drv_cache_size()
        with _RETRACE_LOCK:
            nb_started1 = _NEW_BUCKET_STARTED
        retraces = (cache1 - cache0
                    if cache0 >= 0 and cache1 >= 0 else -1)
        # first-visit drives overlapping this window: ones already active
        # at cache0 plus ones begun since — any of their compiles may land
        # in this window's cache delta (shared process-wide jit cache)
        overlapping = nb_active0 + (nb_started1 - nb_started0)
        bucket = 0
        if retraces > 0 and (new_bucket or overlapping > 0):
            bucket, retraces = retraces, 0
        return StreamBatchResult(
            ranks=R, stats=stats,
            wall_time_s=time.perf_counter() - t0, batch_edges=raw,
            driver_cache_size=cache1,
            driver_retraces=retraces, bucket_retraces=bucket,
            residual_mass=(pextras["residual_l1"]
                           if pextras is not None else None),
            pushed_blocks=(pextras["pushed_blocks"]
                           if pextras is not None else None))

    def _update_walk(self, deletions, insertions, variant: str = "df"
                     ) -> StreamBatchResult:
        """Walk-mode step: patch the adjacency slabs and regenerate ONLY
        the walk segments passing through touched vertices (O(batch ·
        walks-per-touched-vertex), never O(n·R)).  The ``variant`` is
        accepted for surface parity but does not change the marking — walk
        invalidation IS the frontier."""
        t0 = time.perf_counter()
        cache0 = we.cache_size()
        dels_eff, ins_eff = effective_batch(self.hg, deletions, insertions)
        self._hg_prev, self._g_prev = self.hg, None
        self._last_batch = (np.asarray(deletions, np.int64).reshape(-1, 2),
                            np.asarray(insertions, np.int64).reshape(-1, 2))
        self._r_prev = self.R
        self.hg = self.hg.apply_batch(deletions, insertions)
        wstats = self.walks.apply_batch(dels_eff, ins_eff)
        self.R = self.walks.pagerank()
        self._r_verified = self.R
        raw = (np.asarray(deletions).reshape(-1, 2).shape[0]
               + np.asarray(insertions).reshape(-1, 2).shape[0])
        cache1 = we.cache_size()
        retraces = (cache1 - cache0
                    if cache0 >= 0 and cache1 >= 0 else -1)
        bucket = 0
        if retraces > 0 and wstats.new_bucket:
            bucket, retraces = retraces, 0
        stats = SweepStats(
            sweeps=1, iterations=1, blocks_processed=0,
            edges_processed=wstats.steps, sim_time_ms=0.0,
            converged=True, dnf=False)
        return StreamBatchResult(
            ranks=self.R, stats=stats,
            wall_time_s=time.perf_counter() - t0, batch_edges=raw,
            driver_cache_size=cache1,
            driver_retraces=retraces, bucket_retraces=bucket,
            regenerated_walks=wstats.regenerated_walks,
            touched_walks=wstats.touched_walk_mass,
            total_walks=wstats.total_walks)

    def _update_snapshot(self, deletions, insertions, variant: str
                         ) -> StreamBatchResult:
        """Snapshot-mode step: rebuild the snapshot (O(m) host work — the
        legacy path, kept for the oracle engines) and converge through the
        engine adapter."""
        t0 = time.perf_counter()
        cache0 = _driver_cache_size() if self.engine_name == "pallas" else -1
        g_prev = self.g
        hg_new = self.hg.apply_batch(deletions, insertions)
        g_new = hg_new.snapshot(block_size=self.block_size)
        batch_dev = fr.batch_to_device(g_new, deletions, insertions)
        if variant == "df":
            affected = fr.initial_affected(g_prev, g_new, batch_dev)
            R0, expand = pad_ranks(g_new, self.R), True
        elif variant == "dt":
            affected = fr.dt_affected(g_prev, g_new, batch_dev)
            R0, expand = pad_ranks(g_new, self.R), False
        elif variant == "nd":
            affected, expand = g_new.vertex_valid, False
            R0 = pad_ranks(g_new, self.R)
        else:   # static
            affected, expand = g_new.vertex_valid, False
            R0 = initial_ranks(g_new, self._dtype)
        self._hg_prev, self._g_prev = self.hg, g_prev
        self._last_batch = (np.asarray(deletions, np.int64).reshape(-1, 2),
                            np.asarray(insertions, np.int64).reshape(-1, 2))
        self._r_prev = self.R
        self.hg, self.g = hg_new, g_new
        self.n, self.n_pad = g_new.n, g_new.n_pad
        self.valid = g_new.vertex_valid
        res = self._converge(R0, affected, expand=expand, g=g_new)
        raw = (np.asarray(deletions).reshape(-1, 2).shape[0]
               + np.asarray(insertions).reshape(-1, 2).shape[0])
        cache1 = _driver_cache_size() if self.engine_name == "pallas" else -1
        return StreamBatchResult(
            ranks=res.ranks, stats=res.stats,
            wall_time_s=time.perf_counter() - t0, batch_edges=raw,
            driver_cache_size=cache1,
            driver_retraces=(cache1 - cache0
                             if cache0 >= 0 and cache1 >= 0 else -1))

    # -- recompute -----------------------------------------------------------
    def recompute(self, variant: str = "static") -> PagerankResult:
        """Re-solve the session's **current** graph.

        ``"static"`` starts from uniform ranks, ``"nd"`` warm-starts from
        the session's ranks (both with every vertex affected).  ``"dt"`` /
        ``"df"`` *replay the last update batch* with that variant's marking
        from the pre-batch ranks — the what-if tool for comparing variants
        on the same step (requires at least one prior ``update``)."""
        self._ensure_open()
        if variant not in VARIANTS:
            raise ValueError(f"variant={variant!r} invalid; "
                             f"expected one of {VARIANTS}")
        res = self._recompute(variant)
        if self._process_domain is not None and not self._replaying:
            # recompute changes served state OUTSIDE the WAL's batch
            # stream — persist a checkpoint so restore() matches what the
            # live session was serving
            self._checkpoint_now()
        return res

    def _recompute(self, variant: str) -> PagerankResult:
        if self._sharded:
            return self._recompute_sharded(variant)
        if self._walk:
            return self._recompute_walk(variant)
        if self._push:
            return self._recompute_push(variant)
        if variant in ("static", "nd"):
            R0 = (self.R if variant == "nd" else
                  jnp.where(self.valid, 1.0 / self.n, 0).astype(self._dtype))
            if self._stream:
                t0 = time.perf_counter()
                R, stats = self._drive_refill(
                    R0, self.valid, expand=self._tiered,
                    want_rb=(np.arange(self.n_rb) if self._tiered
                             else None))
                self.R = R
                return PagerankResult(ranks=R, stats=stats,
                                      wall_time_s=time.perf_counter() - t0)
            return self._converge(R0, self.valid, expand=False)

        # dt / df: replay the last batch's marking from the pre-batch state
        if self._last_batch is None:
            raise ValueError(
                f"recompute({variant!r}) replays the last update batch, but "
                "no batch has been applied yet — call update() first or use "
                "variant='static'/'nd'")
        g_prev = (self._g_prev if self._g_prev is not None
                  else self._hg_prev.snapshot(block_size=self.block_size))
        g_cur = (self.g if self.g is not None
                 else self.hg.snapshot(block_size=self.block_size))
        batch_dev = fr.batch_to_device(g_cur, *self._last_batch)
        if variant == "df":
            affected = fr.initial_affected(g_prev, g_cur, batch_dev)
        else:
            affected = fr.dt_affected(g_prev, g_cur, batch_dev)
        R0 = pad_ranks(g_cur, self._r_prev)
        mat = aux = None
        if self._stream and not self._tiered:
            # reuse the incrementally maintained operands; tiered sessions
            # hold only a partial device view, so their dt/df replay (an
            # explicitly O(m) what-if path) rebuilds a full throwaway
            # matrix from the snapshot instead
            mat, aux = self.inc.mat, self.inc.aux
        return self._converge(R0, affected, expand=(variant == "df"),
                              g=g_cur, mat=mat, aux=aux)

    def _recompute_push(self, variant: str) -> PagerankResult:
        """Push-session re-solve.  ``nd`` keeps the rank estimate and
        rebuilds the exact residual (O(m)); ``static`` restarts cold.
        ``dt``/``df`` replay the *pull* marking machinery and have no push
        analogue (same contract as the walk engine's recompute)."""
        if variant not in ("static", "nd"):
            raise ValueError(
                f"recompute({variant!r}) replays the pull driver's "
                "frontier marking; a driver='push' session re-solves via "
                "variant='static' or 'nd'")
        t0 = time.perf_counter()
        if variant == "nd":
            P0 = self.R
            self._residual = self._residual_recompute(P0)
        else:
            P0 = jnp.zeros((self.n_pad,), self._dtype)
            self._residual = jnp.where(
                self.valid, (1.0 - self.config.alpha) / self.n,
                0).astype(self._dtype)
        R, stats, _ = self._drive_push_refill(
            P0, want_rb=(np.arange(self.n_rb) if self._tiered else None))
        self.R = R
        return PagerankResult(ranks=R, stats=stats,
                              wall_time_s=time.perf_counter() - t0)

    def _recompute_sharded(self, variant: str) -> PagerankResult:
        """Sharded re-solve through the cached compiled sweep — same
        variant semantics as single-device recompute."""
        cfg = self.config
        t0 = time.perf_counter()
        if variant in ("static", "nd"):
            R0 = (self.R if variant == "nd" else
                  jnp.where(self.valid, 1.0 / self.n, 0).astype(self._dtype))
            affected, expand = self.valid, False
        else:
            if self._last_batch_rel is None:
                raise ValueError(
                    f"recompute({variant!r}) replays the last update batch, "
                    "but no batch has been applied yet — call update() "
                    "first or use variant='static'/'nd'")
            dels_rel, ins_rel = self._last_batch_rel
            affected = self._sharded_affected(variant, self._hg_rel_prev,
                                              dels_rel, ins_rel)
            R0, expand = self._r_prev, (variant == "df")
        R, dstats = self.runtime.drive(R0, affected, expand=expand,
                                       max_sweeps=cfg.max_iterations)
        self.R = R
        self._x_full += dstats.full_exchanges
        self._x_delta += dstats.delta_exchanges
        self._x_sweeps += dstats.sweeps
        stats = SweepStats(sweeps=dstats.sweeps, iterations=dstats.sweeps,
                           edges_processed=dstats.edges_processed,
                           converged=dstats.converged)
        return PagerankResult(ranks=R, stats=stats,
                              wall_time_s=time.perf_counter() - t0)

    def _recompute_walk(self, variant: str) -> PagerankResult:
        """Walk-mode re-solve: regenerate EVERY walk segment from the
        current graph (``static``/``nd`` — both cold-start here, there is
        no warm iterate to reuse).  The marking replays (``dt``/``df``)
        have no walk analogue: walk invalidation is already the frontier,
        so they raise rather than silently aliasing ``static``."""
        if variant not in ("static", "nd"):
            raise ValueError(
                f"recompute({variant!r}) replays a sweep-engine affected "
                "marking, which the walk engine does not have — walk "
                "sessions regenerate globally via variant='static'/'nd' "
                "(per-delta localization happens inside update())")
        t0 = time.perf_counter()
        cfg = self.config
        self.walks = we.WalkState(
            self.hg, R=cfg.resolved_walks_per_vertex,
            L=cfg.resolved_walk_length, seed=cfg.resolved_walk_seed,
            alpha=cfg.alpha, dtype=self._dtype)
        self.R = self.walks.pagerank()
        self._r_verified = self.R
        stats = SweepStats(sweeps=1, iterations=1,
                           edges_processed=int(self.walks.total_steps),
                           converged=True)
        return PagerankResult(ranks=self.R, stats=stats,
                              wall_time_s=time.perf_counter() - t0)

    # -- serving reads (device-resident, no full-rank host transfer) ---------
    def _vertex_ids(self, vertices) -> np.ndarray:
        """Validate a vertex-id argument (Python int, sequence, or numpy
        array) into a flat int64 array, rejecting non-integer dtypes and
        negative/out-of-range ids with a clear error."""
        arr = np.asarray(vertices)
        if arr.size == 0:       # empty id lists are valid (empty result) —
            return np.zeros(0, np.int64)  # note np.asarray([]) is float64
        if arr.dtype == object or not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"vertex ids must be integers, got dtype {arr.dtype} "
                f"(value: {vertices!r})")
        idx = arr.reshape(-1).astype(np.int64)
        bad = (idx < 0) | (idx >= self.n)
        if bad.any():
            raise ValueError(
                f"vertex id(s) {idx[bad][:8].tolist()} out of range for a "
                f"graph with {self.n} vertices (valid ids: 0..{self.n - 1})")
        return idx

    def query(self, vertices: Union[int, Sequence[int], np.ndarray]
              ) -> np.ndarray:
        """Ranks of the given vertices: one device gather, only ``len(
        vertices)`` values cross to the host.  Accepts a Python int, a
        list, or an integer array; negative or out-of-range ids raise
        ``ValueError``.  Topology-transparent: sharded sessions translate
        through the partitioner relabeling."""
        self._ensure_open()
        idx = self._vertex_ids(vertices)
        if self._sharded:
            idx = self._inv[idx]
        vals = self.R[jnp.asarray(idx)]
        self._queries += int(idx.shape[0])
        return np.asarray(vals)

    def top_k(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(values, vertex ids) of the k highest-ranked vertices — computed
        device-side, only 2k scalars transferred."""
        self._ensure_open()
        if not isinstance(k, (int, np.integer)):
            raise ValueError(
                f"k must be an integer, got {type(k).__name__} ({k!r})")
        if k < 1:
            raise ValueError(f"k={k} must be >= 1")
        k = int(min(k, self.n))
        masked = jnp.where(self.valid, self.R, -jnp.inf)
        vals, idx = jax.lax.top_k(masked, k)
        self._queries += k
        idx = np.asarray(idx)
        if self._sharded:
            idx = self._order[idx]          # back to caller vertex ids
        return np.asarray(vals), idx

    def ppr_query(self, seeds, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(values, vertex ids) of the k highest **personalized** PageRank
        estimates for a uniform restart over ``seeds`` — the per-user read
        the walk engine exists for.  O(read): one device gather over the
        seeds' walk segments plus a top-k; no regeneration, no sweep.
        Engines without the ``"ppr"`` capability raise
        :class:`repro.api.CapabilityError`."""
        self._ensure_open()
        if not self._walk:
            raise registry.CapabilityError(
                f"ppr_query needs an engine declaring the 'ppr' capability; "
                f"engine {self.engine_name!r} declares supports="
                f"{sorted(registry.supports_of(self.engine))} — open the "
                "session with EngineConfig(engine='walk')")
        seeds = self._vertex_ids(seeds)
        if seeds.size == 0:
            raise ValueError("ppr_query needs at least one seed vertex "
                             "(got an empty seed set)")
        if not isinstance(k, (int, np.integer)):
            raise ValueError(
                f"k must be an integer, got {type(k).__name__} ({k!r})")
        if k < 1:
            raise ValueError(f"k={k} must be >= 1")
        k = int(min(k, self.n))
        vals, idx = self.walks.ppr_top_k(seeds, k)
        self._queries += k
        return np.asarray(vals), np.asarray(idx)

    @property
    def ranks(self) -> np.ndarray:
        """Full host copy of the rank vector in caller vertex order (the
        expensive full read — prefer :meth:`query` / :meth:`top_k` for
        serving)."""
        self._ensure_open()
        r = np.asarray(self.R)
        if self._sharded:
            out = np.zeros(self.n_pad, r.dtype)
            out[self._order] = r[:self.n]
            return out
        return r

    # -- lifecycle end -------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def device_footprint(self) -> Tuple[int, ...]:
        """Ids of the devices this session's state occupies (sharded
        sessions span their mesh; closed sessions hold nothing)."""
        if self._closed:
            return ()
        if self._sharded:
            return tuple(d.id for d in self._mesh.devices.flat)
        try:
            return tuple(sorted(d.id for d in self.R.devices()))
        except Exception:           # pragma: no cover - non-jax R
            return (0,)

    def _ensure_open(self) -> None:
        if self._closed:
            raise ValueError("session is closed — open a new "
                             "PageRankSession")

    def close(self) -> None:
        """End the session: unregister from any :class:`PageRankService`
        and drop every device buffer reference (rank vector, tile pool /
        operand mirrors, sharded slabs) so long-lived multi-session
        processes reclaim device memory.  Idempotent; forked twins keep
        their own references and are unaffected."""
        if self._closed:
            return
        self._closed = True
        svc, self._service = self._service, None
        if svc is not None:
            svc._detach(self)
        for attr in ("R", "inc", "runtime", "g", "valid", "_out_deg",
                     "_rb_in", "_rb_out", "_bmat", "_fault_tables",
                     "_r_prev", "store", "_process_domain", "walks",
                     "_r_verified", "_out_deg_host", "_corruption_faults",
                     "pool", "hot", "_rb_res_full", "_deferred_rb",
                     "_residual"):
            if hasattr(self, attr):
                setattr(self, attr, None)

    def __enter__(self) -> "PageRankSession":
        self._ensure_open()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    # -- durability / process fault domain (docs/FAULTS.md) ------------------
    def _meta(self) -> dict:
        """JSON-able store meta: graph identity + a config echo (the
        non-serializable ``faults`` / ``fault_domain`` objects are
        injection schedules, not state — they are not persisted)."""
        cfgd = {}
        for f in dataclasses.fields(self.config):
            if f.name in ("faults", "fault_domain"):
                continue
            v = getattr(self.config, f.name)
            if f.name == "dtype" and v is not None:
                v = str(jnp.dtype(v))
            if f.name == "integrity" and v is not None:
                v = v.to_dict()     # coerced back by EngineConfig
            cfgd[f.name] = v
        return {"format": 1, "kind": "pagerank-session",
                "n": int(self.hg.n), "config": cfgd}

    def _checkpoint_into(self, store: SessionStore) -> str:
        """One atomic checkpoint of the current session state: caller-order
        ranks + the host edge set, keyed by the applied-batch count."""
        if store.read_meta() is None:
            store.write_meta(self._meta())
        return store.checkpoint(
            ranks=np.asarray(self.ranks[:self.n]), edges=self.hg.edges,
            batch_index=self._batch_index)

    def _checkpoint_now(self) -> str:
        return self._checkpoint_into(self.store)

    def save(self, directory: Optional[str] = None) -> str:
        """Force one atomic checkpoint of the current state (ranks +
        edge set, keyed by the applied-batch count).  Durable sessions
        checkpoint into their attached store (also shortening the WAL
        replay a later restore pays); any session may pass ``directory``
        to save into a fresh :class:`~repro.ckpt.checkpoint.SessionStore`.
        Returns the checkpoint path."""
        self._ensure_open()
        if self.hg is None:
            raise ValueError("save() needs a host graph (from_graph, or "
                             "from_snapshot with hg=)")
        store = self.store
        if directory is not None and (
                store is None
                or os.path.abspath(directory) != os.path.abspath(store.dir)):
            store = SessionStore(directory)
        if store is None:
            raise ValueError(
                "save() needs a directory= (this session has no attached "
                "store; open it with durability='wal' + store_dir= for "
                "continuous durability)")
        return self._checkpoint_into(store)

    @classmethod
    def restore(cls, directory: str, *,
                config: Optional[EngineConfig] = None,
                interpret: Optional[bool] = None) -> "PageRankSession":
        """Reopen a session from its durable store: newest valid rank
        checkpoint + WAL replay of every batch logged after it, through
        the normal update hot path (stream mode replays recompile-free).
        ``config`` overrides the stored config — e.g. a different
        ``n_shards`` restores onto a different device count (elastic
        rescale).  The recovery is recorded in ``report()``
        (``replayed_batches``, ``recovery_time_s``)."""
        t0 = time.perf_counter()
        store = SessionStore(directory)
        meta = store.read_meta()
        if meta is None:
            raise ValueError(f"{directory!r} is not a session store "
                             "(missing meta.json)")
        got = store.restore_latest_state()
        if got is None:
            raise ValueError(f"{directory!r} holds no valid checkpoint "
                             "(all steps corrupt or none written)")
        state, ckpt_idx = got
        if config is None:
            config = EngineConfig.from_kwargs(**meta["config"])
        hg = HostGraph(int(meta["n"]), state["edges"])
        sess = cls(hg=hg, config=config, r0=state["ranks"],
                   interpret=interpret,
                   store_dir=directory if config.durability == "wal"
                   else None,
                   _restore_attach=True)
        sess._batch_index = ckpt_idx
        recs = store.read_wal(after=ckpt_idx)
        sess._replaying = True
        try:
            for rec in recs:
                sess.update(rec.deletions, rec.insertions,
                            variant=rec.variant)
        finally:
            sess._replaying = False
        # replay warmed every hot-path cache entry the stream needs; the
        # post-restore retrace counter starts here.  With nothing to
        # replay the session is cold — leave _warm_idx unset so report()
        # excuses the first (compile-bearing) update as usual
        sess._warm_idx = len(sess._history) if recs else None
        sess._recoveries.append(fd.RecoveryRecord(
            domain="process", batch_index=ckpt_idx,
            wall_time_s=time.perf_counter() - t0,
            replayed_batches=len(recs),
            description=(f"restored from checkpoint {ckpt_idx} + "
                         f"{len(recs)} WAL batch(es)")))
        return sess

    # -- warmup / reporting --------------------------------------------------
    def warmup(self) -> None:
        """Trace the full per-batch pipeline at the stream's operand shapes
        without perturbing graph or rank state: a zero-value delta against
        vertex 0's (always present) self-loop tile warms the device scatter
        at the base batch bucket, and an empty-batch step warms the frontier
        seed and the fused driver.  Batches larger than the base bucket
        still pay one compile per new bucket they reach.  Snapshot-mode
        sessions are already warm from their initial solve."""
        self._ensure_open()
        if self._sharded:
            self.runtime.warmup(self.R)
            self._warm_idx = len(self._history)
            return
        if self._walk:
            self.walks.warmup()
            self._warm_idx = len(self._history)
            return
        if self._stream:
            z = np.zeros(1, np.int64)
            if self._tiered:
                # warm the host-tier delta path and the invalidate →
                # re-admit gather at the base bucket (values all zero, so
                # state is unperturbed)
                self.pool.apply_delta(z, z, np.zeros(1))
                self.hot.invalidate(np.zeros(1, np.int64))
                self._admit(np.zeros(1, np.int64))
            else:
                self.inc.mat = ops.apply_delta(self.inc.mat, z, z,
                                               np.zeros(1))
            empty = np.zeros((0, 2), np.int64)
            # not recorded in history, and the dt/df replay state must not
            # see the empty warmup batch as "the last update"
            saved = (self._last_batch, self._hg_prev, self._g_prev,
                     self._r_prev)
            self._update_stream(empty, empty)
            (self._last_batch, self._hg_prev, self._g_prev,
             self._r_prev) = saved
        self._warm_idx = len(self._history)

    def report(self) -> SessionReport:
        """Latency / retrace / work statistics over the update history.

        ``retraces_post_warmup`` sums the driver-cache growth observed
        *during this session's own updates* (after :meth:`warmup`, or after
        the first — expected — trace when warmup was skipped), so sessions
        sharing one process don't count each other's compiles."""
        walls = [r.wall_time_s for r in self._history]
        growth = [r.driver_retraces for r in self._history]
        buckets = 0
        if (self.engine_name not in ("pallas", "distributed", "walk")
                or not growth or any(gr < 0 for gr in growth)):
            retraces = -1
        else:
            start = self._warm_idx if self._warm_idx is not None else 1
            retraces = sum(growth[start:])
            buckets = sum(r.bucket_retraces
                          for r in self._history[start:])
        icfg = self.config.integrity
        integrity = None
        if (icfg is not None or self._integrity_checks
                or self._corruption_detected):
            by_rung = {r: 0 for r in ig.REPAIR_RUNGS}
            for rec in self._recoveries:
                if rec.domain == "corruption" and rec.rung in by_rung:
                    by_rung[rec.rung] += 1
            integrity = {
                "checks_run": int(self._integrity_checks),
                "corruption_detected": int(self._corruption_detected),
                "repairs": by_rung,
                "scrub_interval_s": (float(icfg.scrub_interval_s)
                                     if icfg is not None else None),
            }
        dev_bytes = self._device_bytes()
        spec = self._shard_spec
        wire = None
        if spec is not None:
            frac_full = (self._x_full / max(self._x_sweeps, 1)
                         if spec.exchange == "delta" else 1.0)
            wire = dist.collective_bytes_per_sweep(
                n_pad=self.n_pad, n_dev=spec.n_shards,
                exchange=spec.exchange, rank_bytes=self._dtype.itemsize,
                delta_capacity=spec.delta_capacity, expand=True,
                frac_full=frac_full)
        return SessionReport(
            engine=self.engine_name,
            backend=self.backend if self.engine_name == "pallas" else None,
            mode=self.config.mode,
            n_updates=len(self._history),
            p50_s=float(np.percentile(walls, 50)) if walls else 0.0,
            p95_s=float(np.percentile(walls, 95)) if walls else 0.0,
            retraces_post_warmup=retraces,
            total_sweeps=sum(r.stats.sweeps for r in self._history),
            total_edges_processed=sum(r.stats.edges_processed
                                      for r in self._history),
            queries_served=self._queries,
            wall_times_s=walls,
            batches_converged=sum(1 for r in self._history
                                  if r.stats.converged),
            sweep_cap_hits=sum(1 for r in self._history
                               if not r.stats.converged),
            topology=self.config.topology,
            n_shards=spec.n_shards if spec is not None else None,
            partitioner=spec.partitioner if spec is not None else None,
            edge_cut=(self._cut_edges / max(self.hg.m, 1)
                      if spec is not None else None),
            collective_bytes_per_sweep=wire,
            bucket_retraces_post_warmup=buckets,
            durability=self.config.durability,
            recoveries=len(self._recoveries),
            recovery_time_s=sum(r.wall_time_s for r in self._recoveries),
            replayed_batches=sum(r.replayed_batches
                                 for r in self._recoveries),
            recovery_events=[r.to_dict() for r in self._recoveries],
            integrity=integrity,
            tiering=(self.hot.stats() if self._tiered
                     and self.hot is not None else None),
            device_bytes=dev_bytes,
            bytes_per_vertex=(sum(dev_bytes.values()) / max(self.n, 1)
                              if dev_bytes is not None else None),
            driver=getattr(self.config, "driver", "pull"),
            sweeps_history=[int(r.stats.sweeps) for r in self._history],
            edges_processed_history=[int(r.stats.edges_processed)
                                     for r in self._history],
            residual_mass_last=next(
                (r.residual_mass for r in reversed(self._history)
                 if r.residual_mass is not None), None),
            pushed_blocks=(sum(r.pushed_blocks for r in self._history
                               if r.pushed_blocks is not None)
                           if any(r.pushed_blocks is not None
                                  for r in self._history) else None))

    def _device_bytes(self) -> Optional[dict]:
        """Per-component device-resident bytes (the ``report()`` memory
        audit).  ``None`` for sharded topologies, whose state is accounted
        per device by the wire model instead."""
        if self._sharded or self._closed:
            return None

        def _nb(*arrs):
            return int(sum(a.nbytes for a in arrs
                           if a is not None and hasattr(a, "nbytes")))

        out = {"ranks": _nb(self.R, self.valid,
                            getattr(self, "_residual", None))}
        if self._stream:
            mat = self.inc.mat
            out["tile_pool"] = _nb(mat.tiles)
            out["slot_tables"] = _nb(mat.tile_cols, mat.tile_idx)
            out["operand_mirrors"] = _nb(self._out_deg, self._rb_in,
                                         self._rb_out, self._bmat)
            if self._tiered:
                out["slot_tables"] += _nb(self.hot.rb_res)
        elif self.g is not None:
            out["graph_snapshot"] = _nb(*jax.tree_util.tree_leaves(self.g))
        if self._walk and getattr(self, "walks", None) is not None:
            out["walk_buffers"] = _nb(*(v for v in vars(self.walks).values()
                                        if isinstance(v, jnp.ndarray)))
        return out

    # -- what-if branching ---------------------------------------------------
    def fork(self) -> "PageRankSession":
        """Cheap what-if branch: the new session shares every device array
        with its parent — including the tile pool — until one side's
        updates diverge them (jax arrays are immutable; deltas patch
        functionally).  Host-side mutable state (the aux twins, history,
        replay state) is copied so the branches are fully independent."""
        self._ensure_open()
        new = object.__new__(PageRankSession)
        new.__dict__.update(self.__dict__)
        new._history = []
        new._warm_idx = 0 if self._warm_idx is not None else None
        new._queries = 0
        new._service = None       # forks are not registered with a service
        # a fork is a what-if branch, not a durable replica: two writers
        # on one WAL would interleave corruptingly, so the twin detaches
        # (save(directory=...) gives it its own store when needed)
        new.store = None
        new.store_dir = None
        new._process_domain = None
        new._recoveries = []
        new._replaying = False
        if self._shard_faults is not None:
            new._shard_faults = fd.ShardFaultDomain()
        # integrity state: checks/detections are per-session counters; the
        # bucket set and host twins are mutable and must not be shared
        new._integrity_checks = 0
        new._corruption_detected = 0
        new._integrity_alert = None
        new._scatter_fault = None
        new._driver_keys = set(self._driver_keys)
        if self._corruption_faults is not None:
            new._corruption_faults = fd.CorruptionFaultDomain()
        if getattr(self, "_out_deg_host", None) is not None:
            new._out_deg_host = self._out_deg_host.copy()
        if self.inc is not None:
            aux = self.inc.aux
            # the copies are required: MatrixAux.apply_delta patches bmat
            # in place, so a shared array would carry one branch's tile
            # presence into the other
            new.inc = IncrementalPullMatrix(
                self.inc.mat,
                MatrixAux(bmat=aux.bmat.copy(), rb_in=aux.rb_in.copy(),
                          rb_out=aux.rb_out.copy())
                if aux is not None else None)
        if self._tiered:
            # both tiers branch: the host pool copies (numpy is mutable),
            # the hot set forks over it (the immutable device slab is
            # shared until either side's admissions diverge it)
            new.pool = self.pool.copy()
            new.hot = self.hot.fork(new.pool)
            new._deferred_rb = None
        if self._sharded:
            new.runtime = self.runtime.fork()
        if self._walk:
            new.walks = self.walks.fork()
        return new
