"""Tiered graph storage: host-paged cold tiles + device hot set.

Covers the PR-9 tentpole and its satellites through the public surface:

* tiered streams match the untiered pallas stream within the bounded
  sub-τ abandonment window, at full and fractional budgets;
* a budget far below the pool drains every batch through the refill loop
  with zero post-warmup retraces and no :class:`SweepCapWarning`;
* the capacity-ladder interaction: a grow-then-delete stream under a
  fixed budget evicts/invalidates correctly (no stale-block reads);
* counters, the ``report()`` memory audit (satellite: per-component
  device bytes + bytes/vertex), save/restore budget-independence, fork
  isolation, and the integrity scrubber's host-tier twin;
* the int32 index diet overflow guards and the chunked R-MAT builder's
  seed-reproducibility (satellites);
* the blocked oracle's :class:`EdgePager` parity + ``paged_snapshot``.
"""
import warnings

import numpy as np
import pytest
import jax.numpy as jnp

from repro.api import EngineConfig, PageRankSession, SweepCapWarning
from repro.core import blocked as blk
from repro.core import pagerank as pr
from repro.core import tiering
from repro.core.delta import random_batch
from repro.core.graph import HostGraph
from repro.graphs.generators import grid_road, rmat

TAU = 1e-8
# the maxdr convergence escape abandons waves whose per-sweep change is
# <= tau, so two runs may differ by ~tau * alpha / (1 - alpha) ≈ 5.7 tau
ABANDON_TOL = 1e-6


def _pool_bytes(hg, block_size=64):
    g0 = hg.snapshot(block_size=block_size)
    src, dst = g0.in_edges_host()
    pool = tiering.HostTilePool.from_edges(
        dst, src, g0.n_pad, g0.n_pad, block=block_size,
        dtype=np.dtype(np.float32))
    return int(pool.nbytes)


def _cfg(budget=None, tau=TAU):
    return EngineConfig(engine="pallas", tau=tau, block_size=64,
                        dtype="float32", device_budget_bytes=budget)


def _local_stream(n, batches, k=16, seed=11, window=1024):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(batches):
        base = int(rng.integers(0, max(n - window, 1)))
        ins = base + rng.integers(0, min(window, n), (k, 2))
        out.append((np.zeros((0, 2), np.int64), ins))
    return out


def _run_stream(hg, cfg, stream):
    sess = PageRankSession.from_graph(hg, config=cfg)
    sess.warmup()
    stats = [sess.update(d, i).stats for d, i in stream]
    return sess, stats


# ---------------------------------------------------------------------------
# parity + drain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frac", [1.0, 0.5])
def test_tiered_stream_matches_untiered(frac):
    hg = grid_road(32, seed=7)
    stream = _local_stream(hg.n, 3)
    budget = max(int(_pool_bytes(hg) * frac), 1)
    tiered, st_t = _run_stream(hg, _cfg(budget), stream)
    plain, st_p = _run_stream(hg, _cfg(None), stream)
    assert all(s.converged for s in st_t)
    assert all(s.converged for s in st_p)
    linf = float(np.max(np.abs(np.asarray(tiered.ranks)
                               - np.asarray(plain.ranks))))
    assert linf < ABANDON_TOL, linf
    rep = tiered.report()
    assert rep.tiering is not None
    assert rep.retraces_post_warmup == 0
    tiered.close(), plain.close()


def test_tight_budget_drains_without_sweep_cap():
    """A budget holding only a fraction of the pool must still converge
    every batch via the deferred-refill loop — no SweepCapWarning, no
    retraces, evictions actually exercised."""
    hg = grid_road(64, seed=7)
    stream = _local_stream(hg.n, 4, window=4096)
    budget = _pool_bytes(hg) // 2
    with warnings.catch_warnings():
        warnings.simplefilter("error", SweepCapWarning)
        sess, stats = _run_stream(hg, _cfg(budget), stream)
    assert all(s.converged for s in stats)
    rep = sess.report()
    t = rep.tiering
    assert t["refill_drives"] > 0          # deferrals happened and drained
    assert t["evictions"] > 0              # budget pressure was real
    assert t["resident_blocks"] * 0 == 0 and t["slab_bytes"] <= budget
    assert rep.retraces_post_warmup == 0
    assert rep.bucket_retraces_post_warmup == 0
    sess.close()


def test_counters_and_hit_rate_sane():
    hg = grid_road(32, seed=7)
    sess, _ = _run_stream(hg, _cfg(_pool_bytes(hg) // 2),
                          _local_stream(hg.n, 3))
    t = sess.report().tiering
    for key in ("hits", "misses", "evictions", "admitted_tiles",
                "transfer_bytes", "refill_drives", "refill_stalls"):
        assert t[key] >= 0, key
    assert t["hits"] + t["misses"] > 0
    assert 0.0 <= t["hit_rate"] <= 1.0
    assert t["transfer_bytes"] > 0         # gathers actually moved bytes
    assert t["slab_tiles"] * t["slab_bytes"] >= 0
    assert t["pool_bytes"] >= t["slab_bytes"]
    sess.close()


def test_budget_below_floor_raises():
    hg = grid_road(16, seed=0)
    with pytest.raises(ValueError, match="too small to make a single"):
        PageRankSession.from_graph(hg, config=_cfg(budget=64))


# ---------------------------------------------------------------------------
# capacity-ladder interaction (satellite): grow then delete under pressure
# ---------------------------------------------------------------------------

def test_capacity_ladder_shrink_and_eviction():
    """Grow-then-delete stream under a fixed budget: pool growth rewidens
    the slot tables while eviction cycles the slab; results must match the
    untiered run batch-for-batch (any stale-block read would diverge) and
    the driver must not retrace post-warmup."""
    hg = grid_road(32, seed=3)
    n = hg.n
    rng = np.random.default_rng(5)
    # growth phase: long-range inserts force fresh tiles (ladder growth);
    # shrink phase: delete exactly those edges again
    grow = [rng.integers(0, n, (24, 2)) for _ in range(3)]
    stream = [(np.zeros((0, 2), np.int64), g) for g in grow]
    stream += [(g, np.zeros((0, 2), np.int64)) for g in reversed(grow)]
    budget = _pool_bytes(hg) // 2
    tiered, st_t = _run_stream(hg, _cfg(budget), stream)
    plain, st_p = _run_stream(hg, _cfg(None), stream)
    assert all(s.converged for s in st_t)
    linf = float(np.max(np.abs(np.asarray(tiered.ranks)
                               - np.asarray(plain.ranks))))
    assert linf < ABANDON_TOL, linf
    rep = tiered.report()
    assert rep.retraces_post_warmup == 0
    assert rep.tiering["evictions"] > 0
    # the scrubber cross-checks slab tiles against host truth — a stale
    # resident block would fail the CRC here
    assert tiered.hot.scrub() == []
    tiered.close(), plain.close()


# ---------------------------------------------------------------------------
# memory audit (satellite)
# ---------------------------------------------------------------------------

def test_memory_audit_components_sane():
    hg = grid_road(32, seed=7)
    budget = _pool_bytes(hg) // 2
    sess, _ = _run_stream(hg, _cfg(budget), _local_stream(hg.n, 2))
    rep = sess.report()
    db = rep.device_bytes
    for comp in ("ranks", "tile_pool", "slot_tables", "operand_mirrors"):
        assert comp in db and db[comp] > 0, comp
    # the device tile pool is the bounded slab, not the host pool
    assert db["tile_pool"] <= budget
    assert db["tile_pool"] == rep.tiering["slab_bytes"]
    assert rep.bytes_per_vertex == pytest.approx(
        sum(db.values()) / sess.n)
    # untiered twin holds the whole pool on device
    plain, _ = _run_stream(hg, _cfg(None), _local_stream(hg.n, 2))
    assert plain.report().device_bytes["tile_pool"] > db["tile_pool"]
    sess.close(), plain.close()


# ---------------------------------------------------------------------------
# durability / fork / integrity
# ---------------------------------------------------------------------------

def test_save_restore_budget_independent(tmp_path):
    """Checkpoints serialize host truth: a session saved under one budget
    restores bit-identically under another (or untiered)."""
    hg = grid_road(32, seed=7)
    sess, _ = _run_stream(hg, _cfg(_pool_bytes(hg) // 2),
                          _local_stream(hg.n, 2))
    d = str(tmp_path / "ckpt")
    sess.save(d)
    ref = np.asarray(sess.ranks).copy()
    for cfg in (_cfg(_pool_bytes(hg)), _cfg(None)):
        back = PageRankSession.restore(d, config=cfg)
        np.testing.assert_array_equal(np.asarray(back.ranks), ref)
        # restored session must keep streaming under its new budget
        dels, ins = _local_stream(hg.n, 1, seed=99)[0]
        assert back.update(dels, ins).stats.converged
        back.close()
    sess.close()


def test_fork_isolated():
    hg = grid_road(32, seed=7)
    sess, _ = _run_stream(hg, _cfg(_pool_bytes(hg) // 2),
                          _local_stream(hg.n, 1))
    child = sess.fork()
    before = np.asarray(child.ranks).copy()
    dels, ins = _local_stream(hg.n, 1, seed=42)[0]
    sess.update(dels, ins)
    np.testing.assert_array_equal(np.asarray(child.ranks), before)
    assert child.update(dels, ins).stats.converged
    child.close(), sess.close()


def test_verify_scrubs_host_tier():
    """The integrity scrubber's checksum twin is the HOST tier: a tiered
    session must scrub clean through verify() (mass_tol relaxed to f32
    scale — the default is calibrated for f64 sessions)."""
    hg = grid_road(32, seed=7)
    cfg = EngineConfig(engine="pallas", tau=TAU, block_size=64,
                       dtype="float32",
                       device_budget_bytes=_pool_bytes(hg) // 2,
                       integrity={"mass_tol": 1e-4})
    sess, _ = _run_stream(hg, cfg, _local_stream(hg.n, 2))
    rep = sess.verify()
    assert rep.ok, rep
    assert rep.checks_run > 0
    sess.close()


# ---------------------------------------------------------------------------
# int32 index diet (satellite)
# ---------------------------------------------------------------------------

def test_snapshot_indices_are_int32():
    g = grid_road(16, seed=0).snapshot(block_size=64)
    for name in ("src", "dst", "osrc", "odst"):
        assert np.asarray(getattr(g, name)).dtype == np.int32, name


def test_snapshot_overflow_guard_fires_before_allocation():
    hg = grid_road(16, seed=0)
    with pytest.raises(OverflowError, match="padded edge capacity"):
        hg.snapshot(block_size=64, edge_capacity=2**31)
    # vertex-count guard: fabricate a too-wide HostGraph header without
    # materializing edges (the guard must fire before any allocation)
    wide = HostGraph.__new__(HostGraph)
    wide.n = 2**31
    wide._keys = np.zeros(0, np.int64)
    with pytest.raises(OverflowError, match="padded vertex count"):
        wide.snapshot(block_size=64)


# ---------------------------------------------------------------------------
# chunked R-MAT (satellite)
# ---------------------------------------------------------------------------

def test_rmat_chunked_matches_monolithic():
    for seed in (0, 5):
        mono = rmat(8, 4, seed=seed)
        for chunk in (64, 1000, 1 << 20):   # many chunks / ragged / single
            chunked = rmat(8, 4, seed=seed, chunk_edges=chunk)
            assert chunked.n == mono.n
            np.testing.assert_array_equal(chunked.edges, mono.edges)


def test_rmat_chunk_edges_validated():
    with pytest.raises(ValueError, match="chunk_edges"):
        rmat(6, 4, chunk_edges=0)


# ---------------------------------------------------------------------------
# EdgePager: the blocked oracle's paged twin
# ---------------------------------------------------------------------------

def test_edge_pager_parity_exact():
    """Paged run_blocked must equal the unpaged run bitwise — the pager
    relocates slices, it never changes them."""
    hg = rmat(8, 4, seed=3)
    g = hg.snapshot(block_size=64)
    R0 = jnp.full((g.n_pad,), np.float32(1.0 / g.n))
    for mode in ("lf", "bb"):
        base, st0 = blk.run_blocked(g, R0, g.vertex_valid, mode=mode,
                                    tau=TAU, active_policy="rc")
        pager = tiering.EdgePager(g, budget_bytes=1 << 26)
        paged, st1 = blk.run_blocked(
            tiering.paged_snapshot(g), R0, g.vertex_valid, mode=mode,
            tau=TAU, active_policy="rc", pager=pager)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(paged))
        assert st1.converged == st0.converged
        assert pager.counters["misses"] > 0


def test_edge_pager_repack_and_slab_content():
    """Drive the repack path directly: a slab sized for half the blocks is
    cycled between two disjoint working sets.  Staged slab slices must
    equal the host CSR slices (address translation only, never content)."""
    g = rmat(8, 4, seed=3).snapshot(block_size=64)
    in_ptr = np.asarray(g.in_block_ptr, np.int64)
    out_ptr = np.asarray(g.out_block_ptr, np.int64)
    sizes = np.maximum(np.diff(in_ptr), np.diff(out_ptr))  # staging need
    floor = int((np.diff(in_ptr) + np.diff(out_ptr)).max())  # ctor floor
    n_blk = len(sizes)
    half = np.arange(n_blk // 2)
    rest = np.arange(n_blk // 2, n_blk)
    budget = (int(max(sizes[half].sum(), sizes[rest].sum(),
                      floor + 1)) + 8) * 16
    pager = tiering.EdgePager(g, budget_bytes=budget)

    def check(ids):
        pager.ensure(ids)
        src = np.asarray(g.src)
        for b in ids.tolist():
            lo, ln = int(pager._in_lo[b]), int(pager._in_len[b])
            np.testing.assert_array_equal(
                pager._hsrc[lo:lo + ln], src[in_ptr[b]:in_ptr[b + 1]])

    check(half)
    check(half)                 # all resident: pure hits
    assert pager.counters["hits"] > 0
    check(rest)                 # evicts the first set (repack)
    check(half)                 # and back
    assert pager.counters["repacks"] >= 1
    assert pager.counters["evictions"] >= 1
    # a want set that cannot fit even alone raises with the sizing rule
    with pytest.raises(ValueError, match="does not fit the edge slab"):
        pager.ensure(np.arange(n_blk))


def test_edge_pager_budget_floor_raises():
    g = rmat(7, 4, seed=1).snapshot(block_size=64)
    with pytest.raises(ValueError, match="raise the budget"):
        tiering.EdgePager(g, budget_bytes=16)


def test_tiered_on_the_kernel_matches_the_xla_tile_path():
    """Under a budget, non-resident slots point at the slab's zero tile and
    count as live, so the Pallas walk (here in the TPU interpreter) copies
    them: the streamed ranks match the same session on the XLA tile path."""
    hg = grid_road(16, seed=7)
    dels, ins = random_batch(hg, 8 / hg.m, seed=60)
    r0 = jnp.asarray(pr.numpy_reference(hg.snapshot(block_size=64),
                                        iterations=200), jnp.float32)
    ranks = {}
    for backend in ("pallas", "xla"):
        cfg = EngineConfig(engine="pallas", tau=1e-4 / hg.n, block_size=64,
                           dtype="float32", backend=backend,
                           device_budget_bytes=_pool_bytes(hg) * 3 // 4)
        sess = PageRankSession.from_graph(hg, config=cfg, r0=r0)
        assert sess.backend == backend
        assert sess.update(dels, ins).converged
        assert sess.report().tiering["misses"] > 0
        ranks[backend] = np.asarray(sess.ranks)
        sess.close()
    np.testing.assert_allclose(ranks["pallas"], ranks["xla"], rtol=0,
                               atol=1e-6 * float(ranks["xla"].max()))
