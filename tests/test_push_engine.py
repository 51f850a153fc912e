"""Residual forward-push driver (repro.core.push_engine, ISSUE 10).

Covers the driver="push" tentpole through the public surface:

* cold solve and streamed delete/insert batches reach the pull driver's
  fixed point (vs the independent numpy oracle AND a per-batch blocked
  df oracle) at equal L∞;
* the exact invariant ``r = b + M·p − p`` holds bit-tight after every
  O(batch) residual seed — the correctness core of the scheme;
* push does strictly less edge work than pull on the same stream (the
  ≥5× smoke-scenario gate lives in tests/test_bench_smoke.py);
* zero post-warmup retraces on the push driver's own jit cache;
* tiering composes: at ``device_budget_bytes = pool/2`` pushed-to
  non-resident rows defer into the refill bitmap (never a mid-sweep
  sync), the final state is parity-clean and the counters land in
  ``report().tiering`` — the ISSUE 10 acceptance criterion;
* work accounting: per-batch sweeps/edges history plus the push-only
  ``residual_mass_last`` / ``pushed_blocks`` in ``report()`` and the
  service per-slot rows (satellite);
* config validation, the dt/recompute contract, delete+reinsert, and
  the always-running push-vs-pull fixed-point property across seeds and
  graph families (hypothesis form in tests/test_properties.py).
"""
import warnings

import numpy as np
import pytest
import jax.numpy as jnp

from repro.api import (EngineConfig, IntegrityConfig, PageRankService,
                       PageRankSession, SweepCapWarning)
from repro.core import frontier as fr
from repro.core import pagerank as pr
from repro.core import push_engine as pshe
from repro.core import tiering
from repro.core.delta import random_batch
from repro.core.stream import run_stream
from repro.graphs.generators import grid_road, kmer_chains, powerlaw, rmat

ALPHA = 0.85
TAU = 1e-10
# both drivers stop at per-vertex residual/change <= tau, so each sits
# within ||r||_1 * a/(1-a) <= n * tau * a/(1-a) of the fixed point
def _bound(n):
    return n * TAU * ALPHA / (1.0 - ALPHA)


def _cfg(driver="push", budget=None, **kw):
    return EngineConfig(engine="pallas", block_size=64, driver=driver,
                        device_budget_bytes=budget, **kw)


def _pool_bytes(hg, block_size=64, dtype=np.float64):
    g0 = hg.snapshot(block_size=block_size)
    src, dst = g0.in_edges_host()
    pool = tiering.HostTilePool.from_edges(
        dst, src, g0.n_pad, g0.n_pad, block=block_size,
        dtype=np.dtype(dtype))
    return int(pool.nbytes)


def _stream(hg, k, *, rate=None, seed=50):
    batches, cur = [], hg
    for i in range(k):
        dels, ins = random_batch(cur, rate or 8 / cur.m, seed=seed + i)
        batches.append((dels, ins))
        cur = cur.apply_batch(dels, ins)
    return batches, cur


def _host_residual(sess):
    """The exact invariant residual from host truth (the yardstick the
    device-resident ``_residual`` must track)."""
    return pshe.residual_from_host(
        sess.hg, sess._out_deg_host, np.asarray(sess.R),
        float(sess.config.alpha))


# ---------------------------------------------------------------------------
# config + construction contract
# ---------------------------------------------------------------------------

class TestConfigValidation:
    def test_unknown_driver_rejected(self):
        with pytest.raises(ValueError, match="driver='spin' invalid"):
            EngineConfig(driver="spin")

    def test_push_requires_pallas(self):
        with pytest.raises(ValueError, match="pallas"):
            EngineConfig(engine="dense", driver="push")

    def test_push_requires_lf_mode(self):
        with pytest.raises(ValueError, match="mode must be 'lf'"):
            EngineConfig(engine="pallas", mode="bb", driver="push")

    def test_push_rejects_integrity(self):
        with pytest.raises(ValueError, match="integrity"):
            EngineConfig(engine="pallas", driver="push",
                         integrity=IntegrityConfig())

    def test_push_requires_stream_session(self):
        g = rmat(7, avg_degree=4, seed=0).snapshot(block_size=64)
        with pytest.raises(ValueError, match="from_graph"):
            PageRankSession.from_snapshot(g, config=_cfg())

    def test_driver_defaults_to_pull(self):
        assert EngineConfig().driver == "pull"


# ---------------------------------------------------------------------------
# fixed-point parity + the residual invariant
# ---------------------------------------------------------------------------

def test_cold_solve_matches_reference():
    hg = rmat(9, avg_degree=6, seed=3)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    ref = pr.numpy_reference(hg.snapshot(block_size=64), iterations=300)
    assert float(pr.linf(sess.R[:hg.n], jnp.asarray(ref[:hg.n]))) \
        < _bound(hg.n)
    # at exit every residual entry is at/below tolerance (or the ulp floor)
    assert float(np.abs(np.asarray(sess._residual)).max()) < 4 * TAU
    sess.close()


def test_invariant_exact_across_updates():
    """r = b + M·p − p must hold to fp-accumulation accuracy after every
    O(batch) seed + drive — deletions included.  This is the load-bearing
    invariant: parity, tiered staleness repair and the a-posteriori error
    bound all derive from it."""
    hg = rmat(8, avg_degree=5, seed=7)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    batches, _ = _stream(hg, 4, rate=3e-2, seed=90)
    for dels, ins in batches:
        res = sess.update(dels, ins)
        assert res.converged
        drift = np.abs(np.asarray(sess._residual) - _host_residual(sess))
        assert float(drift.max()) < 1e-12, float(drift.max())
    sess.close()


def test_stream_matches_blocked_df_oracle():
    """Per-batch parity against the pull df oracle (the blocked-engine
    lineage test_stream.py runs for the pull driver) at equal L∞."""
    hg = rmat(9, avg_degree=6, seed=3)
    g = hg.snapshot(block_size=64)
    r0 = jnp.asarray(pr.numpy_reference(g, iterations=300))
    batches, cur = _stream(hg, 3, rate=5e-3, seed=100)
    sess = PageRankSession.from_graph(hg, config=_cfg(), r0=r0)
    r_ref, prev = r0, hg
    for dels, ins in batches:
        res = sess.update(dels, ins)
        g_prev = prev.snapshot(block_size=64)
        prev = prev.apply_batch(dels, ins)
        g_new = prev.snapshot(block_size=64)
        oracle = pr.df_pagerank(
            g_prev, g_new, fr.batch_to_device(g_new, dels, ins), r_ref,
            mode="lf", engine="pallas")
        r_ref = oracle.ranks
        assert res.stats.converged
        assert float(pr.linf(res.ranks, oracle.ranks)) < 2 * _bound(hg.n)
    ref = pr.numpy_reference(cur.snapshot(block_size=64), iterations=300)
    assert float(pr.linf(sess.R[:cur.n], jnp.asarray(ref[:cur.n]))) < 1e-8
    sess.close()


def test_delete_then_reinsert_returns_to_fixed_point():
    hg = kmer_chains(1 << 9, seed=4)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    before = np.asarray(sess.R).copy()
    rng = np.random.default_rng(5)
    pick = rng.choice(hg.m, size=12, replace=False)
    edges = np.stack([hg._keys[pick] // hg.n,
                      hg._keys[pick] % hg.n], axis=1)
    assert sess.update(edges, np.zeros((0, 2), np.int64)).converged
    assert sess.update(np.zeros((0, 2), np.int64), edges).converged
    back = np.asarray(sess.R)
    assert float(np.abs(back - before).max()) < 2 * _bound(hg.n)
    sess.close()


# ---------------------------------------------------------------------------
# work + retrace accounting
# ---------------------------------------------------------------------------

def test_zero_retraces_and_less_edge_work_than_pull():
    hg = kmer_chains(1 << 10, seed=4)
    g = hg.snapshot(block_size=64)
    r0 = jnp.asarray(pr.numpy_reference(g, iterations=300))
    batches, cur = _stream(hg, 4, seed=70)
    reps = {d: run_stream(hg, batches, block_size=64, r0=r0,
                          active_policy="rc", driver=d)
            for d in ("pull", "push")}
    ref = pr.numpy_reference(cur.snapshot(block_size=64), iterations=300)
    edges = {}
    for d, rep in reps.items():
        assert rep.retraces_post_warmup == 0, d
        assert all(r.stats.converged for r in rep.results), d
        assert float(pr.linf(rep.final_ranks[:cur.n],
                             jnp.asarray(ref[:cur.n]))) < 1e-8, d
        edges[d] = sum(r.stats.edges_processed for r in rep.results)
    # work ∝ residual mass beats frontier × sweeps on every stream; the
    # scenario-specific ≥5× gate is asserted on the committed smoke record
    assert edges["push"] < edges["pull"], edges


def test_report_work_accounting():
    hg = rmat(8, avg_degree=5, seed=7)
    batches, _ = _stream(hg, 3, rate=2e-2, seed=20)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    for dels, ins in batches:
        res = sess.update(dels, ins)
        assert res.residual_mass is not None and res.residual_mass >= 0
        assert res.pushed_blocks is not None and res.pushed_blocks > 0
    rep = sess.report()
    assert rep.driver == "push"
    assert len(rep.sweeps_history) == 3
    assert len(rep.edges_processed_history) == 3
    assert rep.edges_processed_history == [
        r.stats.edges_processed for r in sess._history]
    assert rep.residual_mass_last is not None
    assert rep.pushed_blocks is not None and rep.pushed_blocks > 0
    sess.close()

    pull = PageRankSession.from_graph(hg, config=_cfg(driver="pull"))
    pull.update(*batches[0])
    prep = pull.report()
    assert prep.driver == "pull"
    assert len(prep.sweeps_history) == 1
    assert prep.residual_mass_last is None and prep.pushed_blocks is None
    pull.close()


def test_service_rows_expose_driver_accounting():
    hg = rmat(8, avg_degree=5, seed=11)
    svc = PageRankService(
        [PageRankSession.from_graph(hg, config=_cfg()),
         PageRankSession.from_graph(hg, config=_cfg(driver="pull"))],
        warmup=False)
    batches, _ = _stream(hg, 2, rate=1e-2, seed=31)
    # drain between submits: continuous dispatch coalesces queued batches,
    # which would fold both updates into one history entry
    for dels, ins in batches:
        for s in (0, 1):
            svc.submit(s, dels, ins)
        svc.run_until_drained()
    rows = svc.report()["sessions"]
    assert rows[0]["driver"] == "push"
    assert rows[0]["pushed_blocks"] > 0
    assert rows[0]["residual_mass_last"] is not None
    assert len(rows[0]["sweeps_history"]) == 2
    assert rows[1]["driver"] == "pull"
    assert "pushed_blocks" not in rows[1]
    for row in rows:
        assert len(row["edges_processed_history"]) == 2
        assert row["total_edges_processed"] == \
            sum(row["edges_processed_history"])
    svc.stop(drain=False)


# ---------------------------------------------------------------------------
# recompute / variant contract
# ---------------------------------------------------------------------------

def test_dt_update_and_pull_recompute_variants_rejected():
    hg = rmat(7, avg_degree=4, seed=2)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    dels, ins = random_batch(hg, 1e-2, seed=8)
    with pytest.raises(ValueError, match="dt"):
        sess.update(dels, ins, variant="dt")
    for variant in ("df", "dt"):
        with pytest.raises(ValueError, match="static' or 'nd"):
            sess.recompute(variant)
    sess.close()


def test_recompute_nd_and_static_resolve():
    hg = rmat(8, avg_degree=5, seed=9)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    ref = pr.numpy_reference(hg.snapshot(block_size=64), iterations=300)
    for variant in ("nd", "static"):
        out = sess.recompute(variant)
        assert out.stats.converged
        assert float(pr.linf(out.ranks[:hg.n], jnp.asarray(ref[:hg.n]))) \
            < _bound(hg.n), variant
    sess.close()


def test_nd_update_rebuilds_residual():
    hg = rmat(8, avg_degree=5, seed=9)
    sess = PageRankSession.from_graph(hg, config=_cfg())
    dels, ins = random_batch(hg, 2e-2, seed=3)
    assert sess.update(dels, ins, variant="nd").converged
    drift = np.abs(np.asarray(sess._residual) - _host_residual(sess))
    assert float(drift.max()) < 1e-12
    sess.close()


# ---------------------------------------------------------------------------
# tiering composition — the ISSUE 10 acceptance criterion
# ---------------------------------------------------------------------------

def test_tiered_half_budget_parity_and_counters():
    """driver='push' under device_budget_bytes = pool/2: pushed-to
    non-resident rows defer into the refill bitmap (never a mid-sweep
    sync), the refill loop drains every batch, the final state is
    parity-clean vs the untiered push session, and the tiering counters
    are visible in report()."""
    hg = grid_road(32, seed=7)
    budget = _pool_bytes(hg) // 2
    batches, cur = _stream(hg, 3, rate=4e-3, seed=41)

    with warnings.catch_warnings():
        warnings.simplefilter("error", SweepCapWarning)
        tiered = PageRankSession.from_graph(hg, config=_cfg(budget=budget))
        plain = PageRankSession.from_graph(hg, config=_cfg())
        tiered.warmup(), plain.warmup()
        for dels, ins in batches:
            assert tiered.update(dels, ins).converged
            assert plain.update(dels, ins).converged

    linf = float(np.abs(np.asarray(tiered.ranks)
                        - np.asarray(plain.ranks)).max())
    assert linf < 2 * _bound(hg.n), linf
    ref = pr.numpy_reference(cur.snapshot(block_size=64), iterations=300)
    assert float(pr.linf(tiered.R[:cur.n], jnp.asarray(ref[:cur.n]))) \
        < _bound(cur.n)

    rep = tiered.report()
    t = rep.tiering
    assert t is not None
    assert t["misses"] > 0                 # budget pressure was real
    assert t["refill_drives"] > 0          # deferrals happened and drained
    assert t["slab_bytes"] <= budget
    assert rep.retraces_post_warmup == 0
    # random insertions may grow the tile pool past a capacity bucket —
    # that first-visit compile is the legitimate, separately-counted kind
    assert rep.bucket_retraces_post_warmup <= 1
    assert rep.device_bytes["tile_pool"] <= budget
    # tiered invariant repair is exact too: host-truth residual agrees on
    # every resident row (stale rows sit in the deferred bitmap — drained)
    drift = np.abs(np.asarray(tiered._residual) - _host_residual(tiered))
    assert float(drift.max()) < 1e-12
    tiered.close(), plain.close()


# ---------------------------------------------------------------------------
# push-vs-pull fixed point across graph families (always-running form of
# the tests/test_properties.py hypothesis property)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,seed", [
    ("rmat", 1), ("rmat", 5), ("powerlaw", 2), ("kmer", 3),
])
def test_push_pull_same_fixed_point(family, seed):
    hg = {"rmat": lambda: rmat(8, avg_degree=5, seed=seed),
          "powerlaw": lambda: powerlaw(300, avg_degree=6, seed=seed),
          "kmer": lambda: kmer_chains(400, seed=seed)}[family]()
    batches, cur = _stream(hg, 2, rate=2e-2, seed=seed * 13 + 1)
    # append a delete+reinsert pair of an original edge
    e = np.array([[int(hg._keys[0] // hg.n), int(hg._keys[0] % hg.n)]],
                 np.int64)
    zero = np.zeros((0, 2), np.int64)
    batches += [(e, zero), (zero, e)]
    cur = cur.apply_batch(e, zero).apply_batch(zero, e)

    finals = {}
    for driver in ("pull", "push"):
        sess = PageRankSession.from_graph(hg, config=_cfg(driver=driver))
        for dels, ins in batches:
            assert sess.update(dels, ins).converged, driver
        finals[driver] = np.asarray(sess.R[:hg.n]).copy()
        sess.close()
    gap = float(np.abs(finals["push"] - finals["pull"]).max())
    assert gap < 2 * _bound(hg.n), (family, seed, gap)


def test_push_on_the_kernel_matches_the_xla_tile_path():
    """The push driver's pull-layout SpMV runs the Pallas live-tile walk
    (here in the TPU interpreter): its streamed ranks match the same
    session on the XLA tile path."""
    hg = grid_road(16, seed=7)
    batches, _ = _stream(hg, 2, rate=8 / hg.m, seed=60)
    r0 = jnp.asarray(pr.numpy_reference(hg.snapshot(block_size=64),
                                        iterations=200), jnp.float32)
    ranks = {}
    for backend in ("pallas", "xla"):
        sess = PageRankSession.from_graph(hg, config=_cfg(
            tau=1e-6 / hg.n, dtype="float32", backend=backend), r0=r0)
        assert sess.backend == backend
        for dels, ins in batches:
            assert sess.update(dels, ins).converged
        ranks[backend] = np.asarray(sess.ranks)
        sess.close()
    np.testing.assert_allclose(ranks["pallas"], ranks["xla"], rtol=0,
                               atol=1e-6 * float(ranks["xla"].max()))
